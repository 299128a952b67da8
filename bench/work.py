"""Work and bytes of a sweep grid, from its shape alone.

A grid cell commits `total_steps` instructions, whatever engine serves
it, so a grid's simulated work is its real (unpadded) cells times
`total_steps`; it equals the sum of the grid's `instructions` counters.
The least bytes of the window pass are those of its streams and
counters: each committed access reads one (tag, cost) pair of int32
words, and each cell writes its counters once (four per program, one
switch count), whichever implementation runs it.
"""
from __future__ import annotations

import math

WORD = 4


def sweep_instructions(shape, total_steps: int) -> int:
    """Instructions one grid of `shape` (its cell axes) commits."""
    return math.prod(shape) * int(total_steps)


def window_kernel_bytes(shape, num_programs: int, total_steps: int) -> int:
    cells = math.prod(shape)
    return cells * (2 * WORD * int(total_steps)
                    + WORD * (4 * num_programs + 1))
