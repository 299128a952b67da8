"""A design-space sweep through `simulator.sweep_fleet` pinned to one
engine: `sweep_fleet.py`'s cell, whose calls pass the traffic's `path`.

The cell measures one engine, and the program raises rather than fall
back to another where that engine cannot serve the grid: a program
without it fails the cell at once instead of timing a slower engine
under the cell's name.  Traffic keys: those of `sweep_fleet.py`, and
`path`.  The inputs, the reference, the check and the work counts are
`sweep_fleet.py`'s.
"""
from __future__ import annotations

import functools
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_kinds_sweep_fleet",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "sweep_fleet.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)


class Cell(_base.Cell):
    def __init__(self, spec, seed: int):
        super().__init__(spec, seed)
        self.path = spec.traffic["path"]
        # every call of the window and of the warm-up goes to this engine
        self._sweep = functools.partial(self._sweep, path=self.path)
