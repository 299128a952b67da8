"""The plain reference: the reconfigurable-slot core, step by step.

One instruction retires per step.  The running program's next
instruction is looked up in the disambiguator, an LRU cache of `slots`
entries keyed by the instruction's tag (tag -1: hardwired, never
slotted, never a miss).  A miss costs `miss_latency` cycles and fetches
the bitstream through the bitstream cache, a second LRU cache of
`bs_entries` entries; a miss there costs `bs_extra` more.  The step's
cost adds to the current quantum; once the quantum reaches the program's
`quantum`, the timer fires, the program pays `handler` cycles more and
the round robin moves to the next program.  Caches persist across
switches.  Counters are per program: cycles, instructions, slot misses,
bitstream misses; switches are per cell.

Each cell is a `lax.scan` over steps in plain `jax.numpy`, and a grid is
those cells side by side under `vmap`.  This imports nothing of the
program.  `policy="fifo"` breaks the configuration's LRU guarantee (a hit
no longer refreshes an entry): it is the control that the comparison has
to fail.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

BIG = np.iinfo(np.int32).max


class Cell(NamedTuple):
    """One cell's state: cache residency flags and stamps over the tag
    space (last use under LRU, fill under FIFO), the programs' trace
    cursors, the running program, the quantum so far, and the counters
    of this run."""

    slot_in: jnp.ndarray      # (T,) bool
    slot_t: jnp.ndarray       # (T,)
    bs_in: jnp.ndarray        # (T,) bool
    bs_t: jnp.ndarray         # (T,)
    cursors: jnp.ndarray      # (P,)
    prog: jnp.ndarray         # ()
    q_cycles: jnp.ndarray     # ()
    clock: jnp.ndarray        # ()
    cycles: jnp.ndarray       # (P,)
    instructions: jnp.ndarray  # (P,)
    slot_misses: jnp.ndarray  # (P,)
    bs_misses: jnp.ndarray    # (P,)
    switches: jnp.ndarray     # ()


def cold(cells: int, num_tags: int, num_programs: int) -> Cell:
    """`cells` cells with empty caches and every cursor at 0."""
    z = lambda *s: np.zeros((cells,) + s, np.int32)
    return Cell(np.zeros((cells, num_tags), bool), z(num_tags),
                np.zeros((cells, num_tags), bool), z(num_tags),
                z(num_programs), z(), z(), z(), z(num_programs),
                z(num_programs), z(num_programs), z(num_programs), z())


def _access(present, stamp, tag, want, capacity, clock, refresh: bool):
    onehot = jnp.arange(present.shape[0]) == tag
    hit = jnp.any(present & onehot) & want
    miss = want & ~hit
    full = miss & (jnp.sum(present) >= capacity)
    victim = jnp.arange(present.shape[0]) == jnp.argmin(
        jnp.where(present, stamp, BIG))
    present = (present & ~(victim & full)) | (onehot & miss)
    touch = miss | (hit & refresh)
    return present, jnp.where(onehot & touch, clock, stamp), hit


def _cell(tags, costs, fleet, programs, slots, lat, quanta, handler,
          bs_entries, bs_extra, state: Cell, *, steps: int,
          refresh: bool) -> Cell:
    n = tags.shape[-1]

    def step(c: Cell, _):
        p = c.prog
        pos = c.cursors[p] % n
        tag = tags[fleet, p, pos]
        slotted = tag >= 0
        t = jnp.maximum(tag, 0)
        clock = c.clock + 1
        slot_in, slot_t, hit = _access(c.slot_in, c.slot_t, t, slotted,
                                       slots, clock, refresh)
        miss = slotted & ~hit
        bs_in, bs_t, bs_hit = _access(c.bs_in, c.bs_t, t, miss,
                                      bs_entries, clock, refresh)
        bs_miss = miss & ~bs_hit
        cost = (costs[fleet, p, pos] + jnp.where(miss, lat, 0)
                + jnp.where(bs_miss, bs_extra, 0))
        q = c.q_cycles + cost
        switch = q >= quanta[p]
        return Cell(
            slot_in, slot_t, bs_in, bs_t,
            c.cursors.at[p].add(1),
            jnp.where(switch, (p + 1) % programs, p),
            jnp.where(switch, 0, q), clock,
            c.cycles.at[p].add(cost + jnp.where(switch, handler, 0)),
            c.instructions.at[p].add(1),
            c.slot_misses.at[p].add(miss.astype(jnp.int32)),
            c.bs_misses.at[p].add(bs_miss.astype(jnp.int32)),
            c.switches + switch.astype(jnp.int32)), None

    return jax.lax.scan(step, state, None, length=steps)[0]


@functools.partial(jax.jit, static_argnames=("steps", "refresh"))
def _grid(tags, costs, fleet, programs, slots, lat, quanta, handler,
          bs_entries, bs_extra, state, *, steps, refresh):
    return jax.vmap(functools.partial(_cell, steps=steps, refresh=refresh),
                    in_axes=(None, None) + (0,) * 9)(
        tags, costs, fleet, programs, slots, lat, quanta, handler,
        bs_entries, bs_extra, state)


def run(tags, costs, *, fleet, programs, slots, miss_latency, quanta,
        handler, bs_entries, bs_extra, steps: int,
        policy: str = "lru") -> Cell:
    """Run every cell `steps` steps and return the cells' states.

    tags, costs: (F, P, N) per-access tag (-1 unslotted) and hardware
    cycles of each fleet's program traces; cell i runs fleet `fleet[i]`,
    each program reading its trace cyclically from its cursor.
    `programs` (cells,) is each cell's number of programs (the rest of
    the P axis is padding), `quanta` (cells, P); `slots`,
    `miss_latency`, `handler`, `bs_entries` and `bs_extra` are (cells,)
    or scalars.  Every cell starts cold: empty caches, every cursor at
    0, program 0 running.
    """
    if policy not in ("lru", "fifo"):
        raise ValueError(f"unknown policy {policy!r}")
    tags = np.asarray(tags, np.int32)
    fleet = np.asarray(fleet, np.int32)
    cells, p = fleet.shape[0], tags.shape[1]
    vec = lambda x: np.broadcast_to(np.asarray(x, np.int32), (cells,))
    state = cold(cells, max(int(tags.max()) + 1, 1), p)
    per_cell = [fleet, vec(programs), vec(slots), vec(miss_latency),
                np.asarray(quanta, np.int32), vec(handler), vec(bs_entries),
                vec(bs_extra), Cell(*(np.asarray(x) for x in state))]
    # cells are independent: spread them over every device of the run,
    # padded with copies of the first to a whole number per device
    devs = jax.devices()
    pad = -cells % len(devs)
    mesh = jax.sharding.Mesh(np.array(devs), ("cells",))
    split = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
        "cells"))
    per_cell = jax.tree.map(
        lambda x: jax.device_put(np.concatenate(
            [x, np.repeat(x[:1], pad, axis=0)]), split), per_cell)
    out = _grid(jnp.asarray(tags), jnp.asarray(costs, jnp.int32),
                *per_cell, steps=int(steps), refresh=policy == "lru")
    return Cell(*(np.asarray(x)[:cells] for x in out))
