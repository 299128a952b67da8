"""A design-space sweep through `simulator.sweep_fleet`: every k-way
fleet of the configuration's benches over a {quanta x slot counts x miss
latencies} grid, one grid per call, back to back.

Traffic keys: `fleet_size` (k), `trace_len`, `total_steps`, `quanta`,
`slot_counts`, `miss_latencies`.  The traces are synthesised from the
seed; call i rotates every trace by its own offset, drawn from the seed,
so no two calls of a window see the same input and each still does the
same work.  The check compares every counter of every cell of one call,
drawn from the seed among those of the window, with the plain
reference, and that every cell of every call committed `total_steps`
instructions.
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import reference, work  # noqa: E402
from bench.embench import Core  # noqa: E402

FIELDS = ("cycles", "instructions", "slot_misses", "bs_misses", "switches")


def offsets(seed: int, count: int, n: int) -> np.ndarray:
    """The rotation of call i's traces, for i < count, from the seed."""
    return np.random.default_rng([seed % (1 << 64), 1]).integers(
        0, n, size=count)


def sample(seed: int, calls: int) -> list[int]:
    """The calls the reference checks: one drawn from the seed."""
    return [int(np.random.default_rng([seed % (1 << 64), 2]).integers(
        calls))]


class Cell:
    unit = "grid"

    def __init__(self, spec, seed: int):
        import jax
        import jax.numpy as jnp

        from repro.core import isa, simulator

        t = spec.traffic
        self.seed = seed
        self.core = Core(spec.config_path)
        geo = self.core.core
        self.fleets = self.core.fleets(t["fleet_size"])
        self.trace_len = t["trace_len"]
        self.total_steps = t["total_steps"]
        self.quanta = list(t["quanta"])
        self.slot_counts = list(t["slot_counts"])
        self.latencies = list(t["miss_latencies"])
        self.bs_entries = geo["bs_cache_entries"]
        self.bs_extra = geo["bs_miss_extra"]
        self.handler = geo["handler_cycles"]
        names = sorted({n for f in self.fleets for n in f})
        traces = {n: self.core.trace(n, self.trace_len, seed) for n in names}
        self.traces = np.stack([np.stack([traces[n] for n in f])
                                for f in self.fleets])
        self.shape = (len(self.quanta), len(self.fleets),
                      len(self.slot_counts), len(self.latencies))
        self.work = work.sweep_instructions(self.shape, self.total_steps)
        self.kernel_bytes = work.window_kernel_bytes(
            self.shape, t["fleet_size"], self.total_steps)
        self._offsets = offsets(seed, 1 << 16, self.trace_len)
        self._scenario = isa.SlotScenario("bench", geo["num_slots"],
                                          self.core.tags)
        self._sched = simulator.SchedulerConfig(
            handler_cycles=self.handler)
        self._sweep = simulator.sweep_fleet
        self._base = jax.device_put(self.traces)
        self._roll = jax.jit(lambda x, s: jnp.roll(x, s, axis=-1))

    def _inputs(self, shift):
        return self._roll(self._base, np.int32(shift))

    def call(self, i: int):
        return self._sweep(
            self._inputs(self._offsets[i]), self.latencies,
            self._scenario, self._sched, slot_counts=self.slot_counts,
            quanta=self.quanta, bs_cache_entries=self.bs_entries,
            bs_miss_extra=self.bs_extra, total_steps=self.total_steps)

    def warm(self):
        import jax
        jax.block_until_ready(self.call(len(self._offsets) - 1))

    def release(self):
        self._base = None

    def reference(self, shift: int, policy: str = "lru") -> dict:
        """The grid the reference gives for traces rotated by `shift`,
        as (Q, B, K, L, P) arrays and (Q, B, K, L) switches."""
        rolled = np.roll(self.traces, shift, axis=-1)
        q, b, k, l = self.shape
        grid = np.indices(self.shape).reshape(4, -1)
        p = self.traces.shape[1]
        out = reference.run(
            self.core.tags[rolled], self.core.hw_cycles[rolled],
            fleet=grid[1], programs=p,
            slots=np.asarray(self.slot_counts)[grid[2]],
            miss_latency=np.asarray(self.latencies)[grid[3]],
            quanta=np.repeat(np.asarray(self.quanta)[grid[0]][:, None], p,
                             axis=1),
            handler=self.handler, bs_entries=self.bs_entries,
            bs_extra=self.bs_extra, steps=self.total_steps, policy=policy)
        return {f: getattr(out, f).reshape(self.shape + (-1,) if f !=
                                           "switches" else self.shape)
                for f in FIELDS}

    def check(self, outputs):
        """[(name, value, limit)] and the number of calls found wrong."""
        got = [{f: np.asarray(getattr(o, f)) for f in FIELDS}
               for o in outputs]
        wrong = set()
        short = 0
        for i, g in enumerate(got):
            bad = int(np.sum(g["instructions"].sum(axis=-1)
                             != self.total_steps))
            short += bad
            if bad:
                wrong.add(i)
        mism = 0
        for i in sample(self.seed, len(got)):
            want = self.reference(int(self._offsets[i]))
            bad = sum(int(np.sum(got[i][f] != want[f])) for f in FIELDS)
            mism += bad
            if bad:
                wrong.add(i)
        return ([("counter_mismatches", mism, 0),
                 ("cells_wrong_total", short, 0)], len(wrong))
