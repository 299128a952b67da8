"""The bitstream-cache study through `simulator.sweep_bitstream`: solo
programs, unpreempted, over a {slot counts x miss latencies x bitstream
capacities x L2 penalties} grid, one grid per call, back to back.

Traffic keys: `benches` ("fm" or "m": the configuration's bench pool),
`trace_len`, `total_steps`, `slot_counts`, `miss_latencies`,
`bs_entries`, `bs_miss_extras`.  Call i rotates every trace by its own
offset from the seed, as in `sweep_fleet`.  The check compares every
counter of every cell of one call, drawn from the seed, with the plain
reference run cell by cell, and holds every cell of
every call to the identity of an unpreempted run: cycles = the stream's
hardware cycles + slot misses x latency + bitstream misses x penalty.
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import reference, work  # noqa: E402
from bench.embench import Core  # noqa: E402
from bench.kinds.sweep_fleet import offsets, sample  # noqa: E402

NEVER = 1 << 30   # a quantum no run reaches: no preemption


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
        self.benches = {"fm": self.core.fm_benches,
                        "m": self.core.m_benches}[t["benches"]]
        self.trace_len = t["trace_len"]
        self.total_steps = t["total_steps"]
        self.axes = [list(t[k]) for k in ("slot_counts", "miss_latencies",
                                          "bs_entries", "bs_miss_extras")]
        self.traces = np.stack([self.core.trace(n, self.trace_len, seed)
                                for n in self.benches])
        self.shape = (len(self.benches),) + tuple(len(a) for a in self.axes)
        self.work = work.sweep_instructions(self.shape, self.total_steps)
        self._offsets = offsets(seed, 1 << 16, self.trace_len)
        self._scenario = isa.SlotScenario("bench", geo["num_slots"],
                                          self.core.tags)
        self._sweep = simulator.sweep_bitstream
        self._base = jax.device_put(self.traces)
        self._roll = jax.jit(lambda x, s: jnp.roll(x, s, axis=-1))

    def call(self, i: int):
        k, l, e, x = self.axes
        return self._sweep(
            self._roll(self._base, np.int32(self._offsets[i])),
            self._scenario, slot_counts=k, miss_latencies=l, bs_entries=e,
            bs_miss_extras=x, total_steps=self.total_steps)

    def warm(self):
        import jax
        jax.block_until_ready(self.call(len(self._offsets) - 1))

    def release(self):
        self._base = None

    def _streams(self, shift: int) -> np.ndarray:
        """(B, total_steps) instruction ids each cell replays."""
        rolled = np.roll(self.traces, shift, axis=-1)
        return rolled[:, np.arange(self.total_steps) % self.trace_len]

    def reference(self, shift: int, policy: str = "lru") -> dict:
        """The grid the reference gives: (B, K, L, E, X) arrays of each
        cell's cycles, slot misses and bitstream misses."""
        rolled = np.roll(self.traces, shift, axis=-1)[:, None]
        grid = np.indices(self.shape).reshape(5, -1)
        k, l, e, x = (np.asarray(a) for a in self.axes)
        out = reference.run(
            self.core.tags[rolled], self.core.hw_cycles[rolled],
            fleet=grid[0], programs=1, slots=k[grid[1]],
            miss_latency=l[grid[2]], quanta=np.full((grid.shape[1], 1),
                                                    NEVER),
            handler=0, bs_entries=e[grid[3]], bs_extra=x[grid[4]],
            steps=self.total_steps, policy=policy)
        return {f: getattr(out, f)[:, 0].reshape(self.shape)
                for f in ("cycles", "slot_misses", "bs_misses")}

    @staticmethod
    def _cells(g) -> dict:
        """A ColdGrid broadcast to the (B, K, L, E, X) cell grid."""
        cycles = np.asarray(g.cycles).astype(np.int64)
        sm = np.asarray(g.slot_misses)[:, :, None, None, None]
        bm = np.asarray(g.bs_misses)[:, :, None, :, None]
        return {"cycles": cycles,
                "slot_misses": np.broadcast_to(sm, cycles.shape),
                "bs_misses": np.broadcast_to(bm, cycles.shape)}

    def check(self, outputs):
        """[(name, value, limit)] and the number of calls found wrong."""
        got = [self._cells(o) for o in outputs]
        hw = self.core.hw_cycles.astype(np.int64)
        _, l, _, x = (np.asarray(a, np.int64) for a in self.axes)
        wrong = set()
        off = 0
        for i, g in enumerate(got):
            base = hw[self._streams(int(self._offsets[i]))].sum(axis=1)
            want = (base[:, None, None, None, None]
                    + g["slot_misses"] * l[None, None, :, None, None]
                    + g["bs_misses"] * x[None, None, None, None, :])
            bad = int(np.sum(g["cycles"] != want))
            off += bad
            if bad:
                wrong.add(i)
        mism = 0
        for i in sample(self.seed, len(got)):
            want = self.reference(int(self._offsets[i]))
            bad = sum(int(np.sum(got[i][f] != want[f])) for f in want)
            mism += bad
            if bad:
                wrong.add(i)
        return ([("counter_mismatches", mism, 0),
                 ("cells_wrong_total", off, 0)], len(wrong))
