"""The core configuration and its Embench-IoT trace synthesis, kept with
the benchmark so that no change to the program can move the traffic.

`Core` reads a configuration file (`bench/configs/<config>.json`) and
synthesises each program's instruction trace from the calibrated mix
stored there.  The painter is the loop-structure process of the
program's trace model, copied: per superblock, hot F-group bursts,
interleaved M-group singles and one rotating cold group, painted onto a
base-instruction canvas by a numpy Generator seeded from
``crc32("<bench>:<seed>")``.  For the same bench, length and seed it
gives the very trace the program's own model gives.
"""
from __future__ import annotations

import itertools
import json
import zlib
from functools import cached_property

import numpy as np


class Core:
    """One core configuration: the instruction alphabet, the slot tags,
    the per-instruction cycles and the calibrated bench mixes."""

    def __init__(self, path: str):
        with open(path) as f:
            self.data = json.load(f)
        d = self.data
        self.name = d["name"]
        self.core = d["core"]
        self.groups = list(d["groups"])
        self.group_id = {g: i for i, g in enumerate(self.groups)}
        self.instr_names = [r[0] for r in d["instructions"]]
        self.instr_id = {n: i for i, n in enumerate(self.instr_names)}
        self.hw_cycles = np.array([r[2] for r in d["instructions"]],
                                  np.int32)
        self.tags = np.array([r[3] for r in d["instructions"]], np.int32)
        self.num_tags = int(self.tags.max()) + 1
        self.fm_benches = list(d["fm_benches"])
        self.m_benches = list(d["m_benches"])

    def fleets(self, k: int) -> list[tuple[str, ...]]:
        """All slot-competing k-way fleets: every k-subset of the FM
        benches, then every (k-1)-subset joined by one M-only bench — for
        k = 2 the paper's 50 pairs, in the paper's order."""
        fleets = list(itertools.combinations(self.fm_benches, k))
        fleets += [c + (b,) for c in itertools.combinations(
            self.fm_benches, k - 1) for b in self.m_benches]
        return fleets

    def trace(self, bench: str, length: int, seed: int) -> np.ndarray:
        b = self.data["benches"][bench]
        return self._paint(np.asarray(b["mix"], np.float64), length,
                           f"{bench}:{seed}", tuple(b["hot_f_groups"]),
                           b["cold_event_period"], b["f_run_len"],
                           b["sporadic"])

    @cached_property
    def _members(self) -> dict[str, list[int]]:
        return {g: [self.instr_id[n] for n in names]
                for g, names in self.data["group_members"].items()}

    def _paint(self, frac, length, seed_key, hot_f_groups,
               cold_event_period, f_run_len, sporadic) -> np.ndarray:
        gid = self.group_id
        rng = np.random.default_rng(zlib.crc32(seed_key.encode()))
        sb_len = max(int(cold_event_period), 24)
        hot = [g for g in hot_f_groups if frac[gid[g]] > 0]
        cold = [g for g in self.data["f_groups"]
                if g not in hot and frac[gid[g]] > 0]
        m_present = [g for g in self.data["m_groups"] if frac[gid[g]] > 0]
        cycler = {g: 0 for g in self._members}

        def run_of(g: str, count: int) -> list[int]:
            members = self._members[g]
            m = members[cycler[g] % len(members)]
            cycler[g] += 1
            return [m] * count

        base_id = self.instr_id["base"]
        acc = {g: 0.0 for g in hot + cold + m_present}
        trace: list[int] = []
        cold_idx = 0
        while len(trace) < length:
            for g in acc:
                acc[g] += frac[gid[g]] * sb_len
            counts = {}
            for g in hot + m_present:
                counts[g] = int(acc[g])
                acc[g] -= counts[g]
            items: list[list[int]] = []
            run = max(1, f_run_len)
            hot_runs: list[list[int]] = []
            for g in hot:
                c = counts[g]
                while c > 0:
                    take = min(run, c)
                    hot_runs.append(run_of(g, take))
                    c -= take
            rng.shuffle(hot_runs)
            m_singles = []
            for g in m_present:
                m_singles.extend(run_of(g, 1) for _ in range(counts[g]))
            hi, mi = 0, 0
            while hi < len(hot_runs) or mi < len(m_singles):
                if hi < len(hot_runs):
                    items.append(hot_runs[hi])
                    hi += 1
                if mi < len(m_singles):
                    items.append(m_singles[mi])
                    mi += 1
            if cold:
                g = cold[cold_idx % len(cold)]
                cold_idx += 1
                pending = int(acc[g])
                if pending:
                    acc[g] -= pending
                    items.append(run_of(g, pending))
            n_ops = sum(len(it) for it in items)
            body_len = max(sb_len, n_ops + len(items) + 1)
            n_base = body_len - n_ops
            n_gaps = len(items) + 1
            tail = int(n_base * 0.6) if sporadic else 0
            inner = n_base - tail
            gaps = np.full(n_gaps, inner // n_gaps, dtype=np.int64)
            gaps[: inner % n_gaps] += 1
            if n_gaps > 2:
                gaps[:-1] += rng.integers(0, 2, size=n_gaps - 1)
                gaps[-1] -= gaps.sum() - inner
                if gaps[-1] < 0:
                    gaps[0] += gaps[-1]
                    gaps[-1] = 0
            body: list[int] = []
            for i, it in enumerate(items):
                body.extend([base_id] * int(gaps[i]))
                body.extend(it)
            body.extend([base_id] * int(gaps[-1]))
            body.extend([base_id] * tail)
            trace.extend(body)
        return np.asarray(trace[:length], dtype=np.int32)
