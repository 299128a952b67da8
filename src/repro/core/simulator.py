"""Cycle-approximate simulator of the FPGA-extended reconfigurable core.

Mirrors the paper's methodology (§V): the softcore supports all RV32IMF
instructions; the instruction disambiguator acts as an L0 cache over
reconfigurable slots and *adds latency* on slot misses, abstracting the
reconfiguration technology behind a configurable miss-latency constant
(10 / 50 / 250 cycles studied).  Two execution modes:

  * fixed-ISA machines (RV32I/IM/IF/IMF baselines of Fig. 4) — analytic:
    absent extensions expand to ABI soft routines; no slots, no misses;
  * the reconfigurable core (Fig. 6/7) — `lax.scan` over a synthesised
    instruction trace with exact-LRU disambiguator + bitstream-cache state.

Multi-programming (Fig. 7) adds a FreeRTOS-style round-robin scheduler with
a cycle quantum and a context-switch handler cost; slot state deliberately
persists across switches (the architecture's whole point — shared extensions
stay resident, §IV).  The scheduler runs over arbitrary fleets of P programs
(`simulate_many`), each with its own slot taxonomy (per-program tag tables),
heterogeneous per-program quanta, and integer priority weights (weighted
round-robin — see `SchedulerConfig`; the uniform unit-priority case is the
paper's scheduler, bit-for-bit).  `sweep_fleet` crosses {quanta x fleets x
slot counts x miss latencies} in one jitted vmap^4 — slot counts sweep
dynamically by masking a max-size disambiguator, quanta by vmapping the
per-program quantum vector.  The paper's pair experiments are the P=2
special case; the scheduling-policy axes feed `repro.sched`'s
contention-aware placement and admission control.

Four execution paths serve the sweep entry points (`sweep_fleet`,
`simulate_many`, `simulate_single`, `simulate_single_batch`); a dispatcher
picks per call:

  * **stack-distance fast path** (`repro.core.stackdist`): one Mattson pass
    per trace yields exact miss counts for every slot count at once, and
    cycles reconstruct affinely per miss latency — the {slot count x
    latency} grid collapses into post-processing.  Exact (bit-for-bit equal
    to the scan) iff the run is *unpreempted* (the quantum exceeds any
    reachable cycle count, so only program 0 runs and trace order is
    latency-independent) and the bitstream cache is *warm* (entries >=
    distinct tags, so it never evicts).  `stackdist_eligible` encodes both
    rules plus the no-overflow guard.
  * **interleaved fast path** (`repro.core.stackdist_interleaved`): the
    preempted generalisation.  Switch points depend on per-access costs
    (the quantum is counted in cycles), so the merged access order differs
    per {slot count x latency x quantum} cell and the grid cannot collapse;
    instead each cell replays its interleaving at *scheduler-window*
    granularity — one vectorized Mattson cummax pass per window, a
    `lax.while_loop` whose trip count is ~steps/window + one per context
    switch instead of one per step.  Exact (bit-for-bit) iff costs are
    non-negative and no int32 accumulator can overflow
    (`interleaved_eligible`); a bitstream cache smaller than the FLEET's
    merged tag set adds a second LRU distance per window, within the
    slot-miss stream (the bitstream level); ~15x over the optimized scan
    on preempted fig6-style grids (BENCH_sweep.json).  The engine is also
    *resumable* with a warm bitstream cache: a scan-shaped `FleetState`
    seeds it (cache contents map to virtual merged-stream positions, the
    open quantum / scheduler cursor / counters seed the loop carry) and
    a `FleetState` materialises back out, bit-for-bit equal to the
    scan's.
  * **stacked cold-bitstream path** (`repro.core.stackdist_cold`): for
    *unpreempted* runs whose bitstream cache is undersized, the
    disambiguator's miss subsequence is itself an LRU reference stream, so
    a second per-slot-count Mattson pass over it yields exact bitstream
    hit/miss counts for every `bs_cache_entries` at once —
    `stackdist_cold_eligible` drops the warmth condition entirely
    (`sweep_bitstream` exposes the full capacity x penalty grid in one
    call).
  * **`lax.scan` path**: the general cycle-by-cycle round-robin machine —
    the reference semantics, and the fallback for what is left: resumed
    runs with a cold bitstream cache, masked (degraded) disambiguators,
    sub-threshold quanta under `path="auto"`, and hand-crafted
    `FleetState`s no engine can seed from.  Its hot loop
    looks up the per-program (tag, hw-cost) streams once per call
    (instead of a dependent double gather per step), fuses the
    disambiguator + bitstream lookups into one state update
    (`slots.lookup_fused`), and unrolls the scan body (`scan_unroll`).

Callers can force a path with
`path="scan"|"stackdist"|"stackdist_cold"|"interleaved"` (parity tests
do); the default `"auto"` routes unpreempted eligible sweeps through
stack distance (warm) or the stacked cold pass, and preempted eligible
sweeps — one-shot or resumed — through the interleaved engine.

The scan's carry is an explicit, resumable value (`FleetState`):
`simulate_many(..., state=S, return_state=True)` runs N steps from S and
returns (results, S'), with the one-shot run being the
`S = init_fleet_state(...)` special case — split-at-any-step resume is
bit-for-bit equal to the unsplit run.  This is what lets the online
serving layer (`repro.sched.online`) carry warm slot/bitstream caches
across epochs and price tenant migration by resuming a tenant on a cold
core.  Resumed segments ride the interleaved engine whenever it is
exact for them (`interleaved_eligible` + a seedable state); every
returned `FleetState` is in *canonical* form — residents sorted by LRU
clock into a prefix — so states are comparable across engines (canonical
form is behaviour-preserving: exact-LRU eviction depends only on the
resident (tag, last_use) set, never on physical slot order).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (isa, lookup, slots, stackdist, stackdist_cold,
                        stackdist_interleaved)
from repro.kernels import window_distance
from repro.core.traces import Mix, analytic_cpi  # re-export for callers

__all__ = [
    "ReconfigConfig", "SchedulerConfig", "SimResult", "PairResult",
    "FleetResult", "FleetState", "init_fleet_state",
    "fleet_tag_table", "stackdist_eligible", "stackdist_cold_eligible",
    "interleaved_eligible",
    "quanta_vector", "priority_schedule",
    "simulate_single", "simulate_single_batch",
    "simulate_many", "sweep_fleet", "sweep_bitstream",
    "simulate_pair", "simulate_pair_batch",
    "analytic_cpi", "fixed_pair_cpi", "fixed_fleet_cpi",
]

# default lax.scan unroll for the cycle-by-cycle path — exposed so callers
# (and benchmarks/perf_sweep.py, which sweeps it) can tune per backend
# without changing results (integer state updates are exact).  Tuned on CPU:
# un-vmapped scans gain ~10% at unroll=4, but the vmap^3 sweep loses badly
# to the duplicated loop body, so the shared default stays 1; accelerators
# with per-step dispatch overhead are where larger unrolls pay off.
SCAN_UNROLL = 1

# default scheduler-window size of the interleaved fast path — a pure
# performance knob (a quantum larger than the window spans several
# iterations via the carried quantum-cycle counter; results are identical
# for any window >= 1).  Backend-aware: the recorded window sweep
# (BENCH_sweep.json, preempted_grid.*.window_sweep_s) shows 256 beating
# 512 on every CPU preempted grid (P=2..4), so CPU defaults to 256; the
# TPU keeps 512, untuned so far.
_INTERLEAVE_WINDOW_BY_BACKEND = {"cpu": 256}


def interleave_window() -> int:
    """The default interleaved-engine window for the running backend,
    looked up at use so importing this module initialises no backend."""
    return _INTERLEAVE_WINDOW_BY_BACKEND.get(jax.default_backend(), 512)


@dataclass(frozen=True)
class ReconfigConfig:
    """Reconfigurable-core parameters (paper §V-A, §V-D)."""

    num_slots: int
    miss_latency: int          # disambiguator-miss cycles (reconfig incl.)
    bs_cache_entries: int = 64  # bitstream-cache entries (>= tags: warm mode)
    bs_miss_extra: int = 100    # added cycles when the bitstream cache misses


# quantum no run can reach: larger than any reachable cycle count, yet far
# enough below int32 overflow that the q_cycles accumulator stays safe.
# Use it (via SchedulerConfig.no_preempt()) for solo/unpreempted runs.
NO_PREEMPT_QUANTUM = 1 << 30


@dataclass(frozen=True)
class SchedulerConfig:
    """Round-robin OS scheduler model (paper §V-B, §VI-C).

    Beyond the paper's single uniform quantum, the scheduler supports

      * **heterogeneous quanta** — `quantum_cycles` may be a length-P tuple
        giving each program its own timer quantum, and
      * **priority weights** — `priorities` (length-P positive ints) turn
        the plain round-robin into a weighted one: program p takes
        `priorities[p]` consecutive quanta per rotation, so CPU share is
        proportional to the weight.  The timer interrupt (and its
        `handler_cycles`) still fires at every quantum expiry, including
        back-to-back quanta of the same program.

    A scalar `quantum_cycles` with `priorities=None` is exactly the paper's
    uniform round-robin and reproduces it bit-for-bit.
    """

    quantum_cycles: int | tuple[int, ...] = 20_000
    handler_cycles: int = 150   # timer-interrupt + context-switch routine
                                # (incl. the 32 FP registers added in §V-B)
    priorities: tuple[int, ...] | None = None

    @classmethod
    def no_preempt(cls, handler_cycles: int = 150) -> "SchedulerConfig":
        """A scheduler that never fires — for solo-program references."""
        return cls(quantum_cycles=NO_PREEMPT_QUANTUM,
                   handler_cycles=handler_cycles)

    def quanta(self, num_programs: int) -> np.ndarray:
        """(P,) int32 per-program quantum vector (scalars broadcast)."""
        return quanta_vector(self.quantum_cycles, num_programs)

    def schedule(self, num_programs: int) -> np.ndarray:
        """The weighted round-robin turn order (see `priority_schedule`)."""
        return priority_schedule(self.priorities, num_programs)


def quanta_vector(quantum_cycles, num_programs: int) -> np.ndarray:
    """Normalise a scalar-or-vector quantum spec to a (P,) int32 vector."""
    q = np.asarray(quantum_cycles, dtype=np.int64)
    if q.ndim == 0:
        q = np.full((num_programs,), int(q), np.int64)
    if q.shape != (num_programs,):
        raise ValueError(
            f"quantum_cycles vector has shape {q.shape}, expected "
            f"({num_programs},) for a fleet of P={num_programs} programs")
    if np.any(q <= 0):
        raise ValueError(f"quantum_cycles must be positive, got {q.tolist()}")
    return q.astype(np.int32)


def priority_schedule(priorities, num_programs: int) -> np.ndarray:
    """Weighted round-robin turn order as a flat program-index sequence.

    `priorities=None` (or all-ones) is the plain rotation `[0, 1, .., P-1]`;
    weights `(2, 1)` yield `[0, 0, 1]`: program 0 takes two consecutive
    quanta per rotation.  The scan holds a cursor into this (static-length)
    sequence, so the weighted policy costs one extra gather per step and the
    uniform case stays bit-for-bit identical to the historical rotation.
    """
    if priorities is None:
        return np.arange(num_programs, dtype=np.int32)
    pr = np.asarray(priorities, dtype=np.int64)
    if pr.shape != (num_programs,):
        raise ValueError(
            f"priorities vector has shape {pr.shape}, expected "
            f"({num_programs},) for a fleet of P={num_programs} programs")
    if np.any(pr <= 0):
        raise ValueError(f"priorities must be positive ints, got "
                         f"{pr.tolist()}")
    return np.repeat(np.arange(num_programs, dtype=np.int32),
                     pr).astype(np.int32)


class SimResult(NamedTuple):
    cycles: jnp.ndarray
    instructions: jnp.ndarray
    slot_misses: jnp.ndarray
    bs_misses: jnp.ndarray

    @property
    def cpi(self):
        return self.cycles / jnp.maximum(self.instructions, 1)


class PairResult(NamedTuple):
    cycles: jnp.ndarray        # (P,) attributed cycles (incl. handler)
    instructions: jnp.ndarray  # (P,)
    slot_misses: jnp.ndarray   # (P,)
    switches: jnp.ndarray      # () context switches

    @property
    def cpi(self):
        return self.cycles / jnp.maximum(self.instructions, 1)


# ---------------------------------------------------------------------------
# Single-program reconfigurable core
# ---------------------------------------------------------------------------


def stackdist_eligible(tag_row, *, quantum_cycles, bs_entries: int,
                       max_miss_latency: int, bs_miss_extra: int,
                       total_steps: int) -> bool:
    """True iff the *unpreempted* stack-distance fast path is exact.

    This predicate gates `repro.core.stackdist` — the engine that collapses
    the whole {slot count x latency} grid into one distance profile.  That
    collapse needs the merged access order to be grid-independent, which
    only holds when program 0 runs alone, so the quantum must be provably
    unreachable; preempted runs are NOT served by this engine, but they are
    no longer scan-only either — `interleaved_eligible` gates the
    interleave-aware engine (`repro.core.stackdist_interleaved`) that
    replays each grid cell's own switch points at window granularity.

    Three conditions (see module docstring and `repro.core.stackdist`):

    1. warm bitstream cache: `bs_entries` covers every distinct tag of the
       scheduled program (`tag_row` is program 0's instr->tag table), so the
       bitstream cache never evicts and each tag misses it exactly once;
    2. unpreempted: the quantum is the NO_PREEMPT sentinel or beyond, so
       trace order is latency-independent and no handler cycles accrue;
    3. no-overflow guard: even the worst-case per-step cost summed over
       `total_steps` stays below the quantum — the scan's q_cycles
       accumulator can provably never fire a switch (and int32 stays safe).

    `quantum_cycles` may be a scalar, a per-program vector, or a whole
    swept quantum grid: with heterogeneous quanta a run is unpreempted only
    when EVERY program's quantum is unreachable, so eligibility is judged
    on the minimum over all entries.
    """
    num_tags = int(np.max(tag_row)) + 1
    warm = bs_entries >= num_tags
    worst_step = (int(np.max(isa.INSTR_HW_CYCLES)) + int(max_miss_latency)
                  + int(bs_miss_extra))
    min_quantum = int(np.min(np.asarray(quantum_cycles)))
    unpreempted = (min_quantum >= NO_PREEMPT_QUANTUM
                   and total_steps * worst_step < min_quantum)
    return warm and unpreempted


def stackdist_cold_eligible(*, quantum_cycles, max_miss_latency: int,
                            bs_miss_extra: int, total_steps: int) -> bool:
    """True iff the stacked cold-bitstream pass is exact for this run.

    Gates `repro.core.stackdist_cold`: `stackdist_eligible`'s unpreempted
    + no-overflow conditions with the warm-bitstream-cache condition
    *dropped* — the second Mattson pass over the disambiguator's miss
    subsequence serves ANY bitstream capacity exactly, so an undersized
    (cold) bitstream cache no longer forces the scan as long as the run
    is unpreempted (a preempted cold run's miss subsequence is
    switch-point-dependent per grid cell and its bitstream misses feed
    back into the switch points: the interleaved engine's bitstream
    level replays those per window instead).
    """
    worst_step = (int(np.max(isa.INSTR_HW_CYCLES)) + int(max_miss_latency)
                  + int(bs_miss_extra))
    min_quantum = int(np.min(np.asarray(quantum_cycles)))
    return (min_quantum >= NO_PREEMPT_QUANTUM
            and total_steps * worst_step < min_quantum)


def interleaved_eligible(*, miss_latencies, bs_miss_extra: int,
                         handler_cycles: int, total_steps: int) -> bool:
    """True iff the interleave-aware fast path is *exact* for this run.

    Gates `repro.core.stackdist_interleaved`, which serves preempted (and
    mixed preempted/unpreempted) one-shot runs.  Unlike
    `stackdist_eligible` there is no quantum condition at all: every grid
    cell replays its own switch points, so any quantum — uniform,
    per-program, swept, even unreachable — is exact.  Nor is there a
    bitstream-cache condition: a cache smaller than the fleet's merged
    tag set (the caches are shared, so the union counts) runs the window
    pass's bitstream level, a second LRU distance within the slot-miss
    stream (`stackdist_interleaved.bs_level`).  What remains:

    1. non-negative costs: latencies / bitstream penalty / handler >= 0,
       so the in-window cycle accumulation is monotone;
    2. no-overflow guard: worst-case per-access cost (a slot miss and a
       bitstream miss on every access) plus a handler every access,
       summed over `total_steps`, stays inside int32 — the same
       accumulators the scan uses.

    Resumed (`state=`) runs need more: the resumable engine models a
    warm bitstream cache only, so `simulate_many` also asks for
    `bs_entries` to cover the merged tag set, and for a scan-shaped
    `FleetState` (`_seedable_fleet_state`: prefix packing, distinct LRU
    clocks, slot residents covered by the bitstream cache) whose counters
    leave int32 headroom for the segment; it falls back to the scan for
    states that fail them.
    """
    lats = np.asarray(miss_latencies)
    nonneg = (int(np.min(lats)) >= 0 and int(bs_miss_extra) >= 0
              and int(handler_cycles) >= 0)
    worst_step = (int(np.max(isa.INSTR_HW_CYCLES)) + int(np.max(lats))
                  + int(bs_miss_extra) + int(handler_cycles))
    no_overflow = total_steps * worst_step < np.iinfo(np.int32).max
    return nonneg and no_overflow


# auto-dispatch heuristics for the interleaved engine (forcing
# path="interleaved" only requires exactness, i.e. `interleaved_eligible`):
# below this minimum quantum a cell switches every handful of accesses and
# the window engine degenerates toward one iteration per scheduler run,
# losing its sequential-depth advantage over the scan
_INTERLEAVED_AUTO_MIN_QUANTUM = 256
# per-iteration transient footprint bound: window x num_tags x grid cells
# per fleet (the fleet axis is chunked separately, see
# _sweep_fleet_interleaved)
_INTERLEAVED_CHUNK_ELEMS = 16_000_000
# fleet batches are padded up to a multiple of this before hitting the
# interleaved sweep, so batch-size churn (contention-model pricing calls
# with B = 1..8) reuses one compiled shape; padded rows are replays of
# fleet 0 and are sliced off the result
_INTERLEAVED_BATCH_BUCKET = 4


def _interleaved_window(quanta_grid, total_steps: int,
                        window: int | None) -> int:
    """Static window size: the tuned default, shrunk to the next power of
    two covering the largest quantum (tiny quanta expire within tiny
    windows) and never beyond the run length."""
    if window is None:
        q = int(np.max(np.asarray(quanta_grid)))
        window = min(interleave_window(), 1 << max(0, (q - 1)).bit_length())
    return max(1, min(int(window), total_steps))


def _interleaved_auto_ok(quanta_grid, grid_cells: int, num_tags: int,
                         total_steps: int, window: int | None) -> bool:
    w = _interleaved_window(quanta_grid, total_steps, window)
    return (int(np.min(np.asarray(quanta_grid)))
            >= _INTERLEAVED_AUTO_MIN_QUANTUM
            and w * max(num_tags, 1) * grid_cells
            <= _INTERLEAVED_CHUNK_ELEMS)


def _check_single_path(path: str, eligible: bool,
                       cold_ok: bool = False) -> str:
    """Path validation for the single-program entry points, which dispatch
    between the unpreempted stack-distance engines (warm / stacked-cold)
    and the scan."""
    if path == "interleaved":
        raise ValueError(
            "interleaved path is not served by the single-program entry "
            "points (a solo run is never preempted; the unpreempted "
            "stack-distance engine already collapses its grid) — use "
            "simulate_many or sweep_fleet to force it")
    return _check_path(path, eligible, cold_ok=cold_ok)


def _check_path(path: str, stackdist_ok: bool, interleaved_ok: bool = False,
                interleaved_auto: bool = False,
                cold_ok: bool = False) -> str:
    if path not in ("auto", "stackdist", "stackdist_cold", "interleaved",
                    "scan"):
        raise ValueError(f"unknown path {path!r}")
    if path == "stackdist" and not stackdist_ok:
        raise ValueError(
            "stack-distance path requires an unpreempted run with a warm "
            "bitstream cache (see simulator.stackdist_eligible)")
    if path == "stackdist_cold" and not cold_ok:
        raise ValueError(
            "stacked cold-bitstream path requires an unpreempted run with "
            "int32-safe costs (see simulator.stackdist_cold_eligible)")
    if path == "interleaved" and not interleaved_ok:
        raise ValueError(
            "interleaved path requires non-negative int32-safe costs "
            "(see simulator.interleaved_eligible)")
    if path == "auto":
        path = ("stackdist" if stackdist_ok
                else "stackdist_cold" if cold_ok
                else "interleaved" if interleaved_ok and interleaved_auto
                else "scan")
    return path


def _simulate_single(trace, instr_tag, miss_latency, num_slots: int,
                     bs_entries: int, bs_miss_extra):
    """P=1 special case of the fleet scan: one program, never preempted.

    One cost model lives in `_fleet_step_fn`; the single-program path is a
    wrapper so disambiguator/bitstream accounting cannot drift between the
    Fig. 6 (single) and Fig. 7 (multi-program) experiments.
    """
    r, _ = _simulate_fleet_impl(
        trace[None, :], instr_tag[None, :], miss_latency,
        jnp.int32(num_slots),
        jnp.full((1,), NO_PREEMPT_QUANTUM, jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.int32(0),
        num_slots, bs_entries, bs_miss_extra, trace.shape[0])
    return SimResult(r.cycles[0], r.instructions[0], r.slot_misses[0],
                     r.bs_misses[0])


_simulate_single_jit = functools.partial(
    jax.jit, static_argnames=("num_slots", "bs_entries"))(_simulate_single)


def _single_eligible(cfg: ReconfigConfig, scenario: isa.SlotScenario,
                     max_miss_latency: int, total_steps: int) -> bool:
    return stackdist_eligible(
        scenario.instr_tag, quantum_cycles=NO_PREEMPT_QUANTUM,
        bs_entries=cfg.bs_cache_entries, max_miss_latency=max_miss_latency,
        bs_miss_extra=cfg.bs_miss_extra, total_steps=total_steps)


def _single_cold_eligible(cfg: ReconfigConfig, max_miss_latency: int,
                          total_steps: int) -> bool:
    return stackdist_cold_eligible(
        quantum_cycles=NO_PREEMPT_QUANTUM, max_miss_latency=max_miss_latency,
        bs_miss_extra=cfg.bs_miss_extra, total_steps=total_steps)


def simulate_single(trace: np.ndarray, cfg: ReconfigConfig,
                    scenario: isa.SlotScenario,
                    path: str = "auto") -> SimResult:
    trace = jnp.asarray(trace, jnp.int32)
    eligible = _single_eligible(cfg, scenario, cfg.miss_latency,
                                trace.shape[0])
    cold_ok = _single_cold_eligible(cfg, cfg.miss_latency, trace.shape[0])
    chosen = _check_single_path(path, eligible, cold_ok)
    if chosen == "stackdist":
        cycles, misses, bs = stackdist.lanes_unpreempted(
            trace[None, :], scenario.instr_tag, isa.INSTR_HW_CYCLES,
            jnp.int32(cfg.num_slots), jnp.asarray([cfg.miss_latency]),
            jnp.int32(cfg.bs_miss_extra),
            num_tags=max(scenario.num_tags, 1), total_steps=trace.shape[0])
        return SimResult(cycles[0], jnp.int32(trace.shape[0]), misses[0],
                         bs[0])
    if chosen == "stackdist_cold":
        cycles, misses, bs = stackdist_cold.lanes_cold(
            trace[None, :], scenario.instr_tag, isa.INSTR_HW_CYCLES,
            jnp.int32(cfg.num_slots), jnp.asarray([cfg.miss_latency]),
            jnp.int32(cfg.bs_cache_entries), jnp.int32(cfg.bs_miss_extra),
            num_tags=max(scenario.num_tags, 1), total_steps=trace.shape[0])
        return SimResult(cycles[0], jnp.int32(trace.shape[0]), misses[0],
                         bs[0])
    return _simulate_single_jit(
        trace,
        jnp.asarray(scenario.instr_tag, jnp.int32),
        jnp.int32(cfg.miss_latency), num_slots=cfg.num_slots,
        bs_entries=cfg.bs_cache_entries,
        bs_miss_extra=jnp.int32(cfg.bs_miss_extra))


def simulate_single_batch(traces: np.ndarray, miss_latencies: np.ndarray,
                          cfg: ReconfigConfig,
                          scenario: isa.SlotScenario,
                          path: str = "auto") -> SimResult:
    """vmap over (trace, miss latency) lanes with a shared scenario.

    Eligible lanes (a single program is never preempted) route through one
    stack-distance profile per lane — warm bitstream caches take the plain
    pass, cold ones the stacked pass — instead of one `lax.scan` per
    lane."""
    traces = jnp.asarray(traces, jnp.int32)
    lats = jnp.asarray(miss_latencies, jnp.int32)
    max_lat = int(np.max(np.asarray(miss_latencies)))
    eligible = _single_eligible(cfg, scenario, max_lat, traces.shape[-1])
    cold_ok = _single_cold_eligible(cfg, max_lat, traces.shape[-1])
    chosen = _check_single_path(path, eligible, cold_ok)
    if chosen in ("stackdist", "stackdist_cold"):
        chunk = _stackdist_chunk(traces.shape[-1],
                                 max(scenario.num_tags, 1))
        if chosen == "stackdist":
            def lanes(tr, la):
                return stackdist.lanes_unpreempted(
                    tr, scenario.instr_tag, isa.INSTR_HW_CYCLES,
                    jnp.int32(cfg.num_slots), la,
                    jnp.int32(cfg.bs_miss_extra),
                    num_tags=max(scenario.num_tags, 1),
                    total_steps=traces.shape[-1])
        else:
            def lanes(tr, la):
                return stackdist_cold.lanes_cold(
                    tr, scenario.instr_tag, isa.INSTR_HW_CYCLES,
                    jnp.int32(cfg.num_slots), la,
                    jnp.int32(cfg.bs_cache_entries),
                    jnp.int32(cfg.bs_miss_extra),
                    num_tags=max(scenario.num_tags, 1),
                    total_steps=traces.shape[-1])
        outs = [lanes(traces[i:i + chunk], lats[i:i + chunk])
                for i in range(0, traces.shape[0], chunk)]
        cycles, misses, bs = (jnp.concatenate(x) for x in zip(*outs))
        instrs = jnp.full(cycles.shape, traces.shape[-1], jnp.int32)
        return SimResult(cycles, instrs, misses, bs)
    tag = jnp.asarray(scenario.instr_tag, jnp.int32)
    fn = jax.vmap(
        lambda t, L: _simulate_single_jit(
            t, tag, L, num_slots=cfg.num_slots,
            bs_entries=cfg.bs_cache_entries,
            bs_miss_extra=jnp.int32(cfg.bs_miss_extra)))
    return fn(traces, lats)


# ---------------------------------------------------------------------------
# Multi-program (round-robin scheduler): the N-program fleet simulator
# ---------------------------------------------------------------------------


class FleetResult(NamedTuple):
    """Per-program counters of an N-program fleet run.

    Leading axes are whatever grid the caller swept (fleets / slot counts /
    miss latencies); the trailing axis is the program index within a fleet.
    """

    cycles: jnp.ndarray        # (..., P) attributed cycles (incl. handler)
    instructions: jnp.ndarray  # (..., P)
    slot_misses: jnp.ndarray   # (..., P)
    bs_misses: jnp.ndarray     # (..., P)
    switches: jnp.ndarray      # (...)  context switches

    @property
    def cpi(self):
        return self.cycles / jnp.maximum(self.instructions, 1)


class FleetState(NamedTuple):
    """The fleet scan's carry as an explicit, resumable value.

    `simulate_many` is "run N steps from state S -> (results, S')": the
    one-shot run is the `S = init_fleet_state(...)` special case, and
    feeding S' back in continues the simulation bit-for-bit — a run split
    at any step boundary equals the unsplit run exactly (cache contents,
    LRU clocks, scheduler cursor and all counters are part of the state).

    Counters (`cycles` .. `switches`) are *cumulative since the state was
    initialised*, so a resumed segment's `FleetResult` reports run totals;
    zero them (`reset_counters`) to measure one segment in isolation.
    The slot/bitstream caches are the warm state the paper's architecture
    preserves across context switches (§IV) — `repro.sched.online` carries
    them across serving epochs and prices tenant migration by resuming a
    tenant's state on a cold core.
    """

    slot_st: slots.SlotState   # disambiguator (shared by the fleet)
    bs_st: slots.SlotState     # bitstream cache
    cursors: jnp.ndarray       # (P,) per-program trace cursor
    sched_idx: jnp.ndarray     # () cursor into the priority schedule
    q_cycles: jnp.ndarray      # () cycles burnt in the current quantum
    cycles: jnp.ndarray        # (P,) attributed cycles (incl. handler)
    instrs: jnp.ndarray        # (P,)
    misses: jnp.ndarray        # (P,) disambiguator misses
    bs_misses: jnp.ndarray     # (P,) bitstream-cache misses
    switches: jnp.ndarray      # () context switches

    @property
    def num_programs(self) -> int:
        return self.cursors.shape[0]

    def result(self) -> "FleetResult":
        """The cumulative counters viewed as a FleetResult."""
        return FleetResult(self.cycles, self.instrs, self.misses,
                           self.bs_misses, self.switches)

    def reset_counters(self) -> "FleetState":
        """Zero the counters, keeping caches/cursors — the next segment's
        FleetResult then reports that segment alone."""
        z = jnp.zeros_like
        return self._replace(cycles=z(self.cycles), instrs=z(self.instrs),
                             misses=z(self.misses),
                             bs_misses=z(self.bs_misses),
                             switches=z(self.switches))


def init_fleet_state(num_programs: int, num_slots: int,
                     bs_entries: int = 64) -> FleetState:
    """Cold-start state for a fleet of P programs (empty caches, step 0)."""
    if num_programs < 1:
        raise ValueError(f"num_programs must be >= 1, got {num_programs}")
    return FleetState(
        slot_st=slots.init(num_slots),
        bs_st=slots.init(bs_entries),
        cursors=jnp.zeros((num_programs,), jnp.int32),
        sched_idx=jnp.int32(0),
        q_cycles=jnp.int32(0),
        cycles=jnp.zeros((num_programs,), jnp.int32),
        instrs=jnp.zeros((num_programs,), jnp.int32),
        misses=jnp.zeros((num_programs,), jnp.int32),
        bs_misses=jnp.zeros((num_programs,), jnp.int32),
        switches=jnp.int32(0),
    )


def _check_fleet_state(state: FleetState, num_programs: int,
                       num_slots: int, bs_entries: int) -> None:
    if state.cursors.shape != (num_programs,):
        raise ValueError(
            f"FleetState carries {state.cursors.shape[0]} program cursors, "
            f"but the traces describe a fleet of P={num_programs} programs")
    if state.slot_st.tags.shape[0] != num_slots:
        raise ValueError(
            f"FleetState disambiguator has {state.slot_st.tags.shape[0]} "
            f"slots, but the config allocates num_slots={num_slots} — "
            f"resume must use the same slot geometry it was initialised "
            f"with")
    if state.bs_st.tags.shape[0] != bs_entries:
        raise ValueError(
            f"FleetState bitstream cache has {state.bs_st.tags.shape[0]} "
            f"entries, but the config allocates "
            f"bs_cache_entries={bs_entries}")


def fleet_tag_table(scenarios, num_programs: int) -> np.ndarray:
    """(P, NUM_INSTRUCTIONS) per-program disambiguator-tag table.

    `scenarios` is either one `SlotScenario` shared by every program or a
    sequence of `num_programs` of them — per-program tables let an FM-class
    and an M-class program disagree about which opcodes are slotted (their
    binaries were compiled against different extension sets, paper §IV).
    """
    if isinstance(scenarios, isa.SlotScenario):
        scenarios = [scenarios] * num_programs
    else:
        scenarios = list(scenarios)
    if len(scenarios) != num_programs:
        raise ValueError(
            f"got {len(scenarios)} slot scenarios for a fleet of "
            f"P={num_programs} programs — pass one SlotScenario to share, "
            f"or exactly one per program")
    for i, s in enumerate(scenarios):
        tag = np.asarray(s.instr_tag)
        if tag.shape != (isa.NUM_INSTRUCTIONS,):
            raise ValueError(
                f"scenario {i} ({getattr(s, 'name', s)!r}) has instr_tag "
                f"shape {tag.shape}, expected ({isa.NUM_INSTRUCTIONS},)")
    return np.stack([s.instr_tag for s in scenarios])


# ---------------------------------------------------------------------------
# FleetState <-> interleaved-engine translation (the resumable fast path)
# ---------------------------------------------------------------------------


def canonical_slot_state(st: slots.SlotState) -> slots.SlotState:
    """Behaviour-preserving canonical arrangement of one cache: residents
    sorted by LRU clock (`last_use`) ascending into a prefix, empty
    entries (tag -1, last_use 0) as the suffix, clock untouched.

    Exact-LRU behaviour depends only on the resident (tag, last_use) set —
    hits are membership tests, the victim is argmin(last_use) with empties
    preferred, fills take the first empty — never on physical entry order
    (`slots._access`).  Ties in `last_use` (impossible in real scan
    states, whose filled clocks are distinct) keep their original relative
    order (stable sort), preserving the scan's lowest-index-victim
    tiebreak.  Fault surgery (`seu_fleet_state`, `degrade_fleet_state`)
    re-canonicalises after punching holes so a mutated cache is
    prefix-packed again.
    """
    tags = np.asarray(st.tags)
    lu = np.asarray(st.last_use)
    filled = tags >= 0
    k = int(filled.sum())
    order = np.argsort(lu[filled], kind="stable")
    t = np.full(tags.shape, -1, np.int32)
    u = np.zeros(lu.shape, np.int32)
    t[:k] = tags[filled][order]
    u[:k] = lu[filled][order]
    return slots.SlotState(tags=jnp.asarray(t), last_use=jnp.asarray(u),
                           clock=st.clock)


def _canonical_state(state: FleetState) -> FleetState:
    """Behaviour-preserving canonical cache arrangement of a whole
    `FleetState` (see `canonical_slot_state`).  Canonicalising every
    returned `FleetState` makes states comparable across engines: the
    interleaved engine recovers the resident *sets* and clocks exactly
    but not the scan's incidental fill order, so both report this shared
    normal form.
    """
    return state._replace(slot_st=canonical_slot_state(state.slot_st),
                          bs_st=canonical_slot_state(state.bs_st))


# ---------------------------------------------------------------------------
# fault surgery: the state mutations a fleet's fault events inflict
# ---------------------------------------------------------------------------


def seu_fleet_state(state: FleetState, slot_indices) -> FleetState:
    """A single-event upset corrupts the disambiguator entries at
    `slot_indices`: their residents are invalidated (the configuration
    bits are garbage, so the implementation must be re-loaded on next
    use) and the cache is re-canonicalised so survivors pack a prefix.

    The result is usually NOT seedable by the interleaved resume — a
    partially-filled disambiguator next to a fuller bitstream cache is a
    geometry no uninterrupted LRU run reaches (`_seedable_fleet_state`)
    — so the next resumed segment falls back to the cycle-by-cycle scan;
    once that segment refills the disambiguator, subsequent segments ride
    the engine again.
    """
    idx = np.asarray(slot_indices, np.int64).reshape(-1)
    n = np.asarray(state.slot_st.tags).shape[0]
    if idx.size and (idx.min() < 0 or idx.max() >= n):
        raise ValueError(
            f"SEU slot indices {idx.tolist()} out of range for a "
            f"{n}-slot disambiguator")
    return state._replace(slot_st=canonical_slot_state(
        slots.invalidate(state.slot_st, idx)))


def flush_bitstream(state: FleetState) -> FleetState:
    """A failed partial reconfiguration (or scrub) colds the bitstream
    cache; the configured slots keep running, but every future
    disambiguator miss re-pays the full bitstream re-load penalty
    (`bs_miss_extra` — the LUTstructions cost made real).

    Slot residents no longer covered by the bitstream cache make the
    state unseedable by the interleaved resume, so the next resumed
    segment rides the scan until the bitstream cache re-warms.
    """
    bs_entries = np.asarray(state.bs_st.tags).shape[0]
    return state._replace(bs_st=slots.init(bs_entries))


def degrade_fleet_state(state: FleetState, num_active: int) -> FleetState:
    """Shrink a fleet state to a core that came back with only
    `num_active` usable disambiguator slots: the `num_active`
    most-recently-used residents survive (packed canonically into the
    active prefix), everything else is invalidated.

    The result is the state contract of `simulate_many(...,
    num_active=k)`: masking (`slots.lookup`'s `num_active`) makes
    inactive slots inert — never matched, never victims — so a masked
    run over a state whose residents all sit inside the active prefix is
    bit-for-bit an LRU cache of the smaller size (the degraded-core
    equivalence property, pinned by tests/test_faults.py).
    """
    n = np.asarray(state.slot_st.tags).shape[0]
    if not 1 <= num_active <= n:
        raise ValueError(
            f"num_active must be in [1, {n}], got {num_active}")
    st = canonical_slot_state(state.slot_st)
    tags = np.asarray(st.tags)
    filled = int((tags >= 0).sum())
    if filled > num_active:
        # canonical order is LRU-ascending: the dead slots take the
        # least-recently-used residents (prefix entries)
        st = canonical_slot_state(
            slots.invalidate(st, np.arange(filled - num_active)))
    return state._replace(slot_st=st)


def _seedable_fleet_state(state: FleetState, num_tags: int,
                          worst_step: int, total_steps: int) -> bool:
    """True iff the interleaved engine can seed from this `FleetState`.

    Any state an actual scan produced qualifies; the conditions only
    exclude hand-crafted states whose cache geometry no LRU run can reach
    (those silently fall back to the scan under `path="auto"`):

      * both caches prefix-packed with distinct resident tags in
        `[0, num_tags)` and distinct LRU clocks no later than the cache
        clock (scan fills always pack a prefix, clocks are unique);
      * slot residents all bitstream-resident, and a non-full
        disambiguator implies identical resident sets (no eviction can
        have happened before the cache filled) — this is what lets the
        seed order evicted tags below residents without knowing the
        true eviction history;
      * int32 headroom: the seed's counters/cursors/clocks plus a
        worst-case segment stay below int32 (the scan tolerates silent
        wraparound only in the sense that nothing guards it; the engine
        refuses to seed rather than diverge).
    """
    def cache(st: slots.SlotState):
        tags = np.asarray(st.tags)
        lu = np.asarray(st.last_use).astype(np.int64)
        filled = tags >= 0
        k = int(filled.sum())
        if not (np.all(tags[:k] >= 0) and np.all(tags[k:] < 0)):
            return None
        res = tags[:k]
        if k and (int(res.max()) >= num_tags
                  or len(np.unique(res)) != k
                  or len(np.unique(lu[:k])) != k
                  or int(lu[:k].max()) > int(st.clock)
                  or int(lu[:k].min()) < 0):
            return None
        return res

    slot_res = cache(state.slot_st)
    bs_res = cache(state.bs_st)
    if slot_res is None or bs_res is None:
        return False
    if not np.isin(slot_res, bs_res).all():
        return False
    full = slot_res.size == np.asarray(state.slot_st.tags).size
    if not full and slot_res.size != bs_res.size:
        return False
    lim = np.iinfo(np.int32).max
    top = max(int(state.q_cycles), int(state.switches),
              *(int(np.max(np.asarray(x))) for x in
                (state.cycles, state.instrs, state.misses, state.bs_misses)))
    return (top + total_steps * worst_step < lim
            and int(np.max(np.asarray(state.cursors))) + total_steps < lim
            and int(state.slot_st.clock) + total_steps < lim
            and int(state.bs_st.clock) + total_steps < lim)


def _seed_carry(state: FleetState,
                num_tags: int) -> stackdist_interleaved.CellCarry:
    """Translate a (seedable) `FleetState` into engine coordinates.

    Cache contents become the virtual per-tag position block `[0,
    num_tags)` below all segment positions: evicted-but-bitstream-resident
    tags at the bottom (their next access must re-fault at every slot
    count — the disambiguator is provably full whenever they exist — and
    they are not cold), disambiguator residents above them ordered by LRU
    clock, untouched tags -1.  Scheduler state and counters seed the
    carry verbatim.
    """
    slot_tags = np.asarray(state.slot_st.tags)
    slot_lu = np.asarray(state.slot_st.last_use).astype(np.int64)
    bs_tags = np.asarray(state.bs_st.tags)
    filled = slot_tags >= 0
    residents = slot_tags[filled][np.argsort(slot_lu[filled])]
    evicted = np.setdiff1d(bs_tags[bs_tags >= 0], residents)
    last_pos = np.full((num_tags,), -1, np.int32)
    last_pos[evicted] = np.arange(evicted.size, dtype=np.int32)
    last_pos[residents] = evicted.size + np.arange(residents.size,
                                                   dtype=np.int32)
    return stackdist_interleaved.CellCarry(
        last_pos=jnp.asarray(last_pos),
        last_miss_pos=jnp.full((num_tags,), -1, jnp.int32),
        cursors=state.cursors, sched_idx=state.sched_idx,
        steps_done=jnp.int32(0), q_cycles=state.q_cycles,
        cycles=state.cycles, instrs=state.instrs, misses=state.misses,
        bs_misses=state.bs_misses, switches=state.switches)


def _state_from_final(final: stackdist_interleaved.CellCarry,
                      seed_state: FleetState, num_slots: int,
                      bs_entries: int, num_tags: int,
                      total_steps: int) -> FleetState:
    """Rebuild the canonical `FleetState` from the engine's final carry.

    Both cache clocks advance by exactly one per access (the bitstream
    clock ticks on every `lookup_fused` step too, tag -1 or hit or not),
    so clock' = seed clock + steps.  A touched tag's LRU clock is the
    scan clock value of its last access — seed clock plus its 1-based
    segment step index, i.e. `last_pos - num_tags + 1` — and untouched
    tags keep their seed clock; the bitstream cache is touched exactly on
    slot misses, so its clocks come from `last_miss_pos` the same way.
    Residency: the disambiguator holds the `num_slots` most recent
    distinct tags of the merged stream (seed block included), the warm
    bitstream cache holds every tag ever present.  Entries pack in
    canonical order (`_canonical_state`'s normal form) directly.
    """
    offset = num_tags
    last_pos = np.asarray(final.last_pos, dtype=np.int64)
    last_miss = np.asarray(final.last_miss_pos, dtype=np.int64)
    seed_slot_clock = int(seed_state.slot_st.clock)
    seed_bs_clock = int(seed_state.bs_st.clock)

    def lu_map(st: slots.SlotState) -> np.ndarray:
        m = np.zeros((num_tags,), np.int64)
        tags = np.asarray(st.tags)
        f = tags >= 0
        m[tags[f]] = np.asarray(st.last_use, np.int64)[f]
        return m

    slot_lu = np.where(last_pos >= offset,
                       seed_slot_clock + (last_pos - offset) + 1,
                       lu_map(seed_state.slot_st))
    bs_lu = np.where(last_miss >= 0,
                     seed_bs_clock + (last_miss - offset) + 1,
                     lu_map(seed_state.bs_st))
    present = np.nonzero(last_pos >= 0)[0]
    by_recency = present[np.argsort(last_pos[present])]
    slot_res = by_recency[-num_slots:]   # ascending position = ascending lu
    bs_res = present[np.argsort(bs_lu[present])]

    def pack(res: np.ndarray, lu: np.ndarray, size: int,
             clock: int) -> slots.SlotState:
        t = np.full((size,), -1, np.int32)
        u = np.zeros((size,), np.int32)
        t[:res.size] = res
        u[:res.size] = lu[res].astype(np.int32)
        return slots.SlotState(tags=jnp.asarray(t), last_use=jnp.asarray(u),
                               clock=jnp.int32(clock))

    return FleetState(
        slot_st=pack(slot_res, slot_lu, num_slots,
                     seed_slot_clock + total_steps),
        bs_st=pack(bs_res, bs_lu, bs_entries, seed_bs_clock + total_steps),
        cursors=final.cursors, sched_idx=final.sched_idx,
        q_cycles=final.q_cycles, cycles=final.cycles, instrs=final.instrs,
        misses=final.misses, bs_misses=final.bs_misses,
        switches=final.switches)


def _engine_num_tags(table: np.ndarray, state: FleetState | None) -> int:
    """Static tag-alphabet size for the interleaved engine: the fleet's
    table plus any *stale* resident tags a carried state may hold from
    scenarios no longer in the fleet — stale residents still occupy real
    LRU stack positions, so the engine must model them."""
    nt = int(np.max(table)) + 1
    if state is not None:
        for st in (state.slot_st, state.bs_st):
            t = np.asarray(st.tags)
            if t.size and int(t.max()) >= 0:
                nt = max(nt, int(t.max()) + 1)
    return max(nt, 1)


def _resume_fleet_interleaved(traces, table, cfg: ReconfigConfig, quanta,
                              schedule, handler, seed_state: FleetState,
                              total_steps: int, num_tags: int,
                              use_kernel=None):
    """Run one resumable interleaved cell from a `FleetState` seed ->
    (FleetResult, final CellCarry)."""
    w = _interleaved_window(quanta, total_steps, None)
    final = stackdist_interleaved.resume_preempted(
        traces, jnp.asarray(table, jnp.int32), isa.INSTR_HW_CYCLES,
        jnp.int32(cfg.num_slots), jnp.int32(cfg.miss_latency),
        jnp.asarray(quanta, jnp.int32), jnp.asarray(schedule, jnp.int32),
        jnp.int32(handler), jnp.int32(cfg.bs_miss_extra),
        _seed_carry(seed_state, num_tags),
        num_tags=num_tags, total_steps=total_steps, window=w,
        use_kernel=use_kernel)
    res = FleetResult(final.cycles, final.instrs, final.misses,
                      final.bs_misses, final.switches)
    return res, final


def _fleet_step_fn(ptags, pcosts, miss_latency, active_slots, quanta,
                   schedule, handler, bs_miss_extra):
    """Round-robin step over precomputed per-program (tag, cost) streams.

    `ptags`/`pcosts` are the (P, N) lookups `tags[p, traces[p, i]]` /
    `hw[traces[p, i]]` hoisted out of the step: the hot loop does two
    independent stream loads instead of a dependent double gather per cycle,
    and one fused disambiguator+bitstream update (`slots.lookup_fused`).

    `quanta` is the (P,) per-program quantum vector and `schedule` the
    weighted round-robin turn order (`priority_schedule`): the scan walks a
    cursor through `schedule` instead of incrementing the program index, so
    priority weights are one extra gather per step.  With uniform quanta
    and unit priorities this reduces exactly to the historical rotation.
    """
    trace_len = ptags.shape[1]
    sched_len = schedule.shape[0]

    def step(c: FleetState, _):
        p = schedule[c.sched_idx]
        i = jnp.remainder(c.cursors[p], trace_len)
        tag = ptags[p, i]
        # on a disambiguator miss the bitstream is fetched through the
        # bitstream cache; a miss there goes to the unified L2 (extra cost)
        slot_st, bs_st, hit, bs_hit = slots.lookup_fused(
            c.slot_st, c.bs_st, tag, active_slots)
        cost = pcosts[p, i]
        cost = cost + jnp.where(hit, 0, miss_latency).astype(jnp.int32)
        cost = cost + jnp.where(hit | bs_hit, 0,
                                bs_miss_extra).astype(jnp.int32)

        q = c.q_cycles + cost
        do_switch = q >= quanta[p]
        # the outgoing program pays the interrupt-handler cycles, mirroring
        # the paper's observation that short quanta inflate all runtimes
        cost_p = cost + jnp.where(do_switch, handler, 0).astype(jnp.int32)

        # slot/bitstream state deliberately persists across the switch —
        # shared extensions stay resident (the architecture's point, §IV)
        return FleetState(
            slot_st=slot_st,
            bs_st=bs_st,
            cursors=c.cursors.at[p].add(1),
            sched_idx=jnp.where(do_switch,
                                (c.sched_idx + 1) % sched_len,
                                c.sched_idx),
            q_cycles=jnp.where(do_switch, 0, q),
            cycles=c.cycles.at[p].add(cost_p),
            instrs=c.instrs.at[p].add(1),
            misses=c.misses.at[p].add((~hit).astype(jnp.int32)),
            bs_misses=c.bs_misses.at[p].add(
                (~(hit | bs_hit)).astype(jnp.int32)),
            switches=c.switches + do_switch.astype(jnp.int32),
        ), None

    return step


def _simulate_fleet_impl(traces, tag_table, miss_latency, active_slots,
                         quanta, schedule, handler, num_slots: int,
                         bs_entries: int, bs_miss_extra, total_steps: int,
                         scan_unroll: int = SCAN_UNROLL,
                         state: FleetState | None = None
                         ) -> tuple[FleetResult, FleetState]:
    """(P, N) traces + (P, num_opcodes) tags -> (FleetResult, FleetState).

    `num_slots` is the *allocated* (static) disambiguator size;
    `active_slots` (traced) masks it down so slot count is a sweep axis.
    `quanta` is the (P,) per-program quantum vector; `schedule` the
    weighted round-robin turn order (see `priority_schedule`).  `state`
    resumes the scan from a prior carry (None = cold init); the returned
    state carries the run's full warm state for further resumption.
    """
    hw = jnp.asarray(isa.INSTR_HW_CYCLES, jnp.int32)
    tags = jnp.asarray(tag_table, jnp.int32)
    num_progs = traces.shape[0]
    # hoist the per-step dependent double lookup: precompute the per-program
    # tag and hw-cost streams once (the instruction id itself is only ever
    # used through these two tables)
    ptags = lookup.table_lookup(tags, traces)
    pcosts = lookup.table_lookup(hw, traces)

    init = (init_fleet_state(num_progs, num_slots, bs_entries)
            if state is None else state)
    step = _fleet_step_fn(ptags, pcosts, miss_latency, active_slots,
                          quanta, schedule, handler, bs_miss_extra)
    final, _ = jax.lax.scan(step, init, None, length=total_steps,
                            unroll=scan_unroll)
    return final.result(), final


_simulate_fleet = functools.partial(
    jax.jit, static_argnames=("num_slots", "bs_entries", "total_steps",
                              "scan_unroll"))(_simulate_fleet_impl)


def simulate_many(traces: np.ndarray, cfg: ReconfigConfig,
                  scenarios, sched: SchedulerConfig,
                  total_steps: int = 400_000,
                  scan_unroll: int = SCAN_UNROLL, *,
                  state: FleetState | None = None,
                  return_state: bool = False,
                  num_active: int | None = None,
                  path: str = "auto",
                  use_kernel=None):
    """Round-robin fleet of P programs sharing one reconfigurable core.

    traces: (P, N) int32 instruction ids; `scenarios` is one shared
    `SlotScenario` or a length-P sequence (per-program slot taxonomies).
    `sched` may carry per-program quanta and/or priority weights
    (`SchedulerConfig`); the uniform unit-priority case reproduces the
    paper's round-robin bit-for-bit.

    The scan carry is an explicit value: `state` resumes a prior run's
    `FleetState` (None = cold start), and `return_state=True` additionally
    returns the final state, making the call "run `total_steps` from S ->
    (results, S')".  A run split at any step boundary reproduces the
    one-shot run bit-for-bit (counters are cumulative in the state).

    Dispatch: one-shot result-only calls route through the
    interleave-aware fast path (`repro.core.stackdist_interleaved`),
    preempted or not, whatever the bitstream cache's size (a cache
    smaller than the merged tag set runs the window pass's bitstream
    level); resumed (`state=`) and `return_state=True` calls with a warm
    bitstream cache ride its resumable entry.  Both are bit-for-bit equal
    to the scan: the resumable engine seeds from the `FleetState` (a
    one-shot `return_state` run seeds from the cold init state) and
    materialises the final state back out in canonical form.
    Hand-crafted states no scan could produce (`_seedable_fleet_state`),
    state-carrying runs with a cold bitstream cache, and sub-threshold
    quanta fall back to the cycle-by-cycle scan, whose returned states
    are canonicalised too (`_canonical_state` — behaviour-preserving, so
    resumes and state comparisons never see which engine ran).
    `path="scan"|"interleaved"` forces an engine ("interleaved" raises
    on ineligible or unseedable runs); `use_kernel` picks the
    interleaved engine's window-pass implementation (jnp body or the
    fused Pallas kernel — `repro.kernels.window_distance.resolve`),
    bit-for-bit identical either way.

    `num_active` masks the disambiguator down to its first `num_active`
    slots (a degraded core that came back with fewer usable slots —
    `slots.lookup`'s masking, bit-for-bit an LRU cache of that size).
    Masked runs ride the scan: the interleaved engine seeds full-geometry
    caches only, so `path="interleaved"` raises.  A resumed masked run
    requires every resident inside the active prefix
    (`degrade_fleet_state` produces exactly that), otherwise the inert
    masked residents would be re-sorted into live slots on
    canonicalisation.
    """
    traces = jnp.asarray(traces, jnp.int32)
    if traces.ndim != 2:
        raise ValueError(
            f"simulate_many expects (P, N) traces, got shape "
            f"{tuple(traces.shape)}")
    num_progs = traces.shape[0]
    table = fleet_tag_table(scenarios, num_progs)
    schedule = sched.schedule(num_progs)
    if path not in ("auto", "scan", "interleaved"):
        raise ValueError(
            f"unknown path {path!r} — simulate_many accepts "
            f"'auto'|'scan'|'interleaved' (solo unpreempted runs take the "
            f"stack-distance engine through simulate_single/sweep_fleet)")
    quanta = sched.quanta(num_progs)
    active = cfg.num_slots if num_active is None else int(num_active)
    if not 1 <= active <= cfg.num_slots:
        raise ValueError(
            f"num_active must be in [1, {cfg.num_slots}] "
            f"(the allocated slot count), got {num_active}")
    masked = active < cfg.num_slots
    if masked and path == "interleaved":
        raise ValueError(
            "a masked (degraded) disambiguator rides the scan — the "
            "interleaved engine seeds full-geometry caches only; use "
            "path='auto' or 'scan'")
    if state is not None:
        _check_fleet_state(state, num_progs, cfg.num_slots,
                           cfg.bs_cache_entries)
        if masked and bool(np.any(
                np.asarray(state.slot_st.tags)[active:] >= 0)):
            raise ValueError(
                f"num_active={active} masks slots the state still "
                f"populates — apply simulator.degrade_fleet_state first "
                f"so the dead slots hold no residents")
        if int(state.sched_idx) >= schedule.shape[0]:
            raise ValueError(
                f"FleetState scheduler cursor {int(state.sched_idx)} is "
                f"out of range for a priority schedule of length "
                f"{schedule.shape[0]} — resume must use a SchedulerConfig "
                f"whose priority weights produce a schedule at least as "
                f"long as the one the state was built under")
    eligible = interleaved_eligible(
        miss_latencies=[cfg.miss_latency],
        bs_miss_extra=cfg.bs_miss_extra,
        handler_cycles=sched.handler_cycles, total_steps=total_steps)
    if state is None and not return_state:
        # one-shot result-only: no state to seed or materialise
        if path == "interleaved" and not eligible:
            raise ValueError(
                "interleaved path requires non-negative int32-safe costs "
                "(see simulator.interleaved_eligible)")
        if path == "interleaved" or (
                path == "auto" and not masked and eligible
                and _interleaved_auto_ok(
                    quanta[None, :], 1, int(np.max(table)) + 1, total_steps,
                    None)):
            res = _sweep_fleet_interleaved(
                traces[None], table,
                jnp.asarray([cfg.miss_latency], jnp.int32),
                jnp.asarray([cfg.num_slots], jnp.int32), quanta[None, :],
                schedule, sched.handler_cycles, cfg.bs_miss_extra,
                total_steps, None, use_kernel,
                bs_entries=cfg.bs_cache_entries)
            return FleetResult(*(x[0, 0, 0, 0] for x in res))
    else:
        # state-carrying: seed the resumable engine from the given state
        # (or the cold init state for one-shot return_state runs)
        seed_state = state if state is not None else init_fleet_state(
            num_progs, cfg.num_slots, cfg.bs_cache_entries)
        num_tags = _engine_num_tags(table, seed_state)
        worst_step = (int(np.max(isa.INSTR_HW_CYCLES))
                      + int(cfg.miss_latency) + int(cfg.bs_miss_extra)
                      + int(sched.handler_cycles))
        resumable = (not masked and eligible
                     and cfg.bs_cache_entries >= num_tags
                     and _seedable_fleet_state(seed_state, num_tags,
                                               worst_step, total_steps))
        if path == "interleaved" and not resumable:
            raise ValueError(
                "interleaved path requires a warm bitstream cache over the "
                "fleet's merged tag set, non-negative int32-safe costs, "
                "and a scan-shaped FleetState seed with int32 headroom "
                "(see simulator.interleaved_eligible and "
                "simulator._seedable_fleet_state)")
        if path == "interleaved" or (
                path == "auto" and resumable and _interleaved_auto_ok(
                    quanta[None, :], 1, num_tags, total_steps, None)):
            res, final = _resume_fleet_interleaved(
                traces, table, cfg, quanta, schedule, sched.handler_cycles,
                seed_state, total_steps, num_tags, use_kernel)
            if not return_state:
                return res
            return res, _state_from_final(final, seed_state, cfg.num_slots,
                                          cfg.bs_cache_entries, num_tags,
                                          total_steps)
    res, final = _simulate_fleet(
        traces, table, jnp.int32(cfg.miss_latency),
        jnp.int32(active),
        jnp.asarray(quanta),
        jnp.asarray(schedule),
        jnp.int32(sched.handler_cycles), cfg.num_slots,
        cfg.bs_cache_entries, jnp.int32(cfg.bs_miss_extra), total_steps,
        scan_unroll, state)
    return (res, _canonical_state(final)) if return_state else res


# Host spans of the sweep entries' stages, on the profiler's clock (a
# constructor call each when no trace is taken): `sim.plan` (input checks, tag table,
# eligibility, path choice), `sim.stage` (inputs put in an engine's
# form), `sim.launch` (the engine's host dispatch) and `sim.assemble`
# (the result put together), inside one `sim.sweep_fleet` or
# `sim.sweep_bitstream` span per call.  Every span opens and closes on
# the host, outside jit, and waits for nothing on the device.
_span = jax.profiler.TraceAnnotation

# grid cells the sweep engines were asked for, and the cells they were
# launched over (padded fleet rows included), in all and by engine; see
# `cell_counts`
_cells_real = 0
_cells_launched = 0
_ENGINES = ("interleaved", "stackdist", "stackdist_cold", "scan")
_cells_by_engine = dict.fromkeys(
    [f"cells_{e}" for e in _ENGINES] + ["cells_bs_level"], 0)


def _count_cells(real: int, launched: int, engine: str,
                 bs_level: bool = False) -> None:
    global _cells_real, _cells_launched
    _cells_real += int(real)
    _cells_launched += int(launched)
    _cells_by_engine[f"cells_{engine}"] += int(launched)
    if bs_level:
        _cells_by_engine["cells_bs_level"] += int(launched)


def cell_counts() -> dict:
    """Grid cells the sweep engines were asked for (`cells_real`) and
    launched over (`cells_launched`: fleet rows the interleaved engine
    pads to its batch bucket or the mesh count their cells too), summed
    over the process's `sweep_fleet` and `sweep_bitstream` calls and
    `simulate_many`'s one-shot interleaved runs.  The launched cells are
    also counted by the engine that ran them (`cells_interleaved`,
    `cells_stackdist`, `cells_stackdist_cold`, `cells_scan`), and
    `cells_bs_level` counts the interleaved cells whose window pass ran
    the bitstream level (a cache smaller than the merged tag set)."""
    return {"cells_real": _cells_real, "cells_launched": _cells_launched,
            **_cells_by_engine}


@functools.partial(
    jax.jit, static_argnames=("num_slots", "bs_entries", "total_steps",
                              "scan_unroll"))
def _sweep_fleet(fleets, tag_table, miss_latencies, slot_counts, quanta,
                 schedule, handler, num_slots: int, bs_entries: int,
                 bs_miss_extra, total_steps: int,
                 scan_unroll: int) -> FleetResult:
    def one(t, s, lat, qv):
        return _simulate_fleet_impl(
            t, tag_table, lat, s, qv, schedule, handler, num_slots,
            bs_entries, bs_miss_extra, total_steps, scan_unroll)[0]

    f = jax.vmap(one, in_axes=(None, None, 0, None))   # miss-latency axis
    f = jax.vmap(f, in_axes=(None, 0, None, None))     # slot-count axis
    f = jax.vmap(f, in_axes=(0, None, None, None))     # fleet axis
    f = jax.vmap(f, in_axes=(None, None, None, 0))     # quantum axis
    return f(fleets, slot_counts, miss_latencies, quanta)


# the distance profile materializes (total_steps, num_tags)-shaped int32
# temporaries per batched lane; cap chunk_size * total_steps * num_tags so
# the fast path's transient footprint stays bounded (~64 MB per temporary,
# a few alive at once) no matter how many fleets an eligible sweep batches
# or how fine the tag taxonomy is
_STACKDIST_CHUNK_ELEMS = 16_000_000


def _stackdist_chunk(total_steps: int, num_tags: int) -> int:
    return max(1, _STACKDIST_CHUNK_ELEMS
               // max(total_steps * max(num_tags, 1), 1))


def _sweep_fleet_stackdist(fleets, table, lats, counts, bs_miss_extra,
                           total_steps: int) -> FleetResult:
    """Assemble the scan-shaped FleetResult from one stack-distance pass.

    Only valid for eligible (unpreempted) runs: program 0 executes every
    step, programs 1..P-1 never get scheduled (their counters are zero in
    the scan too), and no switch ever fires.  The fleet axis is processed
    in memory-bounded chunks (at most two compiled shapes: full + tail).
    """
    num_progs = fleets.shape[1]
    num_tags = max(int(np.max(np.asarray(table[0]))) + 1, 1)
    chunk = _stackdist_chunk(total_steps, num_tags)
    grids = [
        stackdist.sweep_unpreempted(
            fleets[i:i + chunk, 0, :], table[0], isa.INSTR_HW_CYCLES,
            counts, lats, jnp.int32(bs_miss_extra), num_tags=num_tags,
            total_steps=total_steps)
        for i in range(0, fleets.shape[0], chunk)]
    cycles = jnp.concatenate([g.cycles for g in grids])
    slot_misses = jnp.concatenate([g.slot_misses for g in grids])
    bs_misses = jnp.concatenate([g.bs_misses for g in grids])
    b, k, l = cycles.shape
    zeros = jnp.zeros((b, k, l, num_progs), jnp.int32)
    return FleetResult(
        cycles=zeros.at[..., 0].set(cycles),
        instructions=zeros.at[..., 0].set(jnp.int32(total_steps)),
        slot_misses=zeros.at[..., 0].set(slot_misses[:, :, None]),
        bs_misses=zeros.at[..., 0].set(bs_misses[:, None, None]),
        switches=jnp.zeros((b, k, l), jnp.int32),
    )


def _sweep_fleet_stackdist_cold(fleets, table, lats, counts, bs_entries,
                                bs_miss_extra,
                                total_steps: int) -> FleetResult:
    """Assemble the scan-shaped FleetResult from the stacked cold pass.

    Same unpreempted contract as `_sweep_fleet_stackdist` (program 0 only,
    no switches), but the bitstream-miss count now varies with the slot
    count — the cold cache sees a different miss stream per S — so the
    `bs_misses` field broadcasts over latencies only.  The per-slot-count
    second pass multiplies the transient footprint by K, so the fleet
    chunking divides by it.
    """
    num_progs = fleets.shape[1]
    num_tags = max(int(np.max(np.asarray(table[0]))) + 1, 1)
    chunk = max(1, _stackdist_chunk(total_steps, num_tags)
                // max(int(counts.shape[0]), 1))
    grids = [
        stackdist_cold.sweep_cold(
            fleets[i:i + chunk, 0, :], table[0], isa.INSTR_HW_CYCLES,
            counts, lats, jnp.asarray([bs_entries], jnp.int32),
            jnp.asarray([bs_miss_extra], jnp.int32), num_tags=num_tags,
            total_steps=total_steps)
        for i in range(0, fleets.shape[0], chunk)]
    cycles = jnp.concatenate([g.cycles[:, :, :, 0, 0] for g in grids])
    slot_misses = jnp.concatenate([g.slot_misses for g in grids])
    bs_misses = jnp.concatenate([g.bs_misses[:, :, 0] for g in grids])
    b, k, l = cycles.shape
    zeros = jnp.zeros((b, k, l, num_progs), jnp.int32)
    return FleetResult(
        cycles=zeros.at[..., 0].set(cycles),
        instructions=zeros.at[..., 0].set(jnp.int32(total_steps)),
        slot_misses=zeros.at[..., 0].set(slot_misses[:, :, None]),
        bs_misses=zeros.at[..., 0].set(bs_misses[:, :, None]),
        switches=jnp.zeros((b, k, l), jnp.int32),
    )


def _fleet_mesh():
    """1-D device mesh over the fleet axis, or None on single-device
    hosts (the mesh path must be a no-op there: every BENCH anchor is
    recorded single-device and stays byte-identical)."""
    devs = jax.devices()
    if len(devs) <= 1:
        return None
    return jax.sharding.Mesh(np.array(devs), ("fleet",))


def fleet_mesh_size() -> int:
    """Devices the interleaved sweep shards its fleet axis over (1 on
    single-device hosts).  Batch-building callers (the contention
    model's candidate-group sweeps) round their batch shapes to a
    multiple of this so every shard is full and the padded shape is
    reused across calls."""
    mesh = _fleet_mesh()
    return int(mesh.devices.size) if mesh is not None else 1


def _mesh_sweep_preempted(mesh, part, table, counts, lats, quanta_grid,
                          schedule, handler, bs_miss_extra, num_tags: int,
                          total_steps: int, w: int, use_kernel,
                          bs_entries: int | None = None):
    """Shard one padded fleet chunk across the device mesh: each device
    runs the interleaved sweep (jnp or Pallas-kernel window pass alike)
    over its fleet shard; grid/scalar operands replicate.  Results
    concatenate along the fleet axis, so this is bit-identical to the
    single-device call on the same chunk.  The sharded program is one
    jitted function of the mesh and the static sizes, so a repeat call
    of the same shapes compiles nothing."""
    kernel, interpret = window_distance.resolve(use_kernel)
    return _mesh_sweep_impl(
        part, jnp.asarray(table, jnp.int32),
        jnp.asarray(isa.INSTR_HW_CYCLES, jnp.int32), counts, lats,
        jnp.asarray(quanta_grid, jnp.int32),
        jnp.asarray(schedule, jnp.int32), jnp.int32(handler),
        jnp.int32(bs_miss_extra), mesh=mesh, num_tags=num_tags,
        total_steps=total_steps, window=w, kernel=kernel,
        interpret=interpret,
        bs_entries=stackdist_interleaved.bs_level(bs_entries, num_tags))


@functools.partial(jax.jit, static_argnames=(
    "mesh", "num_tags", "total_steps", "window", "kernel", "interpret",
    "bs_entries"))
def _mesh_sweep_impl(part, table, costs, counts, lats, quanta, schedule,
                     handler, bs_miss_extra, *, mesh, num_tags: int,
                     total_steps: int, window: int, kernel: bool,
                     interpret: bool, bs_entries: int | None):
    spec = jax.sharding.PartitionSpec

    def shard(pt, *operands):
        return stackdist_interleaved._sweep_impl(
            pt, *operands, num_tags=num_tags, total_steps=total_steps,
            window=window, kernel=kernel, interpret=interpret,
            bs_entries=bs_entries)

    operands = (table, costs, counts, lats, quanta, schedule, handler,
                bs_miss_extra)
    out_specs = stackdist_interleaved.InterleavedGrid(
        *([spec(None, "fleet")] * 5))
    return jax.shard_map(
        shard, mesh=mesh,
        in_specs=(spec("fleet"),) + (spec(),) * len(operands),
        out_specs=out_specs, check_vma=False)(part, *operands)


def _sweep_fleet_interleaved(fleets, table, lats, counts, quanta_grid,
                             schedule, handler, bs_miss_extra,
                             total_steps: int, window: int | None,
                             use_kernel=None,
                             bs_entries: int | None = None) -> FleetResult:
    """Serve the full (Q, B, K, L) grid from the interleave-aware engine.

    Each cell replays its own switch points (they are cost-dependent), so
    nothing broadcasts — but the sequential depth per cell is scheduler
    windows, not steps.  The fleet axis is processed in memory-bounded
    chunks, mirroring `_sweep_fleet_stackdist`, and padded up to a bucket
    size so repeat callers with varying batch sizes (the contention
    model's candidate sweeps price groups in batches of 1..8) hit one
    compiled shape instead of one per batch size — compiling this sweep
    costs seconds, replaying a few padded cells costs milliseconds.  On
    multi-device hosts each chunk's fleet axis additionally shards
    across a 1-D device mesh (`jax.shard_map`) — cells are
    independent, so sharding the batch is exact; padding rounds up to
    the device count and padded rows are sliced off as before.
    `use_kernel` picks the window-pass implementation
    (`repro.kernels.window_distance.resolve`); `bs_entries` is the
    bitstream cache's capacity, which below the merged tag set's size
    compiles the window pass's bitstream level in (None: warm).
    """
    num_tags = max(int(np.max(np.asarray(table))) + 1, 1)
    level = stackdist_interleaved.bs_level(bs_entries, num_tags) is not None
    w = _interleaved_window(quanta_grid, total_steps, window)
    cells = quanta_grid.shape[0] * counts.shape[0] * lats.shape[0]
    chunk = max(1, _INTERLEAVED_CHUNK_ELEMS // max(w * num_tags * cells, 1))
    b_total = fleets.shape[0]
    mesh = _fleet_mesh()
    ndev = mesh.devices.size if mesh is not None else 1
    grids, reals = [], []
    for i in range(0, b_total, chunk):
        with _span("sim.stage"):
            part = jnp.asarray(fleets[i:i + chunk])
            if b_total > chunk:
                target = chunk          # tail rides the full-chunk shape
            else:
                target = min(-(-b_total // _INTERLEAVED_BATCH_BUCKET)
                             * _INTERLEAVED_BATCH_BUCKET, chunk)
            target = -(-target // ndev) * ndev  # mesh: divisible shards
            real = part.shape[0]
            reals.append(real)
            pad = target - real
            if pad > 0:
                part = jnp.concatenate(
                    [part, jnp.broadcast_to(part[:1],
                                            (pad,) + part.shape[1:])],
                    axis=0)
        _count_cells(cells * real, cells * target, "interleaved", level)
        with _span("sim.launch"):
            if mesh is not None:
                grids.append(_mesh_sweep_preempted(
                    mesh, part, table, counts, lats, quanta_grid, schedule,
                    handler, bs_miss_extra, num_tags, total_steps, w,
                    use_kernel, bs_entries))
            else:
                grids.append(stackdist_interleaved.sweep_preempted(
                    part, table, isa.INSTR_HW_CYCLES, counts, lats,
                    jnp.asarray(quanta_grid, jnp.int32),
                    jnp.asarray(schedule, jnp.int32), jnp.int32(handler),
                    jnp.int32(bs_miss_extra), num_tags=num_tags,
                    total_steps=total_steps, window=w,
                    bs_entries=bs_entries, use_kernel=use_kernel))
    with _span("sim.assemble"):
        # every chunk drops its own padded rows: on a mesh each chunk,
        # not only the tail, is padded to a multiple of the device count
        return FleetResult(*(jnp.concatenate(
            [g[f][:, :r] for g, r in zip(grids, reals)], axis=1)
            for f in range(5)))


@functools.partial(jax.profiler.annotate_function, name="sim.sweep_fleet")
def sweep_fleet(fleets: np.ndarray, miss_latencies, scenarios,
                sched: SchedulerConfig, *, slot_counts, quanta=None,
                bs_cache_entries: int = 64, bs_miss_extra: int = 100,
                total_steps: int = 400_000, path: str = "auto",
                scan_unroll: int = SCAN_UNROLL,
                interleave_window: int | None = None,
                use_kernel=None) -> FleetResult:
    """One call over the {quanta x fleets x slot counts x miss latencies}
    grid.

    fleets: (B, P, N) int32 traces.  Result axes: (B, K_slots, L_lat, P) —
    or, when `quanta` is given, (Q, B, K_slots, L_lat, P) with the swept
    quantum axis outermost.  Each `quanta` entry is a scalar (shared by
    every program) or a length-P vector of per-program quanta; `quanta=None`
    keeps the historical 3-axis grid at `sched.quantum_cycles`.  Priority
    weights (`sched.priorities`) apply to every cell of the grid.

    Dispatch (see module docstring): grids unpreempted at EVERY quantum
    cell with a warm bitstream cache (`stackdist_eligible`) collapse the
    K x L grid into one stack-distance pass per fleet (quantum cells are
    then identical by construction and broadcast); unpreempted grids with
    a COLD bitstream cache take the stacked pass
    (`stackdist_cold_eligible` / `repro.core.stackdist_cold`) instead of
    the scan; preempted or mixed grids (`interleaved_eligible`) replay
    every cell's own interleaving at scheduler-window granularity, with
    the bitstream level compiled in where `bs_cache_entries` is below the
    fleet's merged tag set (`repro.core.stackdist_interleaved`;
    `interleave_window` overrides the tuned backend-aware window size and
    `use_kernel` the window-pass implementation — jnp body or fused
    Pallas kernel, see `repro.kernels.window_distance.resolve` — results
    identical for any value of either); everything else — negative or
    int32-unsafe costs, and quanta below the auto floor unless the engine
    is forced — runs the jitted vmap^4 of `lax.scan`s,
    where slot counts sweep by masking one max-size disambiguator
    (`slots.lookup`'s `num_active`).  `path` forces a specific engine
    ("stackdist"/"stackdist_cold"/"interleaved" raise if the grid is
    ineligible); all engines return bit-for-bit identical results on
    eligible grids.
    """
    with _span("sim.plan"):
        fleets = jnp.asarray(fleets, jnp.int32)
        if fleets.ndim != 3:
            raise ValueError(
                f"sweep_fleet expects (B, P, N) fleet traces, got shape "
                f"{tuple(fleets.shape)}")
        num_progs = fleets.shape[1]
        table = fleet_tag_table(scenarios, num_progs)
        counts = jnp.asarray(slot_counts, jnp.int32).reshape(-1)
        lats = jnp.asarray(miss_latencies, jnp.int32).reshape(-1)
        if quanta is None:
            quanta_grid = sched.quanta(num_progs)[None, :]      # (1, P)
        else:
            if np.isscalar(quanta) or getattr(quanta, "ndim", None) == 0:
                raise ValueError(
                    f"quanta must be a sequence of quantum cells (scalars "
                    f"or per-program vectors), got bare scalar "
                    f"{quanta!r} — pass quanta=[{quanta!r}] for a "
                    f"single-cell axis")
            quanta = list(quanta)
            if not quanta:
                raise ValueError("quanta needs at least one quantum cell")
            quanta_grid = np.stack([quanta_vector(q, num_progs)
                                    for q in quanta])
        eligible = stackdist_eligible(
            table[0], quantum_cycles=quanta_grid,
            bs_entries=bs_cache_entries,
            max_miss_latency=int(np.max(np.asarray(miss_latencies))),
            bs_miss_extra=bs_miss_extra, total_steps=total_steps)
        inter_eligible = interleaved_eligible(
            miss_latencies=lats, bs_miss_extra=bs_miss_extra,
            handler_cycles=sched.handler_cycles, total_steps=total_steps)
        grid_cells = quanta_grid.shape[0] * counts.shape[0] * lats.shape[0]
        inter_auto = _interleaved_auto_ok(
            quanta_grid, grid_cells, int(np.max(table)) + 1, total_steps,
            interleave_window)
        cold_eligible = stackdist_cold_eligible(
            quantum_cycles=quanta_grid,
            max_miss_latency=int(np.max(np.asarray(miss_latencies))),
            bs_miss_extra=bs_miss_extra, total_steps=total_steps)
        chosen = _check_path(path, eligible, inter_eligible, inter_auto,
                             cold_eligible)
        schedule = sched.schedule(num_progs)
    if chosen != "interleaved":     # which counts its padded chunks itself
        _count_cells(grid_cells * fleets.shape[0],
                     grid_cells * fleets.shape[0], chosen)
    if chosen == "interleaved":
        res = _sweep_fleet_interleaved(
            fleets, table, lats, counts, quanta_grid, schedule,
            sched.handler_cycles, bs_miss_extra, total_steps,
            interleave_window, use_kernel, bs_entries=bs_cache_entries)
    elif chosen == "scan":
        with _span("sim.stage"):
            s_max = int(np.max(np.asarray(slot_counts)))
            quanta_d, schedule_d = (jnp.asarray(quanta_grid),
                                    jnp.asarray(schedule))
            handler = jnp.int32(sched.handler_cycles)
            extra = jnp.int32(bs_miss_extra)
        with _span("sim.launch"):
            res = _sweep_fleet(fleets, table, lats, counts, quanta_d,
                               schedule_d, handler, s_max, bs_cache_entries,
                               extra, total_steps, scan_unroll)
    else:
        with _span("sim.launch"):
            if chosen == "stackdist":
                res = _sweep_fleet_stackdist(fleets, table, lats, counts,
                                             bs_miss_extra, total_steps)
            else:
                res = _sweep_fleet_stackdist_cold(
                    fleets, table, lats, counts, bs_cache_entries,
                    bs_miss_extra, total_steps)
        if quanta is None:
            return res
        # every quantum cell is unpreempted, so cells are identical:
        # broadcast the one reconstructed grid over the quantum axis
        with _span("sim.assemble"):
            q = quanta_grid.shape[0]
            return FleetResult(*(jnp.broadcast_to(x[None], (q,) + x.shape)
                                 for x in res))
    if quanta is None:
        with _span("sim.assemble"):
            return FleetResult(*(x[0] for x in res))
    return res


@functools.partial(jax.profiler.annotate_function,
                   name="sim.sweep_bitstream")
def sweep_bitstream(traces: np.ndarray, scenario: isa.SlotScenario, *,
                    slot_counts, miss_latencies, bs_entries, bs_miss_extras,
                    total_steps: int,
                    path: str = "auto") -> stackdist_cold.ColdGrid:
    """Solo-program sweep over the full reconfiguration-cost design space:
    {slot count x miss latency x bitstream capacity x bitstream penalty}.

    traces: (B, N) int32 solo instruction traces, run unpreempted.
    Returns a `stackdist_cold.ColdGrid` with (B, K, L, E, X) cycles,
    (B, K) slot misses and (B, K, E) bitstream misses — the axes
    `benchmarks/bitstream_study.py` studies, in one call.

    Dispatch: eligible runs (`stackdist_cold_eligible` — unpreempted is
    by construction here, so only the int32 guard matters) take the
    stacked Mattson pass, one profile per (trace, slot count) serving the
    whole capacity x penalty sub-grid; `path="scan"` forces one
    cycle-by-cycle run per grid cell (the parity reference).
    """
    with _span("sim.plan"):
        traces = jnp.asarray(traces, jnp.int32)
        if traces.ndim != 2:
            raise ValueError(
                f"sweep_bitstream expects (B, N) solo traces, got shape "
                f"{tuple(traces.shape)}")
        counts = np.asarray(slot_counts, np.int32).reshape(-1)
        lats = np.asarray(miss_latencies, np.int32).reshape(-1)
        caps = np.asarray(bs_entries, np.int32).reshape(-1)
        extras = np.asarray(bs_miss_extras, np.int32).reshape(-1)
        cold_ok = stackdist_cold_eligible(
            quantum_cycles=NO_PREEMPT_QUANTUM,
            max_miss_latency=int(np.max(lats)),
            bs_miss_extra=int(np.max(extras)), total_steps=total_steps)
        if path not in ("auto", "stackdist_cold", "scan"):
            raise ValueError(
                f"unknown path {path!r} — sweep_bitstream accepts "
                f"'auto'|'stackdist_cold'|'scan'")
        if path == "stackdist_cold" and not cold_ok:
            raise ValueError(
                "stacked cold-bitstream path requires an unpreempted run "
                "with int32-safe costs (see "
                "simulator.stackdist_cold_eligible)")
    b = traces.shape[0]
    shape = (b, counts.size, lats.size, caps.size, extras.size)
    fast = path != "scan" and cold_ok
    _count_cells(np.prod(shape), np.prod(shape),
                 "stackdist_cold" if fast else "scan")
    if fast:
        with _span("sim.stage"):
            args = (jnp.asarray(counts), jnp.asarray(lats),
                    jnp.asarray(caps), jnp.asarray(extras))
        with _span("sim.launch"):
            return stackdist_cold.sweep_cold(
                traces, scenario.instr_tag, isa.INSTR_HW_CYCLES, *args,
                num_tags=max(scenario.num_tags, 1), total_steps=total_steps)
    # reference fallback: one scan per cell (slot/bitstream misses do not
    # depend on the latency/penalty axes in an unpreempted run, so the
    # counter fields come from the first L x X cell)
    cycles = np.zeros(shape, np.int32)
    slot_misses = np.zeros(shape[:2], np.int32)
    bs_misses = np.zeros((b, counts.size, caps.size), np.int32)
    with _span("sim.launch"):
        for i in range(b):
            stream = traces[i][jnp.remainder(
                jnp.arange(total_steps, dtype=jnp.int32), traces.shape[-1])]
            for k, s in enumerate(counts):
                for e, cap in enumerate(caps):
                    for l, lat in enumerate(lats):
                        for x, pen in enumerate(extras):
                            r = simulate_single(
                                stream,
                                ReconfigConfig(num_slots=int(s),
                                               miss_latency=int(lat),
                                               bs_cache_entries=int(cap),
                                               bs_miss_extra=int(pen)),
                                scenario, path="scan")
                            cycles[i, k, l, e, x] = int(r.cycles)
                            slot_misses[i, k] = int(r.slot_misses)
                            bs_misses[i, k, e] = int(r.bs_misses)
    with _span("sim.assemble"):
        return stackdist_cold.ColdGrid(cycles=jnp.asarray(cycles),
                                       slot_misses=jnp.asarray(slot_misses),
                                       bs_misses=jnp.asarray(bs_misses))


# --- pair path: the P=2 special case, kept as thin wrappers so the Fig. 7
# --- numbers stay reproducible bit-for-bit through the fleet machinery


def simulate_pair(traces: np.ndarray, cfg: ReconfigConfig,
                  scenario: isa.SlotScenario, sched: SchedulerConfig,
                  total_steps: int = 400_000) -> PairResult:
    r = simulate_many(traces, cfg, scenario, sched, total_steps)
    return PairResult(r.cycles, r.instructions, r.slot_misses, r.switches)


def simulate_pair_batch(traces: np.ndarray, cfg: ReconfigConfig,
                        scenario: isa.SlotScenario, sched: SchedulerConfig,
                        total_steps: int = 400_000) -> PairResult:
    """traces: (B, P, N) — one-cell sweep over the pair lanes."""
    r = sweep_fleet(
        jnp.asarray(traces, jnp.int32), [cfg.miss_latency], scenario, sched,
        slot_counts=[cfg.num_slots], bs_cache_entries=cfg.bs_cache_entries,
        bs_miss_extra=cfg.bs_miss_extra, total_steps=total_steps)
    # squeeze the singleton slot-count / latency axes -> (B, P) like before
    return PairResult(r.cycles[:, 0, 0], r.instructions[:, 0, 0],
                      r.slot_misses[:, 0, 0], r.switches[:, 0, 0])


# ---------------------------------------------------------------------------
# Fixed-ISA analytic helpers (Fig. 4 baselines; pair variant for Fig. 7)
# ---------------------------------------------------------------------------


def fixed_fleet_cpi(mix: Mix, spec: isa.Spec, sched: SchedulerConfig,
                    program_index: int = 0) -> float:
    """CPI of a fixed-ISA machine inside a round-robin fleet (any P).

    The handler executes `handler_cycles` of base instructions once per
    quantum; amortised per original instruction that is
    handler * CPI / quantum — independent of how many programs share the
    core, since every program pays it once per own quantum.  Priority
    weights don't change CPI either (they change wall-clock share, not
    per-instruction cost).  With heterogeneous quanta, pass the program's
    index so its own quantum amortises the handler.
    """
    cpi = analytic_cpi(mix, spec)
    q = np.asarray(sched.quantum_cycles).reshape(-1)
    quantum = int(q[program_index if q.size > 1 else 0])
    return cpi * (1.0 + sched.handler_cycles / quantum)


# historical name from the pair-only simulator; the formula never depended
# on the fleet size, so the P=2 name is just an alias now
fixed_pair_cpi = fixed_fleet_cpi
