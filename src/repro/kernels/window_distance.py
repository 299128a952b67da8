"""Fused Pallas kernel for the interleaved engine's window pass.

`repro.core.stackdist_interleaved._simulate_cell` commits one scheduler
window per `lax.while_loop` iteration: gather the scheduled program's
next W accesses, build the (W, num_tags) occurrence matrix, one `cummax`
pass for the merged-stream stack distances, classify cold/miss, cumsum
the cycle costs, search the quantum-expiry point, and fold the committed
prefix back into the carried per-tag `last_pos` vector.  Under XLA each
of those steps is its own HBM-round-trip over the (W, num_tags) `occ` /
`cm` intermediates, multiplied by the vmap^4 grid.

This module fuses the whole pass — last-occurrence update, stack
distance, cold/miss classification, the bitstream level where the
bitstream cache is smaller than the tag set (a second lane scan over the
slot-miss positions, compiled in only then), cost cumsum and
quantum-expiry search — into ONE Pallas kernel.  The per-tag `last_pos`
vector (and in materialise mode or with the bitstream level
`last_miss_pos`) lives in vector registers as the
`while_loop` carry for the whole cell run, the per-program counters and
cursors live in SMEM, and the (num_tags, W) matrices exist only as
in-kernel values and never hit HBM.  Two entry points share one kernel:

* `window_grid` — the one-shot counter-tuple sweep: one `pallas_call`
  whose grid is the full {quantum x fleet x slots x latency} cell grid
  (each grid step runs one cell's entire while-loop), returning the
  `InterleavedGrid` counter arrays.
* `window_cell` — the seeded/`materialise` single-cell form behind
  `resume_preempted`: accepts the engine-coordinate seed and returns the
  full final `CellCarry` field tuple (cumulative counters plus the
  per-tag occurrence vectors the simulator turns back into a
  `FleetState`).

TPU layout: the matrices are (tags, window) — the window runs along the
128 lanes and the tags along the 8 sublanes, so a window's per-access
vectors are single rows and a taxonomy of ~10 tags costs two sublane
tiles instead of a 128-lane pad.  The window read is an aligned load of
`w_pad + 128` lanes from the VMEM-resident trace row followed by a
dynamic lane rotation (Mosaic refuses an unaligned dynamic lane slice),
and every scalar the loop indexes with (schedule, quanta, cursors,
counters) lives in SMEM.

All arithmetic is int32 and mirrors the jnp body operation-for-
operation (the cumulative max/sum use a log-doubling rotate scan —
exact for integers), so interpret mode (`pl.pallas_call(...,
interpret=True)`) is bit-for-bit equal to the jnp engine on any backend;
CPU CI proves it without a chip (tests/test_window_kernel.py), and
tests/test_tpu_compile.py compiles the kernel for a described v5e.
Dispatch policy lives in `resolve()`: compiled Pallas on TPU,
interpret-mode parity path on CPU when the kernel is forced, and the jnp
body as the always-available fallback (the CPU default — interpret mode
is a correctness vehicle, not a fast path).

Like its siblings in this package the kernel is shape-generic and knows
nothing about the RISC-V alphabet; callers pass pre-gathered (P, N) tag
and cost streams.  The tag axis is padded to the 8-sublane boundary and
the window to the 128-lane boundary (padded tags never occur in any
stream and padded lanes carry tag -1 / cost 0, so both pads are inert —
see the parity argument in tests/test_window_kernel.py).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["window_grid", "window_cell", "resolve", "set_default_mode",
           "DEFAULT_MODE"]

_LANES = 128      # TPU lane width: window-axis pad boundary
_SUBLANES = 8     # TPU sublane width: tag-axis pad boundary
_INT_MIN = int(jnp.iinfo(jnp.int32).min)

# knob vocabulary for the `use_kernel` dispatch (see `resolve`); the
# session-wide default can be preset via the REPRO_WINDOW_KERNEL env var
# (benchmarks/run.py --interpret sets it) or `set_default_mode`.
_MODES = ("auto", "kernel", "interpret", "jnp")
DEFAULT_MODE = os.environ.get("REPRO_WINDOW_KERNEL", "auto")


def set_default_mode(mode: str) -> None:
    """Set the session default `use_kernel` mode ('auto'|'kernel'|
    'interpret'|'jnp') that `resolve(None)` falls back to."""
    global DEFAULT_MODE
    if mode not in _MODES:
        raise ValueError(f"unknown window-kernel mode {mode!r} "
                         f"(expected one of {_MODES})")
    DEFAULT_MODE = mode


def resolve(use_kernel=None) -> tuple[bool, bool]:
    """Resolve a `use_kernel` knob value to (run_kernel, interpret).

    None -> the session default mode (env REPRO_WINDOW_KERNEL or 'auto');
    True/'kernel' -> the kernel, compiled on TPU and interpret-mode
    elsewhere; 'interpret' -> the kernel in interpret mode everywhere
    (the CPU parity path); False/'jnp' -> the jnp window pass.  'auto'
    picks the compiled kernel on TPU and the jnp body on CPU, where
    interpret mode would be strictly slower than XLA's fused loop.
    """
    mode = use_kernel
    if mode is None:
        mode = DEFAULT_MODE
    elif mode is True:
        mode = "kernel"
    elif mode is False:
        mode = "jnp"
    if mode not in _MODES:
        raise ValueError(f"unknown use_kernel value {use_kernel!r} "
                         f"(expected None/bool or one of {_MODES})")
    tpu = jax.default_backend() == "tpu"
    if mode == "auto":
        return tpu, False
    if mode == "kernel":
        return True, not tpu
    if mode == "interpret":
        return True, True
    return False, False


def _interp(interpret) -> bool:
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def _lane_scan(x: jnp.ndarray, op, unit) -> jnp.ndarray:
    """Inclusive scan along lanes (axis 1) by log-doubling rotations —
    exact for the integer max/add monoids, and built from static lane
    rotations only so it lowers inside a kernel body."""
    n = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < n:
        x = op(x, jnp.where(lane >= shift, pltpu.roll(x, shift, 1), unit))
        shift *= 2
    return x


def _pads(window: int, num_tags: int, trace_len: int):
    """(w_pad, t_pad, ext): padded window lanes, padded tag sublanes, and
    the wrapped trace length that keeps every aligned window read of
    `w_pad + 128` lanes starting below `trace_len` in bounds."""
    w_pad = _round_up(max(int(window), 1), _LANES)
    t_pad = _round_up(max(int(num_tags), 1), _SUBLANES)
    ext = _round_up(trace_len, _LANES) + w_pad
    return w_pad, t_pad, ext


# SMEM counter block: five (P,) fields, in this order
_CURSORS, _CYCLES, _INSTRS, _MISSES, _BS_MISSES = range(5)


def _kernel(counts_ref, lats_ref, quanta_ref, sched_ref, misc_ref, seed_ref,
            tags_ref, costs_ref, seed_last_ref, out_ref, out_last_ref,
            cnt_ref, *, num_progs, trace_len, total_steps, window, w_pad,
            pos_base, materialise, bs_entries):
    """One cell per grid step: `_simulate_cell`'s while-loop with every
    window intermediate kept on-chip.  `tags_ref`/`costs_ref` hold the
    fleet's (P, ext) wrapped streams; `seed_ref` is the flat SMEM seed
    (five (P,) counter fields, then sched_idx, q_cycles, switches).
    `bs_entries` (static, or None for a warm cache) adds the bitstream
    level: a second lane scan over the slot-miss positions."""
    q, k, l = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    num_active = counts_ref[k]
    miss_latency = lats_ref[l]
    handler, bs_extra = misc_ref[0], misc_ref[1]
    sched_len = sched_ref.shape[0]
    span = w_pad + _LANES
    for i in range(5 * num_progs):
        cnt_ref[i] = seed_ref[i]

    t_pad = seed_last_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w_pad), 1)
    valid = lane < window
    tag_ids = jax.lax.broadcasted_iota(jnp.int32, (t_pad, w_pad), 0)

    def read_window(ref, p, start):
        # aligned superset read, then rotate lane `start` down to lane 0
        base = pl.multiple_of((start // _LANES) * _LANES, _LANES)
        row = ref[0, pl.ds(p, 1), pl.ds(base, span)]
        row = pltpu.roll(row, (span - (start - base)) % span, 1)
        return row[:, :w_pad]

    def body(c):
        last_pos, last_miss, sched_idx, steps_done, q_cycles, switches = c
        p = sched_ref[sched_idx]
        cursor = cnt_ref[_CURSORS * num_progs + p]
        start = jax.lax.rem(cursor, jnp.int32(trace_len))
        # padded lanes are inert: tag -1 never slots, cost 0 keeps the
        # cost cumsum flat past the real window
        w_tags = jnp.where(valid, read_window(tags_ref, p, start), -1)
        w_hw = jnp.where(valid, read_window(costs_ref, p, start), 0)
        slotted = w_tags >= 0

        pos = pos_base + steps_done + lane
        match = w_tags == tag_ids
        occ = jnp.where(match, pos, -1)
        cm = _lane_scan(occ, jnp.maximum, _INT_MIN)
        # state observed by each access: the previous lane's cummax (lane
        # 0 sees nothing in-window) floored with the carried last_pos
        prev = jnp.maximum(
            jnp.where(lane >= 1, pltpu.roll(cm, 1, 1), -1), last_pos)
        sel = jnp.maximum(w_tags, 0) == tag_ids
        prev_self = jnp.sum(jnp.where(sel, prev, 0), axis=0, keepdims=True)
        cold = slotted & (prev_self < 0)
        dist = jnp.sum((prev > prev_self).astype(jnp.int32), axis=0,
                       keepdims=True)
        miss = slotted & (cold | (dist >= num_active))

        track_miss = materialise or bs_entries is not None
        if track_miss:
            cm_miss = _lane_scan(jnp.where(match & miss, pos, -1),
                                 jnp.maximum, _INT_MIN)
        if bs_entries is None:
            bs_miss = cold
        else:
            # the bitstream cache's state before each access: the miss
            # stream's previous lane floored with the carried last_miss
            prev2 = jnp.maximum(
                jnp.where(lane >= 1, pltpu.roll(cm_miss, 1, 1), -1),
                last_miss)
            prev2_self = jnp.sum(jnp.where(sel, prev2, 0), axis=0,
                                 keepdims=True)
            dist2 = jnp.sum((prev2 > prev2_self).astype(jnp.int32), axis=0,
                            keepdims=True)
            bs_miss = miss & ((prev2_self < 0) | (dist2 >= bs_entries))

        cost = (w_hw + jnp.where(miss, miss_latency, 0)
                + jnp.where(bs_miss, bs_extra, 0))
        cum = q_cycles + _lane_scan(cost, jnp.add, 0)
        expire = cum >= quanta_ref[q * num_progs + p]
        # padded lanes repeat cum[window-1], so the first expiring lane is
        # always a real one when any real lane expires
        first = jnp.min(jnp.where(expire, lane, w_pad))
        any_exp = first < w_pad
        n_exp = jnp.where(any_exp, first + 1, jnp.int32(window))
        remaining = total_steps - steps_done
        n = jnp.minimum(n_exp, remaining)
        do_switch = any_exp & (n_exp <= remaining)

        last_lane = lane == n - 1
        committed = jnp.max(jnp.where(last_lane, cm, -1), axis=1,
                            keepdims=True)
        end_cum = jnp.sum(jnp.where(last_lane, cum, 0))
        if track_miss:
            last_miss = jnp.maximum(last_miss, jnp.max(
                jnp.where(last_lane, cm_miss, -1), axis=1, keepdims=True))
        run_cycles = end_cum - q_cycles + jnp.where(do_switch, handler, 0)
        in_run = lane < n

        def add(field, delta):
            idx = field * num_progs + p
            cnt_ref[idx] = cnt_ref[idx] + delta

        add(_CURSORS, n)
        add(_CYCLES, run_cycles)
        add(_INSTRS, n)
        add(_MISSES, jnp.sum((miss & in_run).astype(jnp.int32)))
        add(_BS_MISSES, jnp.sum((bs_miss & in_run).astype(jnp.int32)))
        return (jnp.maximum(last_pos, committed), last_miss,
                jnp.where(do_switch, (sched_idx + 1) % sched_len,
                          sched_idx),
                steps_done + n,
                jnp.where(do_switch, 0, end_cum),
                switches + do_switch.astype(jnp.int32))

    seed_sca = 5 * num_progs
    init = (seed_last_ref[...],
            jnp.full((t_pad, 1), -1, jnp.int32),
            seed_ref[seed_sca], jnp.int32(0), seed_ref[seed_sca + 1],
            seed_ref[seed_sca + 2])
    last_pos, last_miss, sched_idx, steps_done, q_cycles, switches = \
        jax.lax.while_loop(lambda c: c[3] < total_steps, body, init)

    # counters out as one (8, 128) tile: rows 0-4 the (P,) fields in lanes
    # 0..P-1, row 5 lanes 0-3 (sched_idx, steps_done, q_cycles, switches)
    rows = jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 1)
    tile = jnp.zeros((8, _LANES), jnp.int32)
    for f in range(5):
        for i in range(num_progs):
            tile = jnp.where((rows == f) & (cols == i),
                             cnt_ref[f * num_progs + i], tile)
    for j, v in enumerate((sched_idx, steps_done, q_cycles, switches)):
        tile = jnp.where((rows == 5) & (cols == j), v, tile)
    out_ref[0] = tile
    lanes_t = jax.lax.broadcasted_iota(jnp.int32, (t_pad, _LANES), 1)
    out_last_ref[0] = jnp.where(lanes_t == 0, last_pos,
                                jnp.where(lanes_t == 1, last_miss, -1))


def _run(ptags, pcosts, slot_counts, miss_latencies, quanta, schedule,
         handler, bs_miss_extra, seed=None, *, num_tags: int,
         total_steps: int, window: int, pos_base: int, materialise: bool,
         interpret, name: str, bs_entries: int | None = None):
    """The shared pallas_call: (B, P, N) streams over the (Q, B, K, L)
    cell grid, every cell starting from the same `seed` — None (cold) or
    (flat SMEM seed, (num_tags,) last_pos).  Returns the (Q, B, K, L, 8,
    128) counter tiles and the (Q, B, K, L, t_pad, 128) last_pos (lane 0)
    / last_miss (lane 1) tiles.  `name` is the kernel's HLO instruction
    name, which device traces show whatever jit encloses the call.
    `bs_entries` is the capacity of a bitstream cache smaller than the
    tag set (None: warm), compiled into the kernel."""
    ptags = jnp.asarray(ptags, jnp.int32)
    num_fleets, num_progs, trace_len = ptags.shape
    if num_progs > _LANES:
        raise ValueError(f"window kernel supports at most {_LANES} "
                         f"programs per fleet, got {num_progs}")
    slot_counts = jnp.asarray(slot_counts, jnp.int32).reshape(-1)
    miss_latencies = jnp.asarray(miss_latencies, jnp.int32).reshape(-1)
    quanta = jnp.asarray(quanta, jnp.int32).reshape(-1, num_progs)
    nq, nk, nl = quanta.shape[0], slot_counts.shape[0], \
        miss_latencies.shape[0]
    w_pad, t_pad, ext = _pads(window, num_tags, trace_len)
    # wrap each stream past its end so an aligned read of w_pad + 128
    # lanes from any start in [0, trace_len) stays in bounds
    reps = -(-ext // trace_len)
    pcosts = jnp.asarray(pcosts, jnp.int32)
    tags_t = jnp.tile(ptags, (1, 1, reps))[..., :ext]
    costs_t = jnp.tile(pcosts, (1, 1, reps))[..., :ext]
    misc = jnp.stack([jnp.asarray(handler, jnp.int32),
                      jnp.asarray(bs_miss_extra, jnp.int32)])
    seed_last = jnp.full((t_pad, 1), -1, jnp.int32)
    if seed is None:
        seed_flat = jnp.zeros((5 * num_progs + 3,), jnp.int32)
    else:
        seed_flat, s_last = seed
        seed_last = seed_last.at[:num_tags, 0].set(s_last)
    kernel = functools.partial(
        _kernel, num_progs=num_progs, trace_len=trace_len,
        total_steps=int(total_steps), window=int(window), w_pad=w_pad,
        pos_base=pos_base, materialise=materialise, bs_entries=bs_entries)
    ncells = nq * num_fleets * nk * nl

    def cell(q, b, k, l):
        return (((q * num_fleets + b) * nk + k) * nl + l, 0, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    stream = pl.BlockSpec((1, num_progs, ext), lambda q, b, k, l: (b, 0, 0))
    # both streams, double-buffered, with the sublane axis padded to 8
    stream_bytes = 2 * 2 * _round_up(num_progs, _SUBLANES) * ext * 4
    tiles, last = pl.pallas_call(
        kernel,
        grid=(nq, num_fleets, nk, nl),
        in_specs=[smem] * 6 + [stream, stream,
                               pl.BlockSpec((t_pad, 1),
                                            lambda q, b, k, l: (0, 0))],
        out_specs=[pl.BlockSpec((1, 8, _LANES), cell),
                   pl.BlockSpec((1, t_pad, _LANES), cell)],
        out_shape=[jax.ShapeDtypeStruct((ncells, 8, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((ncells, t_pad, _LANES),
                                        jnp.int32)],
        scratch_shapes=[pltpu.SMEM((5 * num_progs,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=stream_bytes + (16 << 20)),
        interpret=_interp(interpret),
        name=name,
    )(slot_counts, miss_latencies, quanta.reshape(-1),
      jnp.asarray(schedule, jnp.int32).reshape(-1), misc, seed_flat,
      tags_t, costs_t, seed_last)
    shape = (nq, num_fleets, nk, nl)
    return (tiles.reshape(shape + (8, _LANES)),
            last.reshape(shape + (t_pad, _LANES)))


@functools.partial(jax.jit, static_argnames=("num_tags", "total_steps",
                                             "window", "interpret",
                                             "bs_entries"))
def window_grid(ptags, pcosts, slot_counts, miss_latencies, quanta,
                schedule, handler, bs_miss_extra, *, num_tags: int,
                total_steps: int, window: int, interpret=None,
                bs_entries: int | None = None):
    """One-shot counter sweep: (B, P, N) pre-gathered tag/cost streams ->
    the 5 `InterleavedGrid` arrays, one fused-kernel cell per point of
    the (Q, B, K, L) Pallas grid.  `bs_entries` below `num_tags` runs
    the bitstream level; None is a warm cache.  Bit-for-bit equal to
    `stackdist_interleaved.sweep_preempted`'s jnp path."""
    num_progs = ptags.shape[1]
    tiles, _ = _run(ptags, pcosts, slot_counts, miss_latencies, quanta,
                    schedule, handler, bs_miss_extra, num_tags=num_tags,
                    total_steps=total_steps, window=window, pos_base=0,
                    materialise=False, interpret=interpret,
                    name="window_grid", bs_entries=bs_entries)
    return (tiles[..., _CYCLES, :num_progs], tiles[..., _INSTRS, :num_progs],
            tiles[..., _MISSES, :num_progs],
            tiles[..., _BS_MISSES, :num_progs], tiles[..., 5, 3])


@functools.partial(jax.jit, static_argnames=("num_tags", "total_steps",
                                             "window", "seeded",
                                             "materialise", "interpret"))
def window_cell(ptags, pcosts, num_active, miss_latency, quanta, schedule,
                handler, bs_miss_extra, seed=None, *, num_tags: int,
                total_steps: int, window: int, seeded: bool | None = None,
                materialise: bool = True, interpret=None):
    """One cell through the fused kernel: (P, N) streams (+ optional
    engine-coordinate seed) -> the full `CellCarry` field tuple in
    declaration order.  `seed` is (last_pos, cursors, sched_idx,
    q_cycles, cycles, instrs, misses, bs_misses, switches); None starts
    cold.  Matches `_simulate_cell(..., seed=seed,
    materialise=materialise)` bit-for-bit (its counter-tuple form is the
    tail of the returned fields)."""
    if seeded is None:
        seeded = seed is not None
    num_progs = ptags.shape[0]
    if seed is not None:
        (s_last, s_cursors, s_sched, s_qc, s_cycles, s_instrs, s_misses,
         s_bsm, s_switches) = seed
        seed = (jnp.concatenate(
            [jnp.asarray(x, jnp.int32).reshape(-1) for x in
             (s_cursors, s_cycles, s_instrs, s_misses, s_bsm, s_sched,
              s_qc, s_switches)]), jnp.asarray(s_last, jnp.int32))
    tiles, last = _run(
        jnp.asarray(ptags)[None], jnp.asarray(pcosts)[None], num_active,
        miss_latency, quanta, schedule, handler, bs_miss_extra, seed,
        num_tags=num_tags, total_steps=total_steps, window=window,
        pos_base=num_tags if seeded else 0, materialise=bool(materialise),
        interpret=interpret, name="window_cell")
    tile, last = tiles[0, 0, 0, 0], last[0, 0, 0, 0]
    vec = tile[:5, :num_progs]
    return (last[:num_tags, 0], last[:num_tags, 1], vec[_CURSORS],
            tile[5, 0], tile[5, 1], tile[5, 2], vec[_CYCLES], vec[_INSTRS],
            vec[_MISSES], vec[_BS_MISSES], tile[5, 3])
