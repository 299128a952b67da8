"""Interleave-aware LRU stack-distance engine for *preempted* fleets.

The unpreempted engine (`repro.core.stackdist`) collapses the whole
{slot count x miss latency} grid into post-processing of one distance
profile, but it is only exact when the scheduler never fires.  Under
preemption that collapse is impossible in principle: the round-robin
quantum is counted in *cycles*, a slot miss burns more of the quantum
than a hit, and how often an access misses depends on the slot count and
miss latency — so the context-switch points, and with them the merged
access order itself, differ per grid cell.  No single merged tag stream
can serve the whole grid.

What *can* be shared is the mathematics.  This module keeps Mattson's
argument — an access to a shared exact-LRU disambiguator hits at slot
count S iff its stack distance in the **merged** (interleaved) stream is
below S, where the stack distance is the number of distinct slotted tags
touched since the access's previous occurrence, regardless of which
program touched them — and drops the sequential granularity from *steps*
to *scheduler windows*.  Per grid cell the engine carries the merged
stream's per-tag last-occurrence vector plus the scheduler state
(per-program cursors, priority-schedule cursor, cycles burnt in the open
quantum) and each `lax.while_loop` iteration commits one window of the
scheduled program's upcoming accesses:

  1. gather a static-size window of the scheduled program's next `W`
     accesses (the trace cursor wraps exactly like the scan's);
  2. one `cummax` pass over the (W, num_tags) occurrence matrix — seeded
     with the carried last-occurrence vector — yields every window
     access's stack distance in the merged stream (the same trick as
     `stackdist._profile_one`, shifted to a non-empty initial state);
  3. distances give misses (miss iff first touch or distance >= S),
     misses give per-access cycle costs, the running cost sum gives the
     quantum-expiry point; the window commits up to that point (or the
     whole window when the quantum survives it — the carried
     quantum-cycle counter resumes it next iteration), last-occurrence /
     cursors / counters advance, and an expiry pays the context-switch
     handler and rotates the weighted round-robin schedule.

The loop runs until `total_steps` accesses committed.  Its trip count is
~ total_steps / W plus one extra iteration per context switch — two to
three orders of magnitude below the per-step scan's trip count — while
every inner operation is a wide vector op over the window: the same
sequential-depth-for-parallel-work trade that bought the unpreempted
path its ~40x, now available in the preempted regime the serving stack
(placement search, online re-placement pricing) actually lives in.

**The bitstream level.**  With a warm bitstream cache (entries >=
distinct tags across *every* program's tag table — the disambiguator
and bitstream cache are shared, so tag streams merge) the cache never
evicts and a bitstream miss happens exactly on each tag's first (cold)
touch in the merged stream.  A smaller cache evicts, and what it evicts
depends on the slot-miss stream, so on the slot count, the latency and
the switch points.  It sees exactly that stream, though: a slot miss
fetches through it and nothing else touches it.  So the window pass
stacks a second Mattson pass on the first, as `repro.core.stackdist_cold`
does for unpreempted runs, per window: the carry holds each tag's last
slot-miss position (`last_miss_pos`), a cummax over the window's
miss-masked occurrence matrix, floored with it, gives the bitstream
cache's state before each access, and a slot miss is a bitstream miss
when its tag never missed before or its distance within the miss stream
(the tags that missed since) reaches the capacity.  Bitstream misses
then cost `bs_miss_extra` in the window's cycle sum, so they move the
switch points exactly as in the scan.  The level is static
(`bs_level`): a warm cache compiles the pass without it.  All
arithmetic is int32 like the scan, so eligible results are bit-for-bit
identical (`repro.core.simulator.interleaved_eligible` guards
non-negative costs and int32 overflow; parity is enforced by
tests/test_stackdist_interleaved.py and tests/test_bitstream_level.py).
The resumable entry below models a warm cache only.

**Resumable runs** (`resume_preempted`): a cell can also start from a
scan `FleetState` instead of a cold stream.  The seed translates cache
contents into the engine's coordinates — every tag gets a *virtual*
last-occurrence position in a block `[0, num_tags)` placed below all
segment positions: evicted-but-bitstream-resident tags take the bottom
of the block (any access to them must re-fault: with a full
disambiguator their stack distance is >= every slot count, and they are
not cold, so no bitstream miss is charged), disambiguator residents sit
above them ordered by LRU `last_use`, untouched tags stay -1 (their
first touch is still the compulsory cold+bitstream miss).  Segment
accesses then occupy positions `num_tags + step`, so one cummax pass
recovers exactly the stack distances a seeded LRU cache would produce.
The open quantum (`q_cycles`), scheduler cursor, per-program trace
cursors and cumulative counters seed the carry directly.  To come back
*out*, the cell additionally tracks each tag's last slot-miss position
(`last_miss_pos`, the bitstream cache's own LRU clock input), which —
together with `last_pos` — is enough to rebuild a `FleetState`
bit-for-bit in canonical slot order (`repro.core.simulator` owns the
translation in `_seed_carry` / `_state_from_final`).

The window size `W` is a pure performance knob, not a correctness
parameter: a quantum larger than the window simply spans several
iterations via the carried quantum-cycle counter.  Like its sibling,
this module is deliberately generic — it knows nothing about the RISC-V
alphabet; callers pass the per-opcode tag and cost tables.

**Kernel dispatch** (`use_kernel`): both entry points accept a knob that
routes the window pass through the fused Pallas kernel
(`repro.kernels.window_distance`) instead of the jnp body above — the
whole per-cell loop runs on-chip with the per-tag `last_pos` vector
resident in registers and the (num_tags, W) occurrence matrices
never materialised in HBM.  `None` defers to the session default
(`window_distance.resolve`: compiled Pallas on TPU, the jnp body on
CPU); `'kernel'`/True forces the kernel (interpret mode off-accelerator);
`'interpret'` forces `pl.pallas_call(..., interpret=True)` — the CPU
parity path CI proves bit-for-bit; `'jnp'`/False forces the always-
available jnp fallback.  Every mode returns bit-identical results
(tests/test_window_kernel.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.lookup import pick_along_tags, table_lookup
from repro.kernels import window_distance

__all__ = ["CellCarry", "InterleavedGrid", "resume_preempted",
           "sweep_preempted"]


class InterleavedGrid(NamedTuple):
    """Per-cell fleet counters over a {quantum x fleet x slots x latency}
    grid — the scan's `FleetResult` fields with (Q, B, K, L, ...) axes."""

    cycles: jnp.ndarray        # (Q, B, K, L, P) int32, incl. handler
    instructions: jnp.ndarray  # (Q, B, K, L, P) int32
    slot_misses: jnp.ndarray   # (Q, B, K, L, P) int32
    bs_misses: jnp.ndarray     # (Q, B, K, L, P) int32
    switches: jnp.ndarray      # (Q, B, K, L) int32


class CellCarry(NamedTuple):
    """One cell's loop carry — also the seed/result type of the resumable
    entry.  Counters are cumulative, so a seeded run keeps accumulating
    on top of the seed's values exactly like a resumed scan would.
    `last_miss_pos` is live only when the cell materialises a resumable
    state (`None` otherwise — an empty pytree node, so the one-shot
    sweep's compiled carry is unchanged); seeds always pass -1s for it
    (segment-local: only misses *since the seed* can move bitstream LRU
    order, earlier order is recovered from the seed state itself)."""

    last_pos: jnp.ndarray       # (num_tags,) merged-stream last occurrence
    last_miss_pos: jnp.ndarray  # (num_tags,) last slot-miss occurrence
    cursors: jnp.ndarray        # (P,) per-program trace cursor
    sched_idx: jnp.ndarray      # () cursor into the priority schedule
    steps_done: jnp.ndarray     # () committed accesses (merged position)
    q_cycles: jnp.ndarray       # () cycles burnt in the open quantum
    cycles: jnp.ndarray         # (P,) attributed cycles (incl. handler)
    instrs: jnp.ndarray         # (P,)
    misses: jnp.ndarray         # (P,) disambiguator misses
    bs_misses: jnp.ndarray      # (P,) bitstream-cache misses
    switches: jnp.ndarray       # () context switches


def _simulate_cell(ptags, pcosts, num_active, miss_latency, quanta,
                   schedule, handler, bs_miss_extra, num_tags: int,
                   total_steps: int, window: int,
                   seed: CellCarry | None = None,
                   materialise: bool = False,
                   bs_entries: int | None = None):
    """One grid cell: (P, N) looked-up tag/cost streams -> counters.

    Mirrors `simulator._fleet_step_fn`'s cost model exactly, one window
    per iteration instead of one access per scan step.  `num_active`,
    `miss_latency` and `quanta` are the cell's coordinates; `schedule`
    is the weighted round-robin turn order shared by the whole grid.

    With a `seed` the cell resumes mid-run: segment positions shift up by
    `num_tags` so the seed's virtual per-tag positions in `[0, num_tags)`
    sit below every new access (see module docstring).  With
    `materialise` (static) the carry additionally tracks per-tag last
    slot-miss positions and the full final carry is returned instead of
    the counter tuple.  With `bs_entries` (static, below `num_tags`) the
    window pass adds the bitstream level (module docstring): the carry
    tracks last slot-miss positions and a bitstream miss is a slot miss
    whose distance within the miss stream reaches `bs_entries`.
    """
    num_progs, trace_len = ptags.shape
    tag_ids = jnp.arange(num_tags, dtype=jnp.int32)
    warange = jnp.arange(window, dtype=jnp.int32)
    sched_len = schedule.shape[0]
    # seeded runs place segment accesses above the seed's virtual block
    pos_base = num_tags if seed is not None else 0

    def cond(c: CellCarry):
        return c.steps_done < total_steps

    def body(c: CellCarry) -> CellCarry:
        p = schedule[c.sched_idx]
        idx = jnp.remainder(c.cursors[p] + warange, trace_len)
        w_tags = jnp.take(ptags[p], idx)
        w_hw = jnp.take(pcosts[p], idx)
        slotted = w_tags >= 0

        # merged-stream stack distances for the whole window in one pass:
        # occ/cummax give each tag's last occurrence at-or-before every
        # window row; shifting by one row and flooring with the carried
        # last_pos yields the state each access observes
        pos = c.steps_done + warange
        if pos_base:
            pos = jnp.int32(pos_base) + pos
        match = w_tags[:, None] == tag_ids[None, :]
        occ = jnp.where(match, pos[:, None], jnp.int32(-1))
        cm = jax.lax.cummax(occ, axis=0)
        prev = jnp.concatenate(
            [c.last_pos[None, :],
             jnp.maximum(cm[:-1], c.last_pos[None, :])], axis=0)
        prev_self = pick_along_tags(prev, w_tags)   # 0 where unslotted
        cold = slotted & (prev_self < 0)
        dist = jnp.sum(prev > prev_self[:, None], axis=1).astype(jnp.int32)
        miss = slotted & (cold | (dist >= num_active))

        # the bitstream cache sees exactly the slot-miss stream: its state
        # before each access is the miss stream's last occurrence per tag
        track_miss = materialise or bs_entries is not None
        if track_miss:
            cm_miss = jax.lax.cummax(
                jnp.where(match & miss[:, None], pos[:, None],
                          jnp.int32(-1)), axis=0)
        if bs_entries is None:
            # warm bitstream cache: a bitstream miss is the cold touch
            bs_miss = cold
        else:
            prev2 = jnp.concatenate(
                [c.last_miss_pos[None, :],
                 jnp.maximum(cm_miss[:-1], c.last_miss_pos[None, :])],
                axis=0)
            prev2_self = pick_along_tags(prev2, w_tags)
            dist2 = jnp.sum(prev2 > prev2_self[:, None],
                            axis=1).astype(jnp.int32)
            bs_miss = miss & ((prev2_self < 0) | (dist2 >= bs_entries))

        # scan cost model: hw + miss latency + bitstream penalty
        cost = (w_hw + jnp.where(miss, miss_latency, 0)
                + jnp.where(bs_miss, bs_miss_extra, 0)).astype(jnp.int32)
        cum = c.q_cycles + jnp.cumsum(cost)
        expire = cum >= quanta[p]
        any_exp = jnp.any(expire)
        # first expiring access executes, then the switch fires — exactly
        # the scan's `q = q_cycles + cost; do_switch = q >= quantum`
        n_exp = jnp.where(any_exp,
                          jnp.argmax(expire).astype(jnp.int32) + 1,
                          jnp.int32(window))
        remaining = (total_steps - c.steps_done).astype(jnp.int32)
        n = jnp.minimum(n_exp, remaining)
        do_switch = any_exp & (n_exp <= remaining)

        committed = jnp.take(cm, n - 1, axis=0)   # per-tag last occ <= n-1
        if track_miss:
            last_miss_pos = jnp.maximum(c.last_miss_pos,
                                        jnp.take(cm_miss, n - 1, axis=0))
        else:
            last_miss_pos = c.last_miss_pos
        end_cum = jnp.take(cum, n - 1)
        run_cycles = (end_cum - c.q_cycles
                      + jnp.where(do_switch, handler, 0).astype(jnp.int32))
        in_run = warange < n
        return CellCarry(
            last_pos=jnp.maximum(c.last_pos, committed),
            last_miss_pos=last_miss_pos,
            cursors=c.cursors.at[p].add(n),
            sched_idx=jnp.where(do_switch,
                                (c.sched_idx + 1) % sched_len,
                                c.sched_idx),
            steps_done=c.steps_done + n,
            q_cycles=jnp.where(do_switch, 0, end_cum).astype(jnp.int32),
            cycles=c.cycles.at[p].add(run_cycles),
            instrs=c.instrs.at[p].add(n),
            misses=c.misses.at[p].add(
                jnp.sum(miss & in_run).astype(jnp.int32)),
            bs_misses=c.bs_misses.at[p].add(
                jnp.sum(bs_miss & in_run).astype(jnp.int32)),
            switches=c.switches + do_switch.astype(jnp.int32),
        )

    if seed is None:
        zeros_p = jnp.zeros((num_progs,), jnp.int32)
        init = CellCarry(
            last_pos=jnp.full((num_tags,), -1, jnp.int32),
            last_miss_pos=(jnp.full((num_tags,), -1, jnp.int32)
                           if materialise or bs_entries is not None
                           else None),
            cursors=zeros_p, sched_idx=jnp.int32(0), steps_done=jnp.int32(0),
            q_cycles=jnp.int32(0), cycles=zeros_p, instrs=zeros_p,
            misses=zeros_p, bs_misses=zeros_p, switches=jnp.int32(0))
    else:
        init = seed._replace(
            last_miss_pos=jnp.full((num_tags,), -1, jnp.int32),
            steps_done=jnp.int32(0))
    final = jax.lax.while_loop(cond, body, init)
    if materialise:
        return final
    return (final.cycles, final.instrs, final.misses, final.bs_misses,
            final.switches)


@functools.partial(jax.jit,
                   static_argnames=("num_tags", "total_steps", "window",
                                    "kernel", "interpret"))
def _resume_impl(fleet, tag_table, instr_costs, num_active, miss_latency,
                 quanta, schedule, handler, bs_miss_extra,
                 seed: CellCarry, *, num_tags: int, total_steps: int,
                 window: int, kernel: bool, interpret: bool) -> CellCarry:
    table = jnp.asarray(tag_table, jnp.int32)
    costs = jnp.asarray(instr_costs, jnp.int32)
    fleet = jnp.asarray(fleet, jnp.int32)
    ptags = table_lookup(table, fleet)
    pcosts = table_lookup(costs, fleet)
    if kernel:
        kseed = (seed.last_pos, seed.cursors, seed.sched_idx,
                 seed.q_cycles, seed.cycles, seed.instrs, seed.misses,
                 seed.bs_misses, seed.switches)
        return CellCarry(*window_distance.window_cell(
            ptags, pcosts, num_active, miss_latency, quanta, schedule,
            handler, bs_miss_extra, seed=kseed, num_tags=num_tags,
            total_steps=total_steps, window=window, materialise=True,
            interpret=interpret))
    return _simulate_cell(ptags, pcosts,
                          jnp.asarray(num_active, jnp.int32),
                          jnp.asarray(miss_latency, jnp.int32),
                          jnp.asarray(quanta, jnp.int32),
                          jnp.asarray(schedule, jnp.int32),
                          jnp.asarray(handler, jnp.int32),
                          jnp.asarray(bs_miss_extra, jnp.int32),
                          num_tags, total_steps, window,
                          seed=seed, materialise=True)


def resume_preempted(fleet: jnp.ndarray, tag_table: jnp.ndarray,
                     instr_costs: jnp.ndarray, num_active, miss_latency,
                     quanta: jnp.ndarray, schedule: jnp.ndarray, handler,
                     bs_miss_extra, seed: CellCarry, *, num_tags: int,
                     total_steps: int, window: int,
                     use_kernel=None) -> CellCarry:
    """One resumable cell: (P, N) traces + engine-coordinate seed ->
    final `CellCarry` (cumulative counters plus the per-tag occurrence
    vectors `repro.core.simulator._state_from_final` turns back into a
    `FleetState`).  The seed is built by `simulator._seed_carry`; its
    `last_miss_pos`/`steps_done` fields are ignored (reset to -1/0).
    `use_kernel` picks the window-pass implementation (module
    docstring); every mode is bit-for-bit identical."""
    kernel, interpret = window_distance.resolve(use_kernel)
    return _resume_impl(fleet, tag_table, instr_costs, num_active,
                        miss_latency, quanta, schedule, handler,
                        bs_miss_extra, seed, num_tags=num_tags,
                        total_steps=total_steps, window=window,
                        kernel=kernel, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("num_tags", "total_steps", "window",
                                    "kernel", "interpret", "bs_entries"))
def _sweep_impl(fleets, tag_table, instr_costs, slot_counts,
                miss_latencies, quanta, schedule, handler, bs_miss_extra,
                *, num_tags: int, total_steps: int, window: int,
                kernel: bool, interpret: bool,
                bs_entries: int | None = None) -> InterleavedGrid:
    table = jnp.asarray(tag_table, jnp.int32)
    costs = jnp.asarray(instr_costs, jnp.int32)
    fleets = jnp.asarray(fleets, jnp.int32)
    # hoist the per-access dependent double lookup out of the loop, like
    # the scan path does: (B, P, N) tag and hw-cost streams
    ptags = table_lookup(table, fleets)
    pcosts = table_lookup(costs, fleets)
    if kernel:
        return InterleavedGrid(*window_distance.window_grid(
            ptags, pcosts, slot_counts, miss_latencies, quanta, schedule,
            handler, bs_miss_extra, num_tags=num_tags,
            total_steps=total_steps, window=window, interpret=interpret,
            bs_entries=bs_entries))

    def one(pt, pc, s, lat, qv):
        return _simulate_cell(pt, pc, s, lat, qv, schedule,
                              jnp.asarray(handler, jnp.int32),
                              jnp.asarray(bs_miss_extra, jnp.int32),
                              num_tags, total_steps, window,
                              bs_entries=bs_entries)

    f = jax.vmap(one, in_axes=(None, None, None, 0, None))   # latency axis
    f = jax.vmap(f, in_axes=(None, None, 0, None, None))     # slot-count
    f = jax.vmap(f, in_axes=(0, 0, None, None, None))        # fleet axis
    f = jax.vmap(f, in_axes=(None, None, None, None, 0))     # quantum axis
    return InterleavedGrid(*f(ptags, pcosts,
                              jnp.asarray(slot_counts, jnp.int32),
                              jnp.asarray(miss_latencies, jnp.int32),
                              jnp.asarray(quanta, jnp.int32)))


def sweep_preempted(fleets: jnp.ndarray, tag_table: jnp.ndarray,
                    instr_costs: jnp.ndarray, slot_counts: jnp.ndarray,
                    miss_latencies: jnp.ndarray, quanta: jnp.ndarray,
                    schedule: jnp.ndarray, handler, bs_miss_extra, *,
                    num_tags: int, total_steps: int, window: int,
                    bs_entries: int | None = None,
                    use_kernel=None) -> InterleavedGrid:
    """Preempted-fleet sweep: (B, P, N) traces -> InterleavedGrid.

    `tag_table` is the (P, num_opcodes) per-program instr->tag table,
    `instr_costs` the shared (num_opcodes,) hw-cycle table, `quanta` the
    (Q, P) swept per-program quantum grid, `schedule` the weighted
    round-robin turn order.  Every {quantum x fleet x slot count x miss
    latency} cell runs its own interleaving (the switch points are
    cost-dependent, see module docstring); cells are independent, so the
    grid is a vmap^4 over one cell engine — or, under `use_kernel` (see
    module docstring), one fused Pallas kernel whose grid is the cell
    grid — axis order matching the scan's `simulator._sweep_fleet`.
    `bs_entries` is the bitstream cache's capacity; below `num_tags` the
    window pass runs the bitstream level (module docstring), otherwise
    (or None) the cache is warm and the level is not compiled in.
    """
    kernel, interpret = window_distance.resolve(use_kernel)
    return _sweep_impl(fleets, tag_table, instr_costs, slot_counts,
                       miss_latencies, quanta, schedule, handler,
                       bs_miss_extra, num_tags=num_tags,
                       total_steps=total_steps, window=window,
                       kernel=kernel, interpret=interpret,
                       bs_entries=bs_level(bs_entries, num_tags))


def bs_level(bs_entries: int | None, num_tags: int) -> int | None:
    """The capacity the window pass models as a bitstream level: the
    cache's entries when they cannot hold all `num_tags` tags, else None
    (a warm cache misses exactly on each tag's cold touch)."""
    if bs_entries is None or int(bs_entries) >= num_tags:
        return None
    return int(bs_entries)
