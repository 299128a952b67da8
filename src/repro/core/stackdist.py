"""Single-pass LRU stack-distance engine (Mattson et al., 1970).

LRU has the *stack-inclusion* property: the contents of an S-slot LRU cache
are always a subset of an (S+1)-slot one, so one pass over a tag stream
yields exact hit/miss counts for EVERY cache size at once.  An access whose
stack distance (number of distinct slotted tags touched since the previous
access to the same tag) is `d` hits in any cache of more than `d` slots and
misses in every smaller one; first-touch accesses miss at all sizes.

The fleet simulator's sweep grid (`repro.core.simulator.sweep_fleet`)
brute-forces exactly this axis with one `lax.scan` per {slot count x miss
latency} lane.  Whenever a run is

  * **unpreempted** — the round-robin quantum is unreachable, so only
    program 0 is ever scheduled and its trace order is independent of the
    per-step costs (and hence of the miss latency), and
  * **warm-bitstream** — the bitstream cache holds at least as many entries
    as there are distinct tags, so it never evicts and each tag misses it
    exactly once: on its compulsory (first-touch) disambiguator miss,

the whole grid collapses into post-processing of one distance profile:

    slot_misses(S) = cold + #{accesses with distance >= S}
    bs_misses      = cold                    (== distinct slotted tags)
    cycles(S, L)   = sum(hw[instr]) + slot_misses(S) * L
                     + bs_misses * bs_miss_extra

with no handler cycles and zero switches.  All arithmetic is int32, like
the scan it replaces, so eligible results are bit-for-bit identical
(`simulator` guards eligibility so no int32 accumulator can overflow).

The distance computation itself is vectorised rather than scanned: a
(steps, num_tags) last-occurrence matrix built with `lax.cummax` gives each
access's previous-occurrence cursor, and the stack distance is a row-wise
count of tags touched more recently — O(steps * num_tags) elementwise work
with no sequential dependency beyond the cummax, which is far faster than
stepping an LRU state machine.

This module is deliberately generic: it knows nothing about the RISC-V
alphabet.  Callers pass the per-opcode tag and cost tables
(`repro.core.simulator` passes `isa.INSTR_HW_CYCLES`).

Preempted runs cannot use this collapse — their merged access order is
cost-dependent, hence grid-cell-dependent — but they are not scan-only:
`repro.core.stackdist_interleaved` replays each cell's own interleaving
at scheduler-window granularity with the same cummax distance pass.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.lookup import pick_along_tags, small_bincount, table_lookup

__all__ = [
    "DistanceProfile", "SweepGrid",
    "distance_profile", "misses_for_counts", "cycles_grid",
    "sweep_unpreempted", "lanes_unpreempted",
]


class DistanceProfile(NamedTuple):
    """Everything the affine cycle reconstruction needs, per tag stream."""

    hist: jnp.ndarray         # (num_tags,) int32 — hist[d] = reuse accesses
                              # at finite stack distance d
    cold: jnp.ndarray         # () int32 — first-touch accesses; equals the
                              # number of distinct slotted tags in the stream
    base_cycles: jnp.ndarray  # () int32 — sum of per-instruction hw cycles
    steps: jnp.ndarray        # () int32 — stream length (== instructions)


class SweepGrid(NamedTuple):
    """Reconstructed counters over a {slot count x miss latency} grid."""

    cycles: jnp.ndarray       # (..., K, L) int32
    slot_misses: jnp.ndarray  # (..., K) int32 — latency-independent
    bs_misses: jnp.ndarray    # (...,) int32 — size- and latency-independent


def _profile_one(tags: jnp.ndarray, costs: jnp.ndarray,
                 num_tags: int) -> DistanceProfile:
    """(N,) tag stream (-1 = unslotted) + (N,) hw costs -> DistanceProfile."""
    n = tags.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    tag_ids = jnp.arange(num_tags, dtype=jnp.int32)
    # last_pos[i, u] = last position j <= i with tags[j] == u, else -1
    occurrence = jnp.where(tags[:, None] == tag_ids[None, :],
                           idx[:, None], jnp.int32(-1))
    last_pos = jax.lax.cummax(occurrence, axis=0)
    # shift to *strictly before i*: the state the access at i observes
    prev = jnp.concatenate(
        [jnp.full((1, num_tags), -1, jnp.int32), last_pos[:-1]], axis=0)

    slotted = tags >= 0
    prev_self = pick_along_tags(prev, tags)   # 0 where unslotted
    cold = slotted & (prev_self < 0)
    # distinct tags touched after my previous occurrence (excludes myself:
    # prev[i, tags[i]] == prev_self, never strictly greater)
    dist = jnp.sum(prev > prev_self[:, None], axis=1).astype(jnp.int32)

    bucket = jnp.where(slotted & ~cold, dist, jnp.int32(num_tags))
    hist = small_bincount(bucket, num_tags + 1)[:num_tags]
    return DistanceProfile(
        hist=hist.astype(jnp.int32),
        cold=jnp.sum(cold).astype(jnp.int32),
        base_cycles=jnp.sum(costs).astype(jnp.int32),
        steps=jnp.int32(n),
    )


@functools.partial(jax.jit, static_argnames=("num_tags",))
def distance_profile(tags: jnp.ndarray, costs: jnp.ndarray,
                     num_tags: int) -> DistanceProfile:
    """Profile one (N,) tag/cost stream.  num_tags must cover max(tags)+1."""
    return _profile_one(jnp.asarray(tags, jnp.int32),
                        jnp.asarray(costs, jnp.int32), num_tags)


def misses_for_counts(profile: DistanceProfile,
                      slot_counts: jnp.ndarray) -> jnp.ndarray:
    """(K,) exact LRU miss counts, one per requested slot count."""
    num_tags = profile.hist.shape[0]
    # tail[s] = reuse accesses with distance >= s; tail[num_tags] = 0
    tail = jnp.concatenate(
        [jnp.cumsum(profile.hist[::-1])[::-1].astype(jnp.int32),
         jnp.zeros((1,), jnp.int32)])
    counts = jnp.clip(jnp.asarray(slot_counts, jnp.int32), 0, num_tags)
    return profile.cold + tail[counts]


def cycles_grid(profile: DistanceProfile, slot_counts: jnp.ndarray,
                miss_latencies: jnp.ndarray,
                bs_miss_extra) -> SweepGrid:
    """Affine reconstruction over the full {slot count x latency} grid."""
    misses = misses_for_counts(profile, slot_counts)          # (K,)
    lats = jnp.asarray(miss_latencies, jnp.int32)             # (L,)
    cycles = (profile.base_cycles
              + misses[:, None] * lats[None, :]
              + profile.cold * jnp.int32(bs_miss_extra))      # (K, L)
    return SweepGrid(cycles=cycles, slot_misses=misses, bs_misses=profile.cold)


def _stream(traces: jnp.ndarray, instr_tag: jnp.ndarray,
            instr_costs: jnp.ndarray, total_steps: int):
    """Unroll (…, N) instruction traces into (…, total_steps) tag/cost
    streams, wrapping the cursor exactly like the scan path does (the
    trace repeated end to end, cut at `total_steps`)."""
    reps = -(-total_steps // traces.shape[-1])
    stream = jnp.tile(traces, (1,) * (traces.ndim - 1) + (reps,))
    stream = stream[..., :total_steps]
    return (table_lookup(jnp.asarray(instr_tag, jnp.int32), stream),
            table_lookup(jnp.asarray(instr_costs, jnp.int32), stream))


@functools.partial(jax.jit, static_argnames=("num_tags", "total_steps"))
def sweep_unpreempted(traces: jnp.ndarray, instr_tag: jnp.ndarray,
                      instr_costs: jnp.ndarray, slot_counts: jnp.ndarray,
                      miss_latencies: jnp.ndarray, bs_miss_extra, *,
                      num_tags: int, total_steps: int) -> SweepGrid:
    """Solo-program sweep: (B, N) traces -> SweepGrid with (B, K, L) cycles.

    One distance profile per trace — independent of BOTH grid axes — then
    the whole {slot count x latency} grid reconstructs affinely.
    """
    tags, costs = _stream(jnp.asarray(traces, jnp.int32), instr_tag,
                          instr_costs, total_steps)
    profiles = jax.vmap(
        functools.partial(_profile_one, num_tags=num_tags))(tags, costs)
    return jax.vmap(
        lambda p: cycles_grid(p, slot_counts, miss_latencies,
                              bs_miss_extra))(profiles)


@functools.partial(jax.jit, static_argnames=("num_tags", "total_steps"))
def lanes_unpreempted(traces: jnp.ndarray, instr_tag: jnp.ndarray,
                      instr_costs: jnp.ndarray, num_slots: jnp.ndarray,
                      miss_latencies: jnp.ndarray, bs_miss_extra, *,
                      num_tags: int, total_steps: int):
    """Paired (trace, latency) lanes at one slot count — the
    `simulate_single_batch` shape.  Returns (cycles, slot_misses, bs_misses),
    each (B,) int32."""
    tags, costs = _stream(jnp.asarray(traces, jnp.int32), instr_tag,
                          instr_costs, total_steps)
    profiles = jax.vmap(
        functools.partial(_profile_one, num_tags=num_tags))(tags, costs)
    misses = jax.vmap(
        lambda p: misses_for_counts(p, jnp.reshape(num_slots, (1,)))[0]
    )(profiles)
    lats = jnp.asarray(miss_latencies, jnp.int32).reshape(-1)
    cycles = (profiles.base_cycles + misses * lats
              + profiles.cold * jnp.int32(bs_miss_extra))
    return cycles, misses, profiles.cold
