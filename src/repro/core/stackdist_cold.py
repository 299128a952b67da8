"""Stacked Mattson pass: exact cold-bitstream sweeps for unpreempted runs.

The unpreempted engine (`repro.core.stackdist`) needs a *warm* bitstream
cache: warmth makes bitstream misses coincide with cold touches, so the
bitstream term is a slot-count-independent constant and the whole
{slot count x miss latency} grid reconstructs affinely from one distance
profile.  A cold (undersized) bitstream cache breaks that — which
entries it evicts depends on the slot-miss sequence, which depends on
the slot count — and until now such runs fell back to the per-access
`lax.scan`.

They do not need to.  For an unpreempted run, the *access order* is
fixed (no context switches), so at each slot count ``S`` the
disambiguator's miss subsequence — the only accesses that touch the
bitstream cache — is itself a fully determined LRU reference stream.
Stack one more Mattson pass on top of it:

  1. the first pass gives every access's stack distance ``dist`` in the
     tag stream, hence the slot-miss indicator per slot count
     (``miss_S = slotted & (cold | dist >= S)``);
  2. masking the occurrence matrix down to miss positions and running a
     second cummax gives each miss's stack distance *within the miss
     subsequence* — exactly the bitstream cache's LRU stack distance,
     since the bitstream cache sees precisely the miss stream;
  3. a distance histogram per slot count then answers every bitstream
     capacity ``E`` at once:

         bs_misses(S, E) = cold + #{reuse misses with dist2 >= E}
         cycles(S, L, E, X) = base + slot_misses(S) * L
                                   + bs_misses(S, E) * X

     (``cold`` is capacity-independent: a tag's first touch is always
     both a slot miss and a compulsory bitstream miss, so the bitstream
     cold count equals the slot cold count at every S and E).

All arithmetic is int32 like the scan, so results are bit-for-bit
identical whenever the run is unpreempted and overflow-safe
(`repro.core.simulator.stackdist_cold_eligible` guards both; parity is
pinned by tests/test_resume_fastpath.py).  This turns e.g.
`benchmarks/bitstream_study.py`'s capacity x penalty grid — previously
one full scan per cell — into a single jitted call.

Like its siblings, this module is deliberately generic: it knows nothing
about the RISC-V alphabet; callers pass per-opcode tag/cost tables.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.lookup import pick_along_tags, small_bincount
from repro.core.stackdist import _stream

__all__ = ["ColdGrid", "lanes_cold", "sweep_cold"]


class ColdGrid(NamedTuple):
    """Exact counters over the {slot count x latency x bitstream capacity
    x bitstream penalty} grid of one unpreempted run."""

    cycles: jnp.ndarray       # (..., K, L, E, X) int32
    slot_misses: jnp.ndarray  # (..., K) int32
    bs_misses: jnp.ndarray    # (..., K, E) int32


def _cold_one(tags: jnp.ndarray, costs: jnp.ndarray,
              slot_counts: jnp.ndarray, miss_latencies: jnp.ndarray,
              bs_entries: jnp.ndarray, bs_miss_extras: jnp.ndarray,
              num_tags: int) -> ColdGrid:
    """(N,) tag stream (-1 = unslotted) + (N,) hw costs -> ColdGrid."""
    n = tags.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    tag_ids = jnp.arange(num_tags, dtype=jnp.int32)
    match = tags[:, None] == tag_ids[None, :]
    occurrence = jnp.where(match, idx[:, None], jnp.int32(-1))
    last_pos = jax.lax.cummax(occurrence, axis=0)
    prev = jnp.concatenate(
        [jnp.full((1, num_tags), -1, jnp.int32), last_pos[:-1]], axis=0)
    slotted = tags >= 0
    prev_self = pick_along_tags(prev, tags)   # 0 where unslotted
    cold = slotted & (prev_self < 0)
    dist = jnp.sum(prev > prev_self[:, None], axis=1).astype(jnp.int32)

    def per_count(s):
        # the miss subsequence at slot count s, re-profiled as its own
        # LRU reference stream (the bitstream cache sees exactly it)
        miss = slotted & (cold | (dist >= s))
        cm2 = jax.lax.cummax(
            jnp.where(match & miss[:, None], idx[:, None], jnp.int32(-1)),
            axis=0)
        prev2 = jnp.concatenate(
            [jnp.full((1, num_tags), -1, jnp.int32), cm2[:-1]], axis=0)
        prev2_self = pick_along_tags(prev2, tags)
        dist2 = jnp.sum(prev2 > prev2_self[:, None], axis=1).astype(jnp.int32)
        reuse = miss & (prev2_self >= 0)
        bucket = jnp.where(reuse, dist2, jnp.int32(num_tags))
        hist2 = small_bincount(bucket, num_tags + 1)[:num_tags]
        return jnp.sum(miss).astype(jnp.int32), hist2.astype(jnp.int32)

    slot_misses, hist2 = jax.vmap(per_count)(
        jnp.asarray(slot_counts, jnp.int32))        # (K,), (K, num_tags)
    cold_count = jnp.sum(cold).astype(jnp.int32)
    base = jnp.sum(costs).astype(jnp.int32)

    # tail2[s, e] = reuse misses at slot count s with dist2 >= e
    tail2 = jnp.concatenate(
        [jnp.cumsum(hist2[:, ::-1], axis=1)[:, ::-1].astype(jnp.int32),
         jnp.zeros((hist2.shape[0], 1), jnp.int32)], axis=1)
    caps = jnp.clip(jnp.asarray(bs_entries, jnp.int32), 0, num_tags)
    bs_misses = cold_count + tail2[:, caps]          # (K, E)
    lats = jnp.asarray(miss_latencies, jnp.int32)
    extras = jnp.asarray(bs_miss_extras, jnp.int32)
    cycles = (base
              + slot_misses[:, None, None, None] * lats[None, :, None, None]
              + bs_misses[:, None, :, None] * extras[None, None, None, :])
    return ColdGrid(cycles=cycles, slot_misses=slot_misses,
                    bs_misses=bs_misses)


@functools.partial(jax.jit, static_argnames=("num_tags", "total_steps"))
def sweep_cold(traces: jnp.ndarray, instr_tag: jnp.ndarray,
               instr_costs: jnp.ndarray, slot_counts: jnp.ndarray,
               miss_latencies: jnp.ndarray, bs_entries: jnp.ndarray,
               bs_miss_extras: jnp.ndarray, *, num_tags: int,
               total_steps: int) -> ColdGrid:
    """Solo-program sweep: (B, N) traces -> ColdGrid with (B, K, L, E, X)
    cycles.  One stacked profile per (trace, slot count) pair serves the
    whole latency x capacity x penalty sub-grid affinely."""
    tags, costs = _stream(jnp.asarray(traces, jnp.int32), instr_tag,
                          instr_costs, total_steps)
    return jax.vmap(
        lambda t, c: _cold_one(t, c, slot_counts, miss_latencies,
                               bs_entries, bs_miss_extras, num_tags)
    )(tags, costs)


@functools.partial(jax.jit, static_argnames=("num_tags", "total_steps"))
def lanes_cold(traces: jnp.ndarray, instr_tag: jnp.ndarray,
               instr_costs: jnp.ndarray, num_slots, miss_latencies,
               bs_entries, bs_miss_extra, *, num_tags: int,
               total_steps: int):
    """Paired (trace, latency) lanes at one slot count / capacity /
    penalty — the `simulate_single_batch` shape.  Returns
    (cycles, slot_misses, bs_misses), each (B,) int32."""
    tags, costs = _stream(jnp.asarray(traces, jnp.int32), instr_tag,
                          instr_costs, total_steps)
    lats = jnp.asarray(miss_latencies, jnp.int32).reshape(-1)

    def one(t, c, lat):
        g = _cold_one(t, c, jnp.reshape(num_slots, (1,)),
                      jnp.reshape(lat, (1,)), jnp.reshape(bs_entries, (1,)),
                      jnp.reshape(bs_miss_extra, (1,)), num_tags)
        return g.cycles[0, 0, 0, 0], g.slot_misses[0], g.bs_misses[0, 0]

    return jax.vmap(one)(tags, costs, lats)
