"""Instruction disambiguator — a functional, jittable fully-associative cache.

Paper §IV, Fig. 2: the disambiguator is a small fully-associative L0 cache
whose tags are instruction opcodes (plus function fields).  On a hit it
multiplexes the operands to the slot holding the implementation; on a miss it
requests the bitstream from the bitstream cache and reconfigures the LRU
victim slot, paying a (technology-dependent) reconfiguration latency.

This module gives exact LRU semantics as a pure function over a small state
pytree, so the same machinery runs

  * inside the cycle-approximate core simulator (`lax.scan` over a trace),
  * batched over experiment configurations (`vmap`),
  * per-device inside `shard_map` for the TPU expert-slot runtime
    (`repro.core.expert_slots`).

State is intentionally tiny (two int32 vectors + a scalar clock) so it can
live in registers/SMEM when embedded in kernels.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

EMPTY = np.int32(-1)   # a host scalar: importing makes no device array


class SlotState(NamedTuple):
    """Disambiguator state.

    tags:     (S,) int32 — tag resident in each slot, -1 when empty.
    last_use: (S,) int32 — LRU clock value of the slot's last touch.
    clock:    ()   int32 — monotonically increasing use counter.
    """

    tags: jnp.ndarray
    last_use: jnp.ndarray
    clock: jnp.ndarray


def init(num_slots: int) -> SlotState:
    return SlotState(
        tags=jnp.full((num_slots,), EMPTY, dtype=jnp.int32),
        last_use=jnp.zeros((num_slots,), dtype=jnp.int32),
        clock=jnp.int32(0),
    )


class LookupResult(NamedTuple):
    state: SlotState
    hit: jnp.ndarray          # () bool — tag was resident (or unslotted)
    slot: jnp.ndarray         # () int32 — slot serving the tag (-1 unslotted)
    evicted_tag: jnp.ndarray  # () int32 — tag displaced on a fill, else -1


def _access(state: SlotState, tag: jnp.ndarray,
            num_active: jnp.ndarray | None = None):
    """Shared LRU core: hit-test + victim fill, one implementation.

    Returns (new_state, hit, slot, unslotted, victim) so both the full
    `lookup` (which also reports the evicted tag) and the lean fused
    fleet-scan path (`lookup_fused`, which only needs state + hit) build on
    exactly the same eviction logic and can never drift apart.
    """
    tag = jnp.asarray(tag, jnp.int32)
    unslotted = tag < 0

    matches = state.tags == tag
    if num_active is not None:
        in_active = (jnp.arange(state.tags.shape[0], dtype=jnp.int32)
                     < jnp.asarray(num_active, jnp.int32))
        matches = matches & in_active
    hit_any = jnp.any(matches) & ~unslotted
    hit_slot = jnp.argmax(matches).astype(jnp.int32)

    # LRU victim: prefer empty slots (their last_use is forced to int32 min)
    empties = state.tags == EMPTY
    use_key = jnp.where(empties, jnp.iinfo(jnp.int32).min, state.last_use)
    if num_active is not None:
        use_key = jnp.where(in_active, use_key, jnp.iinfo(jnp.int32).max)
    victim = jnp.argmin(use_key).astype(jnp.int32)

    slot = jnp.where(hit_any, hit_slot, victim)

    clock = state.clock + 1
    do_touch = ~unslotted
    new_tags = jnp.where(
        do_touch & ~hit_any,
        state.tags.at[slot].set(tag),
        state.tags,
    )
    new_last = jnp.where(
        do_touch,
        state.last_use.at[slot].set(clock),
        state.last_use,
    )
    new_state = SlotState(tags=new_tags, last_use=new_last, clock=clock)
    return new_state, hit_any | unslotted, slot, unslotted, victim


def lookup(state: SlotState, tag: jnp.ndarray,
           num_active: jnp.ndarray | None = None) -> LookupResult:
    """Access `tag`; fill the LRU victim on a miss.  tag == -1 is unslotted
    (a hardwired base instruction) and leaves the state untouched but still
    reports hit=True so callers charge no reconfiguration latency.

    `num_active` (optional, traced) restricts the cache to the first
    `num_active` slots: inactive slots never match and are never victims,
    which makes the state behave exactly like an LRU cache of that size.
    This turns the slot *count* — normally a static shape — into a sweepable
    runtime value: allocate the max size once, `vmap` over `num_active`.
    """
    tag = jnp.asarray(tag, jnp.int32)
    new_state, hit, slot, unslotted, victim = _access(state, tag, num_active)
    # a miss that filled an empty slot displaced nothing: tags[victim] is
    # already EMPTY in that case, so no extra guard is needed
    evicted = jnp.where(hit | unslotted, EMPTY, state.tags[victim])
    return LookupResult(
        state=new_state,
        hit=hit,
        slot=jnp.where(unslotted, EMPTY, slot),
        evicted_tag=evicted,
    )


def lookup_fused(slot_state: SlotState, bs_state: SlotState,
                 tag: jnp.ndarray,
                 num_active: jnp.ndarray | None = None):
    """One fused disambiguator + bitstream-cache access — the fleet scan's
    hot pair (paper §IV: a disambiguator miss fetches the bitstream through
    the bitstream cache; a miss there goes to the unified L2).

    Semantically identical to

        res = lookup(slot_state, tag, num_active)
        bs  = lookup(bs_state, where(res.hit, EMPTY, tag))

    but skips the victim-reporting outputs neither cache consumer uses, so
    the per-step state update inside `lax.scan` stays minimal.  Returns
    (slot_state, bs_state, hit, bs_hit).
    """
    tag = jnp.asarray(tag, jnp.int32)
    slot_state, hit, _, _, _ = _access(slot_state, tag, num_active)
    bs_state, bs_hit, _, _, _ = _access(
        bs_state, jnp.where(hit, EMPTY, tag))
    return slot_state, bs_state, hit, bs_hit


def lookup_batch(state: SlotState, tags: jnp.ndarray,
                 num_active: jnp.ndarray | None = None
                 ) -> tuple[SlotState, jnp.ndarray]:
    """Sequentially access a vector of tags; returns (state, hits bool vector).

    A thin `lax.scan` over `lookup` — used by the expert-slot runtime where a
    token block touches a sequence of expert ids on one device.  `num_active`
    masks the pool down exactly like `lookup`'s, so the expert-slot runtime
    can sweep pool sizes over one max-size state the same way the simulator
    sweeps disambiguator sizes.
    """

    def step(st, tag):
        r = lookup(st, tag, num_active)
        return r.state, r.hit

    return jax.lax.scan(step, state, tags)


def invalidate(state: SlotState, idx) -> SlotState:
    """SEU surgery: kill the residents at entry indices `idx`.

    The hit entries become empty (tag -1, last_use 0) exactly as if they
    had never been filled; the clock and every surviving resident are
    untouched, so the survivors keep their relative LRU order.  This is
    the fault-injection primitive behind `simulator.seu_fleet_state` —
    a single-event upset corrupts a slot's configuration bits, so its
    implementation must be re-loaded (and re-pays the reconfiguration
    latency) on next use.
    """
    idx = jnp.asarray(idx, jnp.int32).reshape(-1)
    return SlotState(tags=state.tags.at[idx].set(EMPTY),
                     last_use=state.last_use.at[idx].set(0),
                     clock=state.clock)


def occupancy(state: SlotState) -> jnp.ndarray:
    return jnp.sum(state.tags != EMPTY)


def resident(state: SlotState, tag: jnp.ndarray) -> jnp.ndarray:
    """Non-mutating residency probe (no LRU touch)."""
    return jnp.any(state.tags == jnp.asarray(tag, jnp.int32)) & (tag >= 0)


def resident_many(state: SlotState, tags: jnp.ndarray) -> jnp.ndarray:
    """Vectorized `resident`: (T,) bool residency per probed tag, no LRU
    touch.  Used by the online re-placement layer to measure how much of a
    tenant's slotted working set is still warm in a core's disambiguator
    (the fraction a migration to a cold core would have to re-fault)."""
    tags = jnp.asarray(tags, jnp.int32)
    return jnp.any(state.tags[None, :] == tags[:, None], axis=1) & (tags >= 0)
