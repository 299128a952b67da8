"""Gather-free lookups over small axes (`repro.core.lookup`).

Each helper must equal the indexing op it replaces bit for bit: the
opcode tables against `jnp.take` / `take_along_axis`, the tag-axis pick
against `take_along_axis`, the histogram against `jnp.bincount`.  The
engine cases then pin every entry that runs the helpers to the counters
the gather-based engines produced on the same fixed-seed inputs (the
digests below were recorded from them), so a lookup that drifted by one
element anywhere in a stream shows.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isa, lookup, simulator
from repro.core import stackdist, stackdist_cold
from repro.core import stackdist_interleaved as sdi

I32 = np.int32


def _rng(seed):
    return np.random.default_rng(seed)


def _covering(rng, n, shape):
    """Random indices in [0, n) of `shape` whose first n entries (in C
    order) are a permutation of every index: every table entry is hit."""
    flat = rng.integers(0, n, int(np.prod(shape))).astype(I32)
    flat[:n] = rng.permutation(n)
    return flat.reshape(shape)


# ---------------------------------------------------------------------------
# table_lookup: table[..., idx] over the opcode alphabet
# ---------------------------------------------------------------------------

# (table shape, index shape): a shared table, a per-program table under
# one fleet and under a batch of fleets, the edge lengths 1 and 128
TABLE_CASES = {
    "shared": ((isa.NUM_INSTRUCTIONS,), (5, 400)),
    "shared-scalar-axes": ((isa.NUM_INSTRUCTIONS,), (3, 2, 4, 90)),
    "per-program": ((3, isa.NUM_INSTRUCTIONS), (3, 500)),
    "per-program-batched": ((2, isa.NUM_INSTRUCTIONS), (4, 2, 300)),
    "length-1": ((1,), (2, 50)),
    "length-128": ((128,), (1000,)),
}


@pytest.mark.parametrize("case", TABLE_CASES)
def test_table_lookup_matches_indexing(case):
    tshape, ishape = TABLE_CASES[case]
    rng = _rng(11)
    table = rng.integers(-1, 1 << 20, tshape).astype(I32)
    idx = _covering(rng, tshape[-1], ishape)
    got = lookup.table_lookup(jnp.asarray(table), jnp.asarray(idx))
    if table.ndim == 1:
        want = jnp.take(jnp.asarray(table), jnp.asarray(idx))
    else:   # per-program rows align with the index's second-last axis
        rows = jnp.broadcast_to(jnp.asarray(table),
                                ishape[:-1] + tshape[-1:])
        want = jnp.take_along_axis(rows, jnp.asarray(idx), axis=-1)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# pick_along_tags: prev[..., i, tags[i]] over the tag axis
# ---------------------------------------------------------------------------

# (rows, tags, leading batch axes, how the -1 "unslotted" tags are given)
PICK_CASES = {
    "scenario-2": (2000, 10, (), "clamped"),
    "scenario-1": (700, 29, (), "clamped"),
    "one-tag": (300, 1, (), "clamped"),
    "all-unslotted": (200, 10, (), "all-clamped"),
    "batched": (400, 10, (3, 2), "clamped"),
    "128-tags": (600, 128, (), "clamped"),
    "unclamped-picks-zero": (500, 10, (), "raw"),
}


@pytest.mark.parametrize("case", PICK_CASES)
def test_pick_along_tags_matches_take_along_axis(case):
    n, t, batch, mode = PICK_CASES[case]
    rng = _rng(12)
    prev = rng.integers(-1, 5 * n, batch + (n, t)).astype(I32)
    tags = rng.integers(-1, t, batch + (n,)).astype(I32)
    tags.reshape(-1, n)[:, :t] = rng.permutation(t)
    if mode == "all-clamped":
        tags[...] = -1
    safe = np.clip(tags, 0, None)
    want = jnp.take_along_axis(jnp.asarray(prev),
                               jnp.asarray(safe)[..., None], axis=-1)[..., 0]
    if mode == "raw":   # a tag outside [0, T) picks 0
        got = lookup.pick_along_tags(jnp.asarray(prev), jnp.asarray(tags))
        want = jnp.where(jnp.asarray(tags) >= 0, want, 0)
    else:
        got = lookup.pick_along_tags(jnp.asarray(prev), jnp.asarray(safe))
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# small_bincount: the stack-distance histogram
# ---------------------------------------------------------------------------

# (accesses, length, the values drawn from): every bucket, a few buckets
# with the rest left empty, only the overflow bucket, no accesses at all
BINCOUNT_CASES = {
    "every-bucket": (5000, 11, range(11)),
    "sparse-empty-buckets": (3000, 11, (0, 3, 10)),
    "overflow-only": (800, 11, (10,)),
    "no-accesses": (0, 11, (0,)),
    "length-1": (100, 1, (0,)),
    "length-128": (4000, 128, range(0, 128, 3)),
}


@pytest.mark.parametrize("case", BINCOUNT_CASES)
def test_small_bincount_matches_bincount(case):
    n, length, values = BINCOUNT_CASES[case]
    rng = _rng(13)
    bucket = rng.choice(np.asarray(list(values), I32), n).astype(I32)
    got = lookup.small_bincount(jnp.asarray(bucket), length)
    want = jnp.bincount(jnp.asarray(bucket), length=length)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("helper", ["table_lookup", "pick_along_tags",
                                    "small_bincount"])
def test_helpers_refuse_axes_over_128(helper):
    big = jnp.zeros((4, lookup.MAX_AXIS + 1), jnp.int32)
    idx = jnp.zeros((4,), jnp.int32)
    call = {"table_lookup": lambda: lookup.table_lookup(big[0], idx),
            "pick_along_tags": lambda: lookup.pick_along_tags(big, idx),
            "small_bincount": lambda: lookup.small_bincount(
                idx, lookup.MAX_AXIS + 1)}[helper]
    with pytest.raises(ValueError, match="at most 128"):
        call()


# ---------------------------------------------------------------------------
# engine entries: counters equal to the gather-based engines' on a fixed
# seed (digest = sha256 of every int32 output leaf, shape and bytes)
# ---------------------------------------------------------------------------

def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.ascontiguousarray(np.asarray(leaf), dtype=I32)
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:24]


def _fleets(b, p, n, seed=5):
    return _rng(seed).integers(0, isa.NUM_INSTRUCTIONS,
                               (b, p, n)).astype(I32)


def _preempted_args(p=2):
    # an FM-class program beside an M-class one: the per-program table
    table = simulator.fleet_tag_table(
        [isa.SCENARIO_2, isa.SCENARIO_1][:p] + [isa.SCENARIO_2] * (p - 2), p)
    num_tags = int(table.max()) + 1
    quanta = np.asarray([[300] * p, [2_000] * p], I32)
    return table, num_tags, quanta


def _sweep_preempted(mode):
    table, num_tags, quanta = _preempted_args()
    return sdi.sweep_preempted(
        _fleets(2, 2, 700), table, isa.INSTR_HW_CYCLES,
        jnp.asarray([2, 4], I32), jnp.asarray([10, 50], I32), quanta,
        jnp.asarray([0, 1], I32), 150, 100, num_tags=num_tags,
        total_steps=1_500, window=64, use_kernel=mode)


def _resume_preempted(mode):
    table, num_tags, _ = _preempted_args(3)
    rng = _rng(6)
    last_pos = np.full((num_tags,), -1, I32)
    held = rng.choice(num_tags, 6, replace=False)
    last_pos[held] = np.arange(6, dtype=I32)
    seed = sdi.CellCarry(
        last_pos=jnp.asarray(last_pos),
        last_miss_pos=jnp.full((num_tags,), -1, jnp.int32),
        cursors=jnp.asarray([17, 402, 3], I32), sched_idx=jnp.int32(1),
        steps_done=jnp.int32(0), q_cycles=jnp.int32(40),
        cycles=jnp.asarray([900, 40, 7], I32),
        instrs=jnp.asarray([300, 20, 5], I32),
        misses=jnp.asarray([9, 2, 1], I32),
        bs_misses=jnp.asarray([4, 1, 1], I32), switches=jnp.int32(3))
    return sdi.resume_preempted(
        _fleets(1, 3, 600, seed=7)[0], table, isa.INSTR_HW_CYCLES, 4, 50,
        jnp.asarray([300, 2_000, 500], I32),
        jnp.asarray([0, 1, 2, 1], I32), 150, 100, seed,
        num_tags=num_tags, total_steps=2_000, window=64, use_kernel=mode)


def _sweep_unpreempted(steps):
    s2 = isa.SCENARIO_2
    return stackdist.sweep_unpreempted(
        _fleets(1, 4, 900, seed=8)[0], s2.instr_tag, isa.INSTR_HW_CYCLES,
        jnp.asarray([1, 2, 4, 8], I32), jnp.asarray([10, 50, 250], I32),
        100, num_tags=s2.num_tags, total_steps=steps)


def _sweep_cold(steps):
    s2 = isa.SCENARIO_2
    return stackdist_cold.sweep_cold(
        _fleets(1, 3, 800, seed=9)[0], s2.instr_tag, isa.INSTR_HW_CYCLES,
        jnp.asarray([2, 4], I32), jnp.asarray([50], I32),
        jnp.asarray([2, 4, 8, 16], I32), jnp.asarray([50, 250], I32),
        num_tags=s2.num_tags, total_steps=steps)


def _simulate_many_scan():
    r = simulator.simulate_many(
        _fleets(1, 2, 500, seed=10)[0], simulator.ReconfigConfig(
            num_slots=4, miss_latency=50, bs_cache_entries=6),
        [isa.SCENARIO_2, isa.SCENARIO_1],
        simulator.SchedulerConfig(quantum_cycles=400), total_steps=1_200,
        path="scan")
    return (r.cycles, r.instructions, r.slot_misses, r.bs_misses,
            r.switches)


ENGINE_CASES = {
    "sweep_preempted-jnp": (lambda: _sweep_preempted("jnp"),
                            "803f74d7142a74c830bdc24b"),
    "sweep_preempted-interpret": (lambda: _sweep_preempted("interpret"),
                                  "803f74d7142a74c830bdc24b"),
    "resume_preempted-jnp": (lambda: _resume_preempted("jnp"),
                             "44cd3ef1ea73d4ae7a269974"),
    "resume_preempted-interpret": (lambda: _resume_preempted("interpret"),
                                   "44cd3ef1ea73d4ae7a269974"),
    "sweep_unpreempted-wraps": (lambda: _sweep_unpreempted(2_000),
                                "4586b4fe5938d2003428fb48"),
    "sweep_unpreempted-prefix": (lambda: _sweep_unpreempted(600),
                                 "08f02691d2236aff2500e60f"),
    "sweep_cold-wraps": (lambda: _sweep_cold(1_900),
                         "7f21545c5699d3145f423c42"),
    "sweep_cold-prefix": (lambda: _sweep_cold(800),
                          "587dcdb6e304bcff55561d5b"),
    "simulate_many-scan": (_simulate_many_scan,
                           "117461f651254f73bd76c683"),
}


@pytest.mark.parametrize("case", ENGINE_CASES)
def test_engine_counters_equal_the_gather_based_engines(case):
    run, want = ENGINE_CASES[case]
    assert _digest(run()) == want
