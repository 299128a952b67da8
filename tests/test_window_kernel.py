"""Window-distance Pallas kernel: interpret-mode parity with the jnp pass.

`repro.kernels.window_distance` fuses the interleaved engine's whole
window pass into one Pallas kernel.  Like every engine in this repo it
is only ever allowed to return results bit-for-bit identical to the
reference (`stackdist_interleaved._simulate_cell`), so the whole suite
runs the kernel in interpret mode (`pl.pallas_call(..., interpret=True)`)
and asserts exact integer equality — CPU CI proves the kernel without a
chip.  Two inertness claims carry the proof from the padded kernel shapes
back to the unpadded jnp pass, and the randomized sweeps below exercise
both:

* tag pad (-> 8 sublanes): padded tag rows never occur in any stream,
  so their `prev` entries stay -1 and are never > `prev_self`, never
  counted in a distance, and commit -1 back into `last_pos`;
* window pad (-> 128 lanes): padded lanes carry tag -1 / cost 0, so the
  cost cumsum is flat past the real window and a padded lane expires iff
  lane `window-1` already did — the first expiring index is always real.

Layout mirrors the PR-5 scan-parity harness (test_stackdist_interleaved):
white-box kernel-vs-jnp checks, dispatcher `use_kernel` semantics, a
fixed-seed always-on randomized sweep, and a hypothesis property that
degrades to the seeded variant when hypothesis is absent.  CI runs the
module under the "ci" hypothesis profile.
"""
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from fleet_asserts import assert_fleet_equal

from repro.core import isa, simulator
from repro.core import stackdist_interleaved as sdi
from repro.kernels import window_distance as wd

CFG = simulator.ReconfigConfig(num_slots=4, miss_latency=50)


# ---------------------------------------------------------------------------
# `use_kernel` knob: resolve() vocabulary + session default
# ---------------------------------------------------------------------------

def test_resolve_knob_mapping():
    accel = jax.default_backend() == "tpu"
    assert wd.resolve("auto") == (accel, False)
    assert wd.resolve("kernel") == (True, not accel)
    assert wd.resolve(True) == (True, not accel)
    assert wd.resolve("interpret") == (True, True)
    assert wd.resolve("jnp") == (False, False)
    assert wd.resolve(False) == (False, False)
    with pytest.raises(ValueError, match="use_kernel"):
        wd.resolve("bogus")


def test_default_mode_setter_feeds_resolve_none():
    old = wd.DEFAULT_MODE
    try:
        wd.set_default_mode("interpret")
        assert wd.resolve(None) == (True, True)
        wd.set_default_mode("jnp")
        assert wd.resolve(None) == (False, False)
        wd.set_default_mode("auto")
        assert wd.resolve(None) == wd.resolve("auto")
        with pytest.raises(ValueError, match="window-kernel mode"):
            wd.set_default_mode("fast")
    finally:
        wd.set_default_mode(old)


# ---------------------------------------------------------------------------
# white-box parity: kernel vs `_simulate_cell`, pre-gathered streams
# ---------------------------------------------------------------------------

TRACE_LEN = 48     # small so interpret mode stays cheap
NUM_TAGS = 7
TOTAL_STEPS = 130  # > 2 * TRACE_LEN: every cursor wraps
# fixed quantum menu: 6 expires mid-window for every window size here,
# 1 << 30 never expires (the solo/unreachable regime)
QUANTUM_MENU = (6, 37, 120, 1 << 30)
# 1 degenerate, 13 unaligned, 64 aligned, 200 > TRACE_LEN (a single
# window wraps the whole trace)
WINDOWS = (1, 13, 64, 200)


@functools.partial(jax.jit, static_argnames=("num_tags", "total_steps",
                                             "window", "materialise"))
def _ref_cell(pt, pc, s, lat, qv, sched, handler, bs, seed=None, *,
              num_tags, total_steps, window, materialise=False):
    return sdi._simulate_cell(pt, pc, s, lat, qv, sched, handler, bs,
                              num_tags, total_steps, window, seed=seed,
                              materialise=materialise)


def _streams(rng, p):
    tags = rng.integers(-1, NUM_TAGS, (p, TRACE_LEN)).astype(np.int32)
    costs = rng.integers(0, 9, (p, TRACE_LEN)).astype(np.int32)
    return jnp.asarray(tags), jnp.asarray(costs)


def _sched_of(p):
    # weighted round-robin: program 0 gets a double turn when p > 1
    return jnp.asarray(list(range(p)) + [0], jnp.int32)


def _check_cell(rng, p, window, quanta_idx, *, seeded, materialise,
                total_steps=TOTAL_STEPS, streams=None):
    """One cell, kernel (interpret) vs jnp, every CellCarry field."""
    tags, costs = _streams(rng, p) if streams is None else streams
    sched = _sched_of(p)
    quanta = jnp.asarray([QUANTUM_MENU[i] for i in quanta_idx[:p]],
                         jnp.int32)
    s, lat, handler, bs = jnp.int32(3), jnp.int32(41), jnp.int32(9), \
        jnp.int32(17)
    kw = dict(num_tags=NUM_TAGS, total_steps=total_steps, window=window)
    if seeded:
        # engine-coordinate seed: virtual last_pos in [-1, num_tags) (the
        # shape `simulator._seed_carry` builds), counters mid-flight
        perm = rng.permutation(NUM_TAGS).astype(np.int32) - 1
        seed = sdi.CellCarry(
            last_pos=jnp.asarray(perm),
            last_miss_pos=jnp.full((NUM_TAGS,), -1, jnp.int32),
            cursors=jnp.asarray(rng.integers(0, 3 * TRACE_LEN, p),
                                jnp.int32),
            sched_idx=jnp.int32(rng.integers(0, p + 1)),
            steps_done=jnp.int32(0),
            q_cycles=jnp.int32(rng.integers(0, QUANTUM_MENU[0])),
            cycles=jnp.asarray(rng.integers(0, 9_000, p), jnp.int32),
            instrs=jnp.asarray(rng.integers(0, 900, p), jnp.int32),
            misses=jnp.asarray(rng.integers(0, 900, p), jnp.int32),
            bs_misses=jnp.asarray(rng.integers(0, 90, p), jnp.int32),
            switches=jnp.int32(rng.integers(0, 40)))
        kseed = (seed.last_pos, seed.cursors, seed.sched_idx,
                 seed.q_cycles, seed.cycles, seed.instrs, seed.misses,
                 seed.bs_misses, seed.switches)
    else:
        seed, kseed = None, None
    got = wd.window_cell(tags, costs, s, lat, quanta, sched, handler, bs,
                         seed=kseed, seeded=seeded,
                         materialise=materialise, interpret=True, **kw)
    if materialise:
        want = _ref_cell(tags, costs, s, lat, quanta, sched, handler, bs,
                         seed=seed, materialise=True, **kw)
        for field, g, w in zip(sdi.CellCarry._fields, got, want):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w),
                err_msg=f"{field} (p={p} window={window} seeded={seeded})")
    else:
        want = _ref_cell(tags, costs, s, lat, quanta, sched, handler, bs,
                         seed=seed, materialise=False, **kw)
        carry = sdi.CellCarry(*got)
        for field, g, w in zip(
                ("cycles", "instrs", "misses", "bs_misses", "switches"),
                (carry.cycles, carry.instrs, carry.misses,
                 carry.bs_misses, carry.switches), want):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w),
                err_msg=f"{field} (p={p} window={window} counter-mode)")
        # non-materialise runs must leave the miss vector untouched
        np.testing.assert_array_equal(np.asarray(carry.last_miss_pos),
                                      np.full((NUM_TAGS,), -1, np.int32))


@pytest.mark.parametrize("window", WINDOWS)
def test_cell_parity_unseeded_materialise(window):
    rng = np.random.default_rng(1_000 + window)
    _check_cell(rng, 3, window, (0, 2, 3), seeded=False, materialise=True)


@pytest.mark.parametrize("window", WINDOWS)
def test_cell_parity_seeded_materialise(window):
    rng = np.random.default_rng(2_000 + window)
    _check_cell(rng, 3, window, (1, 0, 3), seeded=True, materialise=True)


@pytest.mark.parametrize("window", (1, 13, 64))
def test_cell_parity_counter_mode(window):
    rng = np.random.default_rng(3_000 + window)
    _check_cell(rng, 2, window, (0, 3), seeded=False, materialise=False)


def test_grid_parity_full_cell_grid():
    """`window_grid` over a (Q, B, K, L) = (2, 2, 2, 2) grid vs one
    `_simulate_cell` per cell — the counter arrays the one-shot sweep
    serves."""
    rng = np.random.default_rng(4_242)
    p = 3
    ptags = jnp.stack([_streams(rng, p)[0] for _ in range(2)])
    pcosts = jnp.stack([_streams(rng, p)[1] for _ in range(2)])
    counts = jnp.asarray([1, 4], jnp.int32)
    lats = jnp.asarray([0, 73], jnp.int32)
    quanta = jnp.asarray([[6, 37, 120], [1 << 30] * 3], jnp.int32)
    sched = _sched_of(p)
    handler, bs = jnp.int32(11), jnp.int32(23)
    for window in WINDOWS:
        kw = dict(num_tags=NUM_TAGS, total_steps=TOTAL_STEPS,
                  window=window)
        got = wd.window_grid(ptags, pcosts, counts, lats, quanta, sched,
                             handler, bs, interpret=True, **kw)
        want = [np.zeros((2, 2, 2, 2, p), np.int32) for _ in range(4)]
        want.append(np.zeros((2, 2, 2, 2), np.int32))
        for q in range(2):
            for b in range(2):
                for k in range(2):
                    for l in range(2):
                        cell = _ref_cell(ptags[b], pcosts[b], counts[k],
                                         lats[l], quanta[q], sched,
                                         handler, bs, **kw)
                        for i in range(5):
                            want[i][q, b, k, l] = np.asarray(cell[i])
        for i, (g, w) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(
                np.asarray(g), w,
                err_msg=f"grid field {i} window={window}")


# ---------------------------------------------------------------------------
# dispatcher parity: sweep_fleet / simulate_many ride the knob unchanged
# ---------------------------------------------------------------------------

def _preempted_fleet(p=2, n=1_000, seed=7):
    rng = np.random.default_rng(seed)
    return rng.integers(0, isa.NUM_INSTRUCTIONS, (1, p, n)).astype(np.int32)


def test_sweep_fleet_kernel_matches_jnp_and_scan():
    fl = _preempted_fleet()
    sched = simulator.SchedulerConfig(quantum_cycles=700)
    kw = dict(slot_counts=[2, 4], total_steps=2_400, path="interleaved",
              interleave_window=96)
    jnp_r = simulator.sweep_fleet(fl, [10, 50], isa.SCENARIO_2, sched,
                                  use_kernel="jnp", **kw)
    ker_r = simulator.sweep_fleet(fl, [10, 50], isa.SCENARIO_2, sched,
                                  use_kernel="interpret", **kw)
    assert_fleet_equal(jnp_r, ker_r)
    scan = simulator.sweep_fleet(fl, [10, 50], isa.SCENARIO_2, sched,
                                 slot_counts=[2, 4], total_steps=2_400,
                                 path="scan")
    assert_fleet_equal(scan, ker_r)


def test_simulate_many_resume_rides_the_kernel():
    """Split a preempted run, resume through the kernel parity path, and
    require identical results AND identical final FleetState vs the jnp
    engine — the serving stack's warm-state contract."""
    tr = _preempted_fleet(p=3, n=1_200, seed=11)[0]
    sched = simulator.SchedulerConfig(quantum_cycles=900,
                                      priorities=(2, 1, 1))
    _, st = simulator.simulate_many(tr, CFG, isa.SCENARIO_2, sched,
                                    total_steps=1_700, return_state=True)
    outs = {}
    for mode in ("jnp", "interpret"):
        outs[mode] = simulator.simulate_many(
            tr, CFG, isa.SCENARIO_2, sched, total_steps=1_300, state=st,
            return_state=True, path="interleaved", use_kernel=mode)
    assert_fleet_equal(outs["jnp"][0], outs["interpret"][0])
    for la, lb in zip(jax.tree_util.tree_leaves(outs["jnp"][1]),
                      jax.tree_util.tree_leaves(outs["interpret"][1])):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_mesh_sharded_sweep_matches_scan():
    """Fleet axis on a 4-device host mesh (forced via XLA_FLAGS in a
    subprocess): B=3 (non-divisible, exercises the chunk round-up) must
    still equal the scan bit-for-bit, kernel and jnp alike."""
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    script = textwrap.dedent("""
        import numpy as np
        import jax
        from repro.core import isa, simulator
        assert jax.device_count() == 4, jax.devices()
        rng = np.random.default_rng(5)
        fl = rng.integers(0, isa.NUM_INSTRUCTIONS, (3, 2, 400)).astype(
            np.int32)
        sched = simulator.SchedulerConfig(quantum_cycles=500)
        kw = dict(slot_counts=[4], total_steps=900)
        scan = simulator.sweep_fleet(fl, [50], isa.SCENARIO_2, sched,
                                     path="scan", **kw)
        for mode in ("jnp", "interpret"):
            fast = simulator.sweep_fleet(fl, [50], isa.SCENARIO_2, sched,
                                         path="interleaved",
                                         interleave_window=64,
                                         use_kernel=mode, **kw)
            for f, a, b in zip(scan._fields, scan, fast):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b), err_msg=f)
        print("MESH-OK")
    """)
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0 and "MESH-OK" in r.stdout, \
        r.stdout + "\n" + r.stderr


# ---------------------------------------------------------------------------
# randomized parity sweep (seeded always-on + hypothesis ci variant)
# ---------------------------------------------------------------------------

def _check_random_kernel(tag_rows, cost_rows, p, window_idx, quanta_idx,
                         seeded, materialise):
    # the drawn lists become the streams; seeds come from an rng derived
    # deterministically from the case shape, so hypothesis shrinking stays
    # meaningful
    rng = np.random.default_rng(7 + p + 31 * window_idx + 1009 * seeded)
    tags = jnp.asarray(np.resize(np.asarray(tag_rows, np.int32),
                                 (p, TRACE_LEN)))
    costs = jnp.asarray(np.resize(np.asarray(cost_rows, np.int32),
                                  (p, TRACE_LEN)))
    _check_cell(rng, p, WINDOWS[window_idx], quanta_idx, seeded=seeded,
                materialise=materialise, streams=(tags, costs))


def test_seeded_random_kernel_matches_jnp_exactly():
    """Always-on seeded variant: random streams, program counts, window
    sizes, quanta mixes, seeded/unseeded and both materialise modes."""
    rng = np.random.default_rng(20_260_809)
    for i in range(6):
        seeded = bool(i % 2)
        _check_random_kernel(
            tag_rows=rng.integers(-1, NUM_TAGS, 64),
            cost_rows=rng.integers(0, 9, 64),
            p=int(rng.integers(1, 4)),
            window_idx=int(rng.integers(0, len(WINDOWS))),
            quanta_idx=[int(q) for q in
                        rng.integers(0, len(QUANTUM_MENU), 3)],
            seeded=seeded,
            # seeded runs always materialise (the resume contract);
            # i == 2 exercises the unseeded counter-tuple mode
            materialise=seeded or i != 2)


try:  # dev extra, not a runtime dep — only these tests skip without it
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

if HAS_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(
        tag_rows=st.lists(st.integers(-1, NUM_TAGS - 1), min_size=1,
                          max_size=64),
        cost_rows=st.lists(st.integers(0, 8), min_size=1, max_size=64),
        p=st.integers(1, 3),
        window_idx=st.integers(0, len(WINDOWS) - 1),
        quanta_idx=st.lists(st.integers(0, len(QUANTUM_MENU) - 1),
                            min_size=3, max_size=3),
        seeded=st.booleans(),
    )
    def test_kernel_matches_jnp_exactly(tag_rows, cost_rows, p, window_idx,
                                        quanta_idx, seeded):
        """Random streams / taxonomy sizes / quanta mixes: the interpret-
        mode kernel must equal the jnp window pass bit-for-bit, every
        CellCarry field (seeded runs always materialise, matching the
        resume contract)."""
        _check_random_kernel(tag_rows, cost_rows, p, window_idx,
                             quanta_idx, seeded, materialise=True)
else:
    @pytest.mark.skip(reason="hypothesis not installed (dev extra)")
    def test_kernel_matches_jnp_exactly():
        pass
