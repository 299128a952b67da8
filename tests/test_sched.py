"""The scheduling subsystem: heterogeneous quanta / priority weights in the
fleet scan (Layer 1) and contention-aware placement + admission (Layer 2).

The parity section pins PR-2 semantics: uniform-quantum `sweep_fleet`
results are asserted bit-for-bit against golden integers captured from the
pre-subsystem code.  The goldens use raw numpy-Generator draws over the isa
alphabet rather than `traces.build_trace` because they were captured while
build_trace was still `hash()`-seeded (PYTHONHASHSEED-randomised across
processes); build_trace is crc32-seeded and process-deterministic now, but
the synthetic goldens stay independent of the trace synthesizer by design.
"""
import math

import numpy as np
import pytest

from repro.core import isa, scheduler, simulator, traces
from repro.sched import (AdmissionController, ContentionModel, Placement,
                         PlacementConfig, PriorityPolicy, fifo_placement,
                         place_tenants, quantum_grid, random_placement,
                         score_placement)

CFG = simulator.ReconfigConfig(num_slots=4, miss_latency=50)


def synthetic_fleet(b=2, p=3, n=4_000, seed=1234):
    rng = np.random.default_rng(seed)
    return rng.integers(0, isa.NUM_INSTRUCTIONS, (b, p, n)).astype(np.int32)


# shared bit-for-bit equality contract, tests/fleet_asserts.py
from fleet_asserts import assert_fleet_equal  # noqa: E402


# ---------------------------------------------------------------------------
# policy construction
# ---------------------------------------------------------------------------

def test_priority_schedule_construction():
    np.testing.assert_array_equal(simulator.priority_schedule(None, 3),
                                  [0, 1, 2])
    np.testing.assert_array_equal(simulator.priority_schedule((2, 1), 2),
                                  [0, 0, 1])
    np.testing.assert_array_equal(simulator.priority_schedule((1, 3, 2), 3),
                                  [0, 1, 1, 1, 2, 2])
    with pytest.raises(ValueError, match="positive"):
        simulator.priority_schedule((1, 0), 2)
    with pytest.raises(ValueError, match="shape"):
        simulator.priority_schedule((1, 2, 3), 2)


def test_quanta_vector_normalisation():
    np.testing.assert_array_equal(simulator.quanta_vector(5_000, 3),
                                  [5_000] * 3)
    np.testing.assert_array_equal(simulator.quanta_vector((1, 2, 3), 3),
                                  [1, 2, 3])
    with pytest.raises(ValueError, match=r"shape \(2,\)"):
        simulator.quanta_vector((1, 2), 3)
    with pytest.raises(ValueError, match="positive"):
        simulator.quanta_vector(0, 2)


def test_priority_policy_presets():
    pol = PriorityPolicy.weighted((3, 1), quantum_cycles=8_000)
    sched = pol.scheduler()
    assert sched.priorities == (3, 1)
    assert sched.quantum_cycles == 8_000
    np.testing.assert_allclose(pol.cpu_share(2), [0.75, 0.25])

    fb = PriorityPolicy.foreground_background(3, fg_weight=4,
                                              fg_quantum=40_000,
                                              bg_quantum=10_000)
    share = fb.cpu_share(3)
    # fg: 4 * 40K = 160K of 180K total
    np.testing.assert_allclose(share, [160 / 180, 10 / 180, 10 / 180])
    with pytest.raises(ValueError):
        PriorityPolicy.foreground_background(1)

    grid = quantum_grid(5_000, (1_000, 20_000), num_programs=2)
    np.testing.assert_array_equal(grid[0], [5_000, 5_000])
    np.testing.assert_array_equal(grid[1], [1_000, 20_000])
    with pytest.raises(ValueError):
        quantum_grid()


# ---------------------------------------------------------------------------
# PR-2 parity pins (uniform quantum must stay bit-for-bit)
# ---------------------------------------------------------------------------

# golden integers from the pre-subsystem (PR-2) scan on
# synthetic_fleet(2, 3, 4_000, seed=1234), quantum 3_000, SCENARIO_2,
# slot_counts [2, 4], latencies [10, 250], 10_000 steps
PR2_CYCLES = [
    [[[41053, 41061, 38814], [605033, 604706, 601422]],
     [[33289, 31568, 31557], [396887, 394361, 393696]]],
    [[[41026, 41037, 40321], [612738, 610764, 611228]],
     [[34085, 31552, 31553], [406319, 402932, 403026]]]]
PR2_SWITCHES = [[[38, 560], [30, 361]], [[38, 567], [30, 369]]]


def _pin_sweep(sched, **kw):
    return simulator.sweep_fleet(
        synthetic_fleet(), [10, 250], isa.SCENARIO_2, sched,
        slot_counts=[2, 4], total_steps=10_000, path="scan", **kw)


def test_uniform_quantum_sweep_matches_pr2_golden():
    res = _pin_sweep(simulator.SchedulerConfig(quantum_cycles=3_000))
    np.testing.assert_array_equal(np.asarray(res.cycles), PR2_CYCLES)
    np.testing.assert_array_equal(np.asarray(res.switches), PR2_SWITCHES)


def test_interleaved_fast_path_reproduces_pr2_golden():
    """The interleave-aware engine must hit the exact PR-2 golden integers
    on the preempted pin grid — same numbers whichever engine serves."""
    sched = simulator.SchedulerConfig(quantum_cycles=3_000)
    res = simulator.sweep_fleet(
        synthetic_fleet(), [10, 250], isa.SCENARIO_2, sched,
        slot_counts=[2, 4], total_steps=10_000, path="interleaved")
    np.testing.assert_array_equal(np.asarray(res.cycles), PR2_CYCLES)
    np.testing.assert_array_equal(np.asarray(res.switches), PR2_SWITCHES)
    # and auto now serves this grid from the interleaved engine
    auto = simulator.sweep_fleet(
        synthetic_fleet(), [10, 250], isa.SCENARIO_2, sched,
        slot_counts=[2, 4], total_steps=10_000)
    assert_fleet_equal(res, auto)


def test_uniform_vector_and_unit_priorities_reproduce_scalar_exactly():
    """A per-program quantum vector of identical values plus unit priority
    weights must reproduce the uniform scan bit-for-bit."""
    scalar = _pin_sweep(simulator.SchedulerConfig(quantum_cycles=3_000))
    vector = _pin_sweep(simulator.SchedulerConfig(
        quantum_cycles=(3_000, 3_000, 3_000), priorities=(1, 1, 1)))
    assert_fleet_equal(scalar, vector)


def test_simulate_many_uniform_vector_parity():
    tr = synthetic_fleet()[0]
    a = simulator.simulate_many(
        tr, CFG, isa.SCENARIO_2,
        simulator.SchedulerConfig(quantum_cycles=2_500), total_steps=8_000)
    b = simulator.simulate_many(
        tr, CFG, isa.SCENARIO_2,
        simulator.SchedulerConfig(quantum_cycles=(2_500,) * 3),
        total_steps=8_000)
    assert_fleet_equal(a, b)


# ---------------------------------------------------------------------------
# heterogeneous quanta + priorities: behaviour
# ---------------------------------------------------------------------------

def test_priority_weights_shift_instruction_share():
    tr = synthetic_fleet(1, 3)[0]
    kw = dict(total_steps=12_000)
    uni = simulator.simulate_many(
        tr, CFG, isa.SCENARIO_2,
        simulator.SchedulerConfig(quantum_cycles=1_000), **kw)
    wtd = simulator.simulate_many(
        tr, CFG, isa.SCENARIO_2,
        simulator.SchedulerConfig(quantum_cycles=1_000,
                                  priorities=(4, 1, 1)), **kw)
    u = np.asarray(uni.instructions, np.float64)
    w = np.asarray(wtd.instructions, np.float64)
    # uniform: roughly equal share; weighted: program 0 gets ~4x a peer
    assert u.max() / u.min() < 1.3
    assert w[0] / w[1] > 3.0 and w[0] / w[2] > 3.0
    assert w[0] > u[0] * 1.5


def test_per_program_quanta_shift_cycle_share():
    """A longer personal quantum holds the core longer per turn: that
    program retires more instructions at the same step budget."""
    tr = np.stack([traces.build_trace("matmult-int", 6_000, seed=0),
                   traces.build_trace("matmult-int", 6_000, seed=1)])
    base = simulator.simulate_many(
        tr, CFG, isa.SCENARIO_2,
        simulator.SchedulerConfig(quantum_cycles=(1_000, 1_000)),
        total_steps=12_000)
    fav = simulator.simulate_many(
        tr, CFG, isa.SCENARIO_2,
        simulator.SchedulerConfig(quantum_cycles=(8_000, 1_000)),
        total_steps=12_000)
    b = np.asarray(base.instructions, np.float64)
    f = np.asarray(fav.instructions, np.float64)
    assert b[0] / b[1] < 1.2            # equal quanta -> equal share
    assert f[0] / f[1] > 4.0            # 8:1 quanta -> lopsided share
    assert int(fav.switches) < int(base.switches)


def test_sweep_fleet_quanta_axis_matches_individual_runs():
    tensor = synthetic_fleet(2, 2, 2_000)
    quanta = [1_500, (1_500, 6_000)]
    sched = simulator.SchedulerConfig(quantum_cycles=999)  # overridden
    res = simulator.sweep_fleet(
        tensor, [10, 50], isa.SCENARIO_2, sched, slot_counts=[2, 4],
        quanta=quanta, total_steps=6_000, path="scan")
    assert np.asarray(res.cycles).shape == (2, 2, 2, 2, 2)
    for qi, q in enumerate(quanta):
        for b in range(2):
            for li, lat in enumerate((10, 50)):
                one = simulator.simulate_many(
                    tensor[b],
                    simulator.ReconfigConfig(num_slots=4, miss_latency=lat),
                    isa.SCENARIO_2,
                    simulator.SchedulerConfig(quantum_cycles=q),
                    total_steps=6_000)
                np.testing.assert_array_equal(
                    np.asarray(res.cycles)[qi, b, 1, li],
                    np.asarray(one.cycles))
    # without quanta= the historical 4-axis shape survives
    legacy = simulator.sweep_fleet(
        tensor, [10, 50], isa.SCENARIO_2,
        simulator.SchedulerConfig(quantum_cycles=1_500),
        slot_counts=[2, 4], total_steps=6_000, path="scan")
    assert np.asarray(legacy.cycles).shape == (2, 2, 2, 2)
    np.testing.assert_array_equal(np.asarray(legacy.cycles),
                                  np.asarray(res.cycles)[0])
    # malformed quanta axes fail with clear errors, not low-level stack
    # traces
    with pytest.raises(ValueError, match="bare scalar"):
        simulator.sweep_fleet(tensor, [10], isa.SCENARIO_2, sched,
                              slot_counts=[4], quanta=1_500,
                              total_steps=100)
    with pytest.raises(ValueError, match="at least one quantum cell"):
        simulator.sweep_fleet(tensor, [10], isa.SCENARIO_2, sched,
                              slot_counts=[4], quanta=[], total_steps=100)


def test_stackdist_eligibility_under_per_program_quanta():
    """Eligible only when EVERY program's quantum is unreachable: one
    preemptible program anywhere in the vector (or quantum grid) kills
    the fast path."""
    tag_row = isa.SCENARIO_2.instr_tag
    kw = dict(bs_entries=64, max_miss_latency=250, bs_miss_extra=100,
              total_steps=40_000)
    big = simulator.NO_PREEMPT_QUANTUM
    assert simulator.stackdist_eligible(
        tag_row, quantum_cycles=(big, big), **kw)
    assert not simulator.stackdist_eligible(
        tag_row, quantum_cycles=(big, 20_000), **kw)
    assert not simulator.stackdist_eligible(
        tag_row, quantum_cycles=np.array([[big, big], [big, 20_000]]), **kw)
    # forcing the fast path on a partially-preemptible grid raises
    with pytest.raises(ValueError, match="stack-distance"):
        simulator.sweep_fleet(
            synthetic_fleet(1, 2, 1_000), [50], isa.SCENARIO_2,
            simulator.SchedulerConfig(quantum_cycles=(big, 20_000)),
            slot_counts=[4], total_steps=1_000, path="stackdist")


def test_stackdist_quanta_axis_broadcast_matches_scan():
    """An all-unpreempted quanta axis collapses to one stack-distance pass
    broadcast over Q — and must still equal the scan bit-for-bit."""
    tensor = synthetic_fleet(2, 1, 2_000)
    big = simulator.NO_PREEMPT_QUANTUM
    kw = dict(slot_counts=[2, 4], total_steps=2_000,
              quanta=[big, big + 1])
    nop = simulator.SchedulerConfig.no_preempt()
    fast = simulator.sweep_fleet(tensor, [10, 50], isa.SCENARIO_2, nop,
                                 path="stackdist", **kw)
    scan = simulator.sweep_fleet(tensor, [10, 50], isa.SCENARIO_2, nop,
                                 path="scan", **kw)
    assert np.asarray(fast.cycles).shape == (2, 2, 2, 2, 1)
    assert_fleet_equal(fast, scan)


# ---------------------------------------------------------------------------
# satellite: make_fleets(k) properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [3, 4])
def test_make_fleets_count_formula(k):
    fleets = scheduler.make_fleets(k)
    n_fm, n_m = len(traces.FM_BENCHES), len(traces.M_BENCHES)
    assert len(fleets) == math.comb(n_fm, k) + math.comb(n_fm, k - 1) * n_m
    assert len(set(fleets)) == len(fleets)          # no duplicate fleets
    assert all(len(f) == k == len(set(f)) for f in fleets)


@pytest.mark.parametrize("k", [3, 4])
def test_make_fleets_slot_competition_invariant(k):
    """Every fleet carries >= k-1 FM-class members (slot competition is
    guaranteed); insensitive benchmarks never appear."""
    fm = set(traces.FM_BENCHES)
    m = set(traces.M_BENCHES)
    for fleet in scheduler.make_fleets(k):
        assert sum(n in fm for n in fleet) >= k - 1
        assert all(n in fm | m for n in fleet)


def test_make_fleets_custom_pools_follow_formula():
    fm = traces.FM_BENCHES[:4]
    m = traces.M_BENCHES[:3]
    for k in (2, 3, 4):
        fleets = scheduler.make_fleets(k, fm=fm, m=m)
        assert len(fleets) == (math.comb(len(fm), k)
                               + math.comb(len(fm), k - 1) * len(m))
    with pytest.raises(ValueError, match="k-1"):
        scheduler.make_fleets(6, fm=fm, m=m)


# ---------------------------------------------------------------------------
# satellite: shape validation
# ---------------------------------------------------------------------------

def test_simulate_many_rejects_wrong_trace_rank():
    with pytest.raises(ValueError, match=r"\(P, N\).*\(4000,\)"):
        simulator.simulate_many(
            synthetic_fleet()[0, 0], CFG, isa.SCENARIO_2,
            simulator.SchedulerConfig(), total_steps=100)


def test_sweep_fleet_rejects_wrong_fleet_rank():
    with pytest.raises(ValueError, match=r"\(B, P, N\).*\(3, 4000\)"):
        simulator.sweep_fleet(
            synthetic_fleet()[0], [50], isa.SCENARIO_2,
            simulator.SchedulerConfig(), slot_counts=[4], total_steps=100)


def test_fleet_tag_table_reports_offending_shapes():
    with pytest.raises(ValueError, match="2 slot scenarios.*P=3"):
        simulator.fleet_tag_table([isa.SCENARIO_1, isa.SCENARIO_2], 3)
    bad = isa.SlotScenario(name="bad", num_slots=4,
                           instr_tag=np.zeros(5, np.int32))
    with pytest.raises(ValueError, match=r"shape \(5,\)"):
        simulator.fleet_tag_table([isa.SCENARIO_1, bad], 2)


def test_scheduler_config_rejects_mismatched_vectors():
    tr = synthetic_fleet()[0]          # P=3
    with pytest.raises(ValueError, match=r"shape \(2,\)"):
        simulator.simulate_many(
            tr, CFG, isa.SCENARIO_2,
            simulator.SchedulerConfig(quantum_cycles=(1_000, 2_000)),
            total_steps=100)
    with pytest.raises(ValueError, match="priorities"):
        simulator.simulate_many(
            tr, CFG, isa.SCENARIO_2,
            simulator.SchedulerConfig(priorities=(1, 2)), total_steps=100)


# ---------------------------------------------------------------------------
# Layer 2: contention model, placement, admission
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    return ContentionModel(PlacementConfig(
        quantum_cycles=2_000, trace_len=3_000, steps_per_program=4_000))


TENANTS = {"a": "minver", "b": "nbody", "c": "crc32", "d": "tarfind"}


def test_contention_model_caches_and_batches(model):
    groups = [("minver", "crc32"), ("crc32", "minver"), ("nbody",)]
    calls0 = model.sim_calls
    preds = model.predict(groups)
    # canonicalisation: order inside a group is irrelevant
    np.testing.assert_array_equal(preds[0], preds[1])
    assert preds[2].shape == (1,)
    calls_after = model.sim_calls
    again = model.predict(groups)
    assert model.sim_calls == calls_after          # fully cached
    for x, y in zip(preds, again):
        np.testing.assert_array_equal(x, y)
    assert calls_after > calls0


def test_contention_slowdowns_exceed_solo(model):
    """Preempted co-residency with slot competition must predict slowdown
    above 1 for slot-hungry tenants."""
    pred = model.predict([("minver", "nbody")])[0]
    assert pred.shape == (2,)
    assert np.all(pred > 1.0)


def test_score_placement_and_baselines(model):
    cores = fifo_placement(sorted(TENANTS), 2)
    assert [len(c) for c in cores] == [2, 2]
    pl = score_placement(cores, TENANTS, model)
    assert isinstance(pl, Placement)
    assert set(pl.tenant_slowdown) == set(TENANTS)
    assert pl.worst_slowdown >= pl.mean_slowdown > 0
    rnd = random_placement(sorted(TENANTS), 2, seed=3)
    assert sorted(n for c in rnd for n in c) == sorted(TENANTS)


def test_place_tenants_beats_or_matches_all_baselines(model):
    placed = place_tenants(TENANTS, 2, model)
    assert sorted(n for c in placed.cores for n in c) == sorted(TENANTS)
    fifo = score_placement(fifo_placement(sorted(TENANTS), 2), TENANTS,
                           model)
    assert placed.worst_slowdown <= fifo.worst_slowdown + 1e-9
    for seed in range(4):
        rnd = score_placement(random_placement(sorted(TENANTS), 2, seed),
                              TENANTS, model)
        assert placed.objective <= rnd.objective or \
            placed.worst_slowdown <= rnd.worst_slowdown + 1e-9


def test_place_tenants_deterministic(model):
    a = place_tenants(TENANTS, 2, model)
    b = place_tenants(TENANTS, 2, model)
    assert a.cores == b.cores
    assert a.objective == b.objective


def test_admission_loose_slo_admits_all(model):
    dec = AdmissionController(slo=100.0, num_cores=2,
                              model=model).decide(TENANTS)
    assert dec.admitted_all
    assert sorted(dec.admitted) == sorted(TENANTS)
    assert dec.predicted_worst <= 100.0
    assert dec.core_of("a") >= 0


def test_admission_impossible_slo_defers_all(model):
    dec = AdmissionController(slo=1e-6, num_cores=2,
                              model=model).decide(TENANTS)
    assert not dec.admitted
    assert sorted(dec.deferred) == sorted(TENANTS)
    assert math.isnan(dec.predicted_worst)
    assert dec.placement is None
    assert dec.core_of("a") == -1


def test_admission_tight_slo_defers_the_most_contended(model):
    loose = AdmissionController(slo=100.0, num_cores=2,
                                model=model).decide(TENANTS)
    slo = float(loose.predicted_worst) - 1e-6   # just below the best case
    dec = AdmissionController(slo=slo, num_cores=2,
                              model=model).decide(TENANTS)
    assert 0 < len(dec.admitted) < len(TENANTS)
    assert set(dec.admitted) | set(dec.deferred) == set(TENANTS)
    assert dec.predicted_worst <= slo


def test_admission_controller_validation(model):
    with pytest.raises(ValueError):
        AdmissionController(slo=0.0)
    with pytest.raises(ValueError):
        AdmissionController(num_cores=0)
    with pytest.raises(ValueError):
        place_tenants({}, 1, model)
    with pytest.raises(ValueError):
        place_tenants(TENANTS, 0, model)


# ---------------------------------------------------------------------------
# satellite: priority-aware admission (per-tenant SLO weights)
# ---------------------------------------------------------------------------

def test_weighted_admission_protects_the_foreground_tenant(model):
    """With an SLO tight enough to force deferrals, the unweighted victim
    (worst predicted slowdown) must survive when its weight makes every
    other tenant a better deferral candidate."""
    ctrl = AdmissionController(slo=1e-6, num_cores=2, model=model)
    baseline = ctrl.decide(TENANTS)
    first_victim = baseline.deferred[0]
    weighted = ctrl.decide(TENANTS, slo_weights={first_victim: 1e6})
    assert weighted.deferred[0] != first_victim
    # an impossible SLO eventually defers everyone — but the protected
    # tenant goes last, not first
    assert weighted.deferred[-1] == first_victim
    assert weighted.slo_weights == {first_victim: 1e6}


def test_weighted_admission_unit_weights_match_unweighted(model):
    ctrl = AdmissionController(slo=1e-6, num_cores=2, model=model)
    a = ctrl.decide(TENANTS)
    b = ctrl.decide(TENANTS, slo_weights={n: 1.0 for n in TENANTS})
    assert a.deferred == b.deferred
    assert a.admitted == b.admitted


def test_weighted_admission_validation(model):
    ctrl = AdmissionController(slo=1.5, num_cores=2, model=model)
    with pytest.raises(ValueError, match="unknown tenant"):
        ctrl.decide(TENANTS, slo_weights={"ghost": 2.0})
    with pytest.raises(ValueError, match="positive"):
        ctrl.decide(TENANTS, slo_weights={"a": 0.0})


# ---------------------------------------------------------------------------
# contention model rides the interleaved fast path (dispatch wiring)
# ---------------------------------------------------------------------------

def test_contention_model_group_sweeps_ride_interleaved_engine(route_spy):
    """The placement search's candidate-group sweeps are one-shot preempted
    warm-cache runs: auto dispatch must serve them from the interleaved
    engine, with predictions bit-for-bit equal to a scan-forced model.
    (`route_spy` is the shared engine-dispatch recorder, tests/conftest.py.)
    """
    cfg = PlacementConfig(quantum_cycles=2_000, trace_len=3_000,
                          steps_per_program=4_000)
    groups = [("minver", "crc32"), ("nbody", "tarfind")]
    auto_model = ContentionModel(cfg)
    preds = auto_model.predict(groups)
    assert route_spy, "group sweep did not dispatch to the interleaved engine"
    scan_model = ContentionModel(cfg, path="scan")
    scan_preds = scan_model.predict(groups)
    for a, b in zip(preds, scan_preds):
        np.testing.assert_array_equal(a, b)
    # solo references too: identical between the two models
    for b in ("minver", "crc32"):
        assert auto_model.solo_cpi(b) == scan_model.solo_cpi(b)


# ---------------------------------------------------------------------------
# satellite: per-tenant slot taxonomies + bench-name validation
# ---------------------------------------------------------------------------

def test_contention_model_rejects_unknown_bench(model):
    # names resolve through repro.workloads.resolve_trace, whose error
    # names both valid sets (Embench benches + "<arch>:<phase>" workloads)
    with pytest.raises(ValueError, match="unknown tenant name.*nosuch"):
        model.predict([("nosuch", "minver")])
    with pytest.raises(ValueError, match="unknown tenant name"):
        model.solo_cpi("alsonosuch")


def test_per_tenant_scenarios_change_predictions():
    cfg = PlacementConfig(quantum_cycles=2_000, trace_len=3_000,
                          steps_per_program=4_000)
    shared = ContentionModel(cfg)
    mapped = ContentionModel(cfg, scenarios={"minver": isa.SCENARIO_3})
    assert mapped.scenario_of("minver") is isa.SCENARIO_3
    assert mapped.scenario_of("crc32") is mapped.scenario
    g = ("crc32", "minver")
    a = shared.predict([g])[0]
    b = mapped.predict([g])[0]
    assert a.shape == b.shape == (2,)
    # minver under the 1-slot extension taxonomy thrashes differently:
    # the group's prediction must genuinely reflect the per-tenant table
    assert not np.allclose(a, b)
    # solo references split by taxonomy too
    assert shared.solo_cpi("minver") != mapped.solo_cpi("minver")
    assert shared.solo_cpi("crc32") == mapped.solo_cpi("crc32")


def test_per_tenant_scenarios_batch_by_signature():
    cfg = PlacementConfig(quantum_cycles=2_000, trace_len=3_000,
                          steps_per_program=4_000)
    m = ContentionModel(cfg, scenarios={"minver": isa.SCENARIO_3})
    groups = [("crc32", "tarfind"), ("crc32", "nbody"),   # same signature
              ("crc32", "minver")]                        # mapped member
    m.predict(groups)
    again = m.predict(groups)
    calls = m.sim_calls
    m.predict(groups)
    assert m.sim_calls == calls            # fully cached
    assert all(p.shape == (2,) for p in again)


# ---------------------------------------------------------------------------
# satellite: placement edge cases + greedy-vs-swap pin
# ---------------------------------------------------------------------------

def test_place_single_tenant(model):
    pl = place_tenants({"only": "minver"}, 1, model)
    assert pl.cores == (("only",),)
    assert pl.worst_slowdown == pl.mean_slowdown > 0


def test_place_one_tenant_per_core(model):
    pl = place_tenants(TENANTS, len(TENANTS), model)
    assert sorted(n for c in pl.cores for n in c) == sorted(TENANTS)
    assert all(len(c) == 1 for c in pl.cores)
    # solo cores: everyone's "contention" is just quantum/handler overhead,
    # identical across cores for identical benches
    assert pl.worst_slowdown < 1.2


def test_place_more_cores_than_tenants(model):
    pl = place_tenants(dict(list(TENANTS.items())[:2]), 5, model)
    placed = [n for c in pl.cores for n in c]
    assert sorted(placed) == sorted(list(TENANTS)[:2])
    assert all(c for c in pl.cores)        # empty cores dropped
    assert len(pl.cores) <= 2


def test_swap_search_never_worsens_greedy_seed(model):
    """Golden pin on the local search's contract: the swap phase may only
    improve the greedy seed's lexicographic objective."""
    greedy = place_tenants(TENANTS, 2, model, max_rounds=0)
    full = place_tenants(TENANTS, 2, model, max_rounds=8)
    assert full.objective <= greedy.objective
    # and on a roster engineered so greedy's miss-rate order misleads it
    roster = {"a": "minver", "b": "cubic", "c": "qrduino", "d": "ud",
              "e": "edn", "f": "crc32"}
    greedy2 = place_tenants(roster, 3, model, max_rounds=0)
    full2 = place_tenants(roster, 3, model, max_rounds=8)
    assert full2.objective <= greedy2.objective


# ---------------------------------------------------------------------------
# perf gate (CI satellite)
# ---------------------------------------------------------------------------

def test_perf_gate_compare():
    from benchmarks.perf_gate import compare
    base = {"fig6": {"us_per_call": 1_000_000},
            "tiny": {"us_per_call": 10},
            "other": {"us_per_call": 180_000},
            "gone": {"us_per_call": 2_000_000}}
    cur = {"fig6": {"us_per_call": 1_200_000},
           "tiny": {"us_per_call": 900},
           "new": {"us_per_call": 5}}
    rows, fails = compare(base, cur, max_slowdown=1.25, min_us=100_000)
    assert not fails                       # 1.2x within budget; tiny skipped
    assert any("new module" in r for r in rows)
    _, fails = compare(base, {"fig6": {"us_per_call": 1_300_000}},
                       max_slowdown=1.25, min_us=100_000)
    assert fails and "fig6" in fails[0]
    # --modules allowlist restricts gating to re-benchmarked entries, and
    # an allowlist matching NOTHING fails closed (vacuous gate)
    _, fails = compare(base, {"fig6": {"us_per_call": 1_300_000},
                              "other": {"us_per_call": 200_000}},
                       max_slowdown=1.25, min_us=100_000,
                       modules=["other"])
    assert not fails                       # fig6 regression not in scope
    _, fails = compare(base, {"fig6": {"us_per_call": 1_300_000}},
                       max_slowdown=1.25, min_us=100_000, modules=["other"])
    assert fails and "vacuous" in fails[0]
    # same-backend rule: entries recorded on different backends are never
    # compared (a CPU baseline must not gate a TPU run); provenance-free
    # pre-PR-9 entries keep the old behaviour
    rows, fails = compare(
        {"fig6": {"us_per_call": 1_000_000, "backend": "cpu"}},
        {"fig6": {"us_per_call": 9_000_000, "backend": "tpu"}},
        max_slowdown=1.25, min_us=100_000)
    assert not fails
    assert any("backend" in r for r in rows)
    _, fails = compare(
        {"fig6": {"us_per_call": 1_000_000, "backend": "cpu"}},
        {"fig6": {"us_per_call": 9_000_000, "backend": "cpu"}},
        max_slowdown=1.25, min_us=100_000)
    assert fails and "fig6" in fails[0]
