"""The interleaved engine's bitstream level: preempted fleets on a core
whose bitstream cache is smaller than the fleet's merged tag set.

The window pass adds a second LRU distance per window, within the
slot-miss stream (`repro.core.stackdist_interleaved.bs_level`), in the
jnp body and in the window kernel alike.  Every assertion here is exact
integer equality with the per-step `lax.scan`, on every counter.  The
grids thrash on purpose: random opcodes over scenario 2's ten tags,
capacities from 1 to 9 entries, and a quantum that expires inside
nearly every window, so bitstream misses move the switch points.
"""
import functools

import jax
import jax.monitoring
import numpy as np
import pytest
from fleet_asserts import assert_fleet_equal

from repro.core import isa, simulator
from repro.core import stackdist_interleaved as sdi

TRACE_LEN = 300
STEPS = 700
# 40 cycles expire inside every 64-access window; 600 spans windows
QUANTA = [40, 600]
SLOTS = [1, 2, 4, 8]
LATS = [7, 50]
SCHED = simulator.SchedulerConfig(quantum_cycles=40, handler_cycles=30)


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    """The sweeps here run on one device, however many the process has
    (a test that imports `repro.launch.dryrun` first gives it 512 host
    devices, and the fleet axis would be padded to as many); the mesh
    has tests of its own below."""
    monkeypatch.setattr(simulator, "_fleet_mesh", lambda: None)


def _fleets(p: int, b: int = 2, seed: int = 11) -> np.ndarray:
    rng = np.random.default_rng([seed, p])
    return rng.integers(0, isa.NUM_INSTRUCTIONS,
                        (b, p, TRACE_LEN)).astype(np.int32)


def _kw(bs: int, extra: int) -> dict:
    return dict(slot_counts=SLOTS, quanta=QUANTA, bs_cache_entries=bs,
                bs_miss_extra=extra, total_steps=STEPS)


@functools.lru_cache(maxsize=None)
def _scan(bs: int, p: int, extra: int):
    return simulator.sweep_fleet(_fleets(p), LATS, isa.SCENARIO_2, SCHED,
                                 path="scan", **_kw(bs, extra))


@pytest.mark.parametrize("mode", ["jnp", "interpret"])
@pytest.mark.parametrize("extra", [0, 250])
@pytest.mark.parametrize("p", [2, 4])
@pytest.mark.parametrize("bs", [1, 2, 4, 9])
def test_bitstream_level_matches_scan(bs, p, extra, mode):
    """`sweep_fleet(path="interleaved")` equals the scan on every counter
    of every {quantum x fleet x slots x latency} cell, through the jnp
    window body and the window kernel in interpret mode."""
    num_tags = int(np.max(simulator.fleet_tag_table(isa.SCENARIO_2, p))) + 1
    assert sdi.bs_level(bs, num_tags) == bs
    fast = simulator.sweep_fleet(_fleets(p), LATS, isa.SCENARIO_2, SCHED,
                                 path="interleaved", use_kernel=mode,
                                 **_kw(bs, extra))
    scan = _scan(bs, p, extra)
    assert_fleet_equal(fast, scan)
    # the grid does thrash: capacity misses beyond the cold touches
    assert int(np.asarray(scan.bs_misses).sum()) > 2 * len(QUANTA) * len(
        SLOTS) * len(LATS) * num_tags


def test_warm_cache_compiles_no_bitstream_level():
    """A cache that holds the merged tag set takes the warm pass: the
    level is static and left out, and the counters still equal the
    scan's."""
    num_tags = isa.SCENARIO_2.num_tags
    assert sdi.bs_level(None, num_tags) is None
    assert sdi.bs_level(num_tags, num_tags) is None
    assert sdi.bs_level(num_tags - 1, num_tags) == num_tags - 1
    before = simulator.cell_counts()
    fast = simulator.sweep_fleet(_fleets(2), LATS, isa.SCENARIO_2, SCHED,
                                 path="interleaved", **_kw(num_tags, 250))
    after = simulator.cell_counts()
    assert after["cells_bs_level"] == before["cells_bs_level"]
    assert after["cells_interleaved"] > before["cells_interleaved"]
    assert_fleet_equal(fast, _scan(num_tags, 2, 250))


def test_cold_grid_counts_its_bitstream_level_cells(route_spy):
    """Under `path="auto"` a preempted cold grid rides the interleaved
    engine, and every cell it launches, padding included, counts as a
    bitstream-level cell."""
    quanta = [300, 2_000]
    before = simulator.cell_counts()
    auto = simulator.sweep_fleet(_fleets(2, b=3), LATS, isa.SCENARIO_2,
                                 SCHED, slot_counts=[2, 4], quanta=quanta,
                                 bs_cache_entries=4, total_steps=STEPS)
    after = simulator.cell_counts()
    assert len(route_spy) == 1
    launched = len(quanta) * 2 * len(LATS) * 4      # 3 fleets -> bucket 4
    delta = {k: after[k] - before[k] for k in after}
    assert delta["cells_launched"] == delta["cells_interleaved"] == launched
    assert delta["cells_bs_level"] == launched
    assert delta["cells_scan"] == 0
    scan = simulator.sweep_fleet(_fleets(2, b=3), LATS, isa.SCENARIO_2,
                                 SCHED, slot_counts=[2, 4], quanta=quanta,
                                 bs_cache_entries=4, total_steps=STEPS,
                                 path="scan")
    assert_fleet_equal(auto, scan)


def test_simulate_many_one_shot_cold_run(route_spy, resume_spy):
    """A one-shot result-only `simulate_many` with a 3-entry bitstream
    cache rides the interleaved engine and equals the scan; a
    state-returning run of the same core stays on the scan."""
    cfg = simulator.ReconfigConfig(num_slots=4, miss_latency=50,
                                   bs_cache_entries=3, bs_miss_extra=250)
    tr = _fleets(3, b=1)[0]
    sched = simulator.SchedulerConfig(quantum_cycles=400)
    auto = simulator.simulate_many(tr, cfg, isa.SCENARIO_2, sched,
                                   total_steps=2_000)
    assert len(route_spy) == 1
    scan = simulator.simulate_many(tr, cfg, isa.SCENARIO_2, sched,
                                   total_steps=2_000, path="scan")
    assert_fleet_equal(auto, scan)
    assert int(np.asarray(scan.bs_misses).sum()) > isa.SCENARIO_2.num_tags
    res, _ = simulator.simulate_many(tr, cfg, isa.SCENARIO_2, sched,
                                     total_steps=2_000, return_state=True)
    assert not resume_spy
    assert_fleet_equal(res, scan)


def test_contention_model_prices_a_cold_core(route_spy):
    """The contention model of a served core with a 4-entry bitstream
    cache: the one `PlacementConfig` field sizes the served core and its
    model, the candidate groups ride the interleaved engine with the
    bitstream level, and the slowdowns equal the scan-priced model's."""
    from repro.sched import (ContentionModel, OnlineConfig, OnlineReplacer,
                             PlacementConfig)

    pcfg = PlacementConfig(num_slots=4, miss_latency=50,
                           quantum_cycles=2_000, bs_cache_entries=4,
                           trace_len=1_500, steps_per_program=1_500)
    rep = OnlineReplacer(OnlineConfig(placement=pcfg))
    assert rep.cfg.reconfig().bs_cache_entries == 4
    assert rep.model.cfg.bs_cache_entries == 4
    with pytest.raises(ValueError, match="different machine"):
        OnlineReplacer(OnlineConfig(placement=pcfg),
                       model=ContentionModel(PlacementConfig()))
    groups = [("minver", "crc32"), ("cubic", "nbody")]
    fast = rep.model.predict(groups)
    assert len(route_spy) == 1
    scan = ContentionModel(pcfg, path="scan").predict(groups)
    for f, s in zip(fast, scan):
        np.testing.assert_array_equal(f, s)
    warm = ContentionModel(
        PlacementConfig(num_slots=4, miss_latency=50, quantum_cycles=2_000,
                        trace_len=1_500, steps_per_program=1_500)
    ).predict(groups)
    assert any(not np.array_equal(f, w) for f, w in zip(fast, warm))


def test_mesh_sweep_compiles_once():
    """The fleet-axis mesh sweep is one jitted program: a repeat call of
    the same shapes compiles nothing (a mesh of this host's first
    device stands in for the chips)."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("fleet",))
    table = simulator.fleet_tag_table(isa.SCENARIO_2, 2)
    num_tags = int(np.max(table)) + 1
    compiles = []

    def listen(name, *args, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)

    def call():
        return jax.block_until_ready(simulator._mesh_sweep_preempted(
            mesh, jax.numpy.asarray(_fleets(2)), table,
            jax.numpy.asarray([2, 4]), jax.numpy.asarray([50]),
            np.asarray([[40, 40], [600, 600]]), np.arange(2), 30, 250,
            num_tags, STEPS, 64, "jnp", 4))

    first = call()
    n = len(compiles)
    again = call()
    assert len(compiles) == n
    want = simulator.sweep_fleet(_fleets(2), [50], isa.SCENARIO_2, SCHED,
                                 slot_counts=[2, 4], quanta=QUANTA,
                                 bs_cache_entries=4, bs_miss_extra=250,
                                 total_steps=STEPS, path="scan")
    for got in (first, again):
        assert_fleet_equal(simulator.FleetResult(*got), want)


MESH_CHUNKS = r"""
import sys
import numpy as np
from repro.core import isa, simulator
rng = np.random.default_rng(5)
fl = rng.integers(0, isa.NUM_INSTRUCTIONS, (3, 2, 300)).astype(np.int32)
sched = simulator.SchedulerConfig(quantum_cycles=40, handler_cycles=30)
kw = dict(slot_counts=[2, 4], quanta=[40, 600],
          bs_cache_entries=int(sys.argv[1]), total_steps=700)
scan = simulator.sweep_fleet(fl, [50], isa.SCENARIO_2, sched, path="scan",
                             **kw)
simulator._INTERLEAVED_CHUNK_ELEMS = 1      # one fleet per chunk
mesh = simulator.sweep_fleet(fl, [50], isa.SCENARIO_2, sched,
                             path="interleaved", **kw)
assert simulator.fleet_mesh_size() == 4
for a, b in zip(scan, mesh):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("ok")
"""


@pytest.mark.parametrize("bs", [64, 4])
def test_mesh_chunks_keep_their_own_rows(bs):
    """Over a mesh every chunk of the fleet axis is padded to a multiple
    of the device count, not only the tail: each drops its own padded
    rows, so a chunked sweep over four (host) devices equals the scan,
    with a warm bitstream cache and with the bitstream level."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": src,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", MESH_CHUNKS, str(bs)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split() == ["ok"]
