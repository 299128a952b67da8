"""Topology layer (repro.sched.topology): geometry and distance tiers,
tiered migration pricing in the OnlineReplacer, per-host static placement
(`place_fleet`), the canonical prediction-cache key, and mesh-sharded
candidate-group sweeps.

The load-bearing equivalences pinned here:

  * `Topology.flat(C)` reproduces the pre-topology flat pool exactly —
    every distance intra-socket, every reload surcharge zero, so
    `migration_penalty(n, dst) == migration_penalty(n)` and
    `place_fleet == place_tenants`;
  * `(group, width)` prediction-cache keys are canonical: a permuted
    group at a degraded width hits the sorted twin's entry, and degraded
    entries never alias (nor get served from) the full-width one;
  * candidate sweeps shard across a forced multi-device host mesh with
    predictions bit-identical to the single-device scan path.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import simulator
from repro.sched import (ContentionModel, OnlineConfig, OnlineReplacer,
                         PlacementConfig, TenantEvent, Topology,
                         place_fleet, place_tenants)
from repro.sched.topology import DISTANCES

PCFG = PlacementConfig(num_slots=4, miss_latency=50, quantum_cycles=2_000,
                       trace_len=2_000, steps_per_program=2_000)


@pytest.fixture(scope="module")
def model():
    return ContentionModel(PCFG)


# ---------------------------------------------------------------------------
# pure geometry
# ---------------------------------------------------------------------------

def test_topology_validation():
    with pytest.raises(ValueError, match="num_hosts"):
        Topology(num_hosts=0)
    with pytest.raises(ValueError, match="cores_per_socket"):
        Topology(cores_per_socket=0)
    with pytest.raises(ValueError, match="multipliers"):
        Topology(cross_socket_reload=-1.0)
    with pytest.raises(ValueError, match="cross_host_reload"):
        Topology(cross_socket_reload=8.0, cross_host_reload=2.0)


def test_topology_geometry_and_distances():
    t = Topology(num_hosts=2, sockets_per_host=2, cores_per_socket=2)
    assert t.num_cores == 8
    assert t.cores_per_host == 4 and t.num_sockets == 4
    assert t.geometry() == (2, 2, 2)
    assert list(t.cores_of_host(1)) == [4, 5, 6, 7]
    assert t.host_of(3) == 0 and t.host_of(4) == 1
    assert t.socket_of(1) == 0 and t.socket_of(2) == 1
    assert t.distance(5, 5) == "intra_core"
    assert t.distance(4, 5) == "intra_socket"
    assert t.distance(4, 6) == "cross_socket"
    assert t.distance(3, 4) == "cross_host"
    assert t.reload_multiplier("intra_core") == 0.0
    assert t.reload_multiplier("intra_socket") == 0.0
    assert t.reload_multiplier("cross_socket") == 4.0
    assert t.reload_multiplier("cross_host") == 16.0
    assert all(d in DISTANCES for d in
               (t.distance(a, b) for a in range(8) for b in range(8)))
    with pytest.raises(ValueError, match="unknown distance"):
        t.reload_multiplier("adjacent")
    with pytest.raises(ValueError, match="core 8"):
        t.distance(0, 8)
    with pytest.raises(ValueError, match="host 2"):
        t.cores_of_host(2)


def test_flat_topology_is_the_pre_topology_pool():
    t = Topology.flat(5)
    assert t.geometry() == (1, 1, 5) and t.num_cores == 5
    for a in range(5):
        for b in range(5):
            d = t.distance(a, b)
            assert d == ("intra_core" if a == b else "intra_socket")
            assert t.reload_multiplier(d) == 0.0


def test_online_config_topology_wiring():
    # default: a flat pool of num_cores
    assert OnlineConfig(num_cores=3).topology.geometry() == (1, 1, 3)
    # explicit topology *defines* num_cores
    cfg = OnlineConfig(num_cores=1, topology=Topology(
        num_hosts=2, sockets_per_host=1, cores_per_socket=3))
    assert cfg.num_cores == 6
    with pytest.raises(TypeError, match="Topology"):
        OnlineConfig(topology=(2, 1, 3))


# ---------------------------------------------------------------------------
# canonical (group, width) prediction-cache keys — the PR 7 keying bugfix
# ---------------------------------------------------------------------------

def test_permuted_degraded_group_hits_the_same_cache_entry(model):
    before = model.groups_simulated
    a = model.predict([("nbody", "tarfind")], num_slots=2)[0]
    assert model.groups_simulated == before + 1
    # the permuted twin at the same degraded width must be a cache hit
    b = model.predict([("tarfind", "nbody")], num_slots=2)[0]
    assert model.groups_simulated == before + 1
    np.testing.assert_array_equal(a, b)
    # and the cache holds exactly one canonical entry for it
    assert model._cache_key(("tarfind", "nbody"), 2) in model._groups
    assert model._cache_key(("nbody", "tarfind"), 2) == \
        model._cache_key(("tarfind", "nbody"), 2)


def test_degraded_width_never_aliases_full_width(model):
    g = ("cubic", "minver")
    before = model.groups_simulated
    full = model.predict([g])[0]
    assert model.groups_simulated == before + 1
    # pricing the same group at a degraded width must simulate anew —
    # serving it from the full-width entry would hide the degradation
    deg = model.predict([g], num_slots=1)[0]
    assert model.groups_simulated == before + 2
    assert model._cache_key(g, 1) in model._groups
    assert model._cache_key(g, PCFG.num_slots) in model._groups
    # the 1-slot core thrashes harder than the full-width one
    assert float(np.max(deg)) > float(np.max(full))


# ---------------------------------------------------------------------------
# topology-aware static placement
# ---------------------------------------------------------------------------

ROSTER = {"a": "minver", "b": "cubic", "c": "qrduino",
          "d": "edn", "e": "crc32"}


def test_place_fleet_flat_equals_place_tenants(model):
    flat = place_fleet(ROSTER, Topology.flat(3), model)
    plain = place_tenants(ROSTER, 3, model)
    assert flat.cores == plain.cores
    assert flat.tenant_slowdown == plain.tenant_slowdown
    assert flat.worst_slowdown == plain.worst_slowdown


def test_place_fleet_partitions_tenants_across_hosts(model):
    topo = Topology(num_hosts=2, sockets_per_host=1, cores_per_socket=2)
    pl = place_fleet(ROSTER, topo, model)
    placed = [n for core in pl.cores for n in core]
    assert sorted(placed) == sorted(ROSTER)       # everyone exactly once
    assert len(pl.cores) <= topo.num_cores
    with pytest.raises(ValueError, match="at least one tenant"):
        place_fleet({}, topo, model)


# ---------------------------------------------------------------------------
# tiered migration pricing in the online replacer
# ---------------------------------------------------------------------------

def _warmed_replacer(model, topo):
    cfg = OnlineConfig(topology=topo, epoch_steps=2_000, probe_steps=800,
                       placement=PCFG)
    rep = OnlineReplacer(cfg, model=model, policy="never")
    rep.run([TenantEvent(0, "arrive", "a", "minver")], 2)
    assert rep.tenants["a"].core == 0     # deterministic arrival tie-break
    return rep


def test_flat_migration_penalty_is_the_bare_probe(model):
    rep = _warmed_replacer(model, Topology.flat(3))
    bare = rep.migration_penalty("a")
    for dst in range(3):
        assert rep.reload_cycles("a", dst) == 0.0
        assert rep.migration_penalty("a", dst) == bare


def test_cross_socket_and_cross_host_moves_pay_the_reload_tiers(model):
    # 4 cores: 0,1 = host 0 (sockets 0,1); 2,3 = host 1 (sockets 2,3)
    topo = Topology(num_hosts=2, sockets_per_host=2, cores_per_socket=1)
    rep = _warmed_replacer(model, topo)
    bare = rep.migration_penalty("a")
    # the serve left warm bitstreams on core 0, so the surcharge is real
    cross_socket = rep.reload_cycles("a", 1)
    cross_host = rep.reload_cycles("a", 2)
    assert cross_socket > 0.0
    assert cross_host == pytest.approx(
        cross_socket * topo.cross_host_reload / topo.cross_socket_reload)
    # the surcharge is resident_bitstreams x bs_miss_extra x multiplier
    assert cross_socket % (rep.cfg.placement.bs_miss_extra
                           * topo.cross_socket_reload) == 0.0
    assert rep.reload_cycles("a", 0) == 0.0            # intra_core
    assert rep.migration_penalty("a", 1) == bare + cross_socket
    assert rep.migration_penalty("a", 2) == bare + cross_host
    # a stranded tenant has no warm state to re-load
    rep.tenants["a"].core = -1
    assert rep.reload_cycles("a", 3) == 0.0


# ---------------------------------------------------------------------------
# mesh-sharded candidate sweeps (forced 4-device host mesh, subprocess)
# ---------------------------------------------------------------------------

def test_fleet_mesh_size_is_a_positive_int():
    n = simulator.fleet_mesh_size()
    assert isinstance(n, int) and n >= 1


def test_mesh_sharded_candidate_sweep_matches_scan():
    """ContentionModel predictions on a forced 4-device mesh (batches pad
    to a multiple of the device count) must equal the single-path scan
    bit-for-bit — 3 candidate groups exercise the non-divisible
    round-up."""
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    script = textwrap.dedent("""
        import numpy as np
        import jax
        from repro.core import simulator
        from repro.sched import ContentionModel, PlacementConfig
        assert jax.device_count() == 4, jax.devices()
        assert simulator.fleet_mesh_size() == 4
        pcfg = PlacementConfig(num_slots=4, miss_latency=50,
                               quantum_cycles=500, trace_len=1_000,
                               steps_per_program=1_000)
        groups = [("minver", "cubic"), ("crc32", "edn"),
                  ("qrduino", "nbody")]
        fast = ContentionModel(pcfg).predict(groups)
        scan = ContentionModel(pcfg, path="scan").predict(groups)
        for g, a, b in zip(groups, fast, scan):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=str(g))
        print("MESH-PREDICT-OK")
    """)
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0 and "MESH-PREDICT-OK" in r.stdout, \
        r.stdout + "\n" + r.stderr
