"""Incremental per-epoch re-solve (repro.sched.online): bit-for-bit
parity with the full re-solve across churn and chaos event streams,
snapshot version validation, benchmark-merge provenance, and the
fleet-scale study vehicle.

The incremental mode's contract is that the per-host target cache is
*pure memoisation* of a deterministic solve: serving the same stream in
``resolve_mode="incremental"`` and ``resolve_mode="full"`` must produce
identical placements, move logs, epoch logs, fault logs and per-tenant
metrics — including under a fault-storm epoch that dirties several cores
(across hosts) at once."""
import json

import pytest

from repro.sched import (ContentionModel, FaultEvent, FaultPlan,
                         OnlineConfig, OnlineReplacer, PlacementConfig,
                         TenantEvent, Topology)

PCFG = PlacementConfig(num_slots=4, miss_latency=50, quantum_cycles=2_000,
                       trace_len=2_000, steps_per_program=2_000)
NUM_EPOCHS = 8

# churn: arrivals forcing a regroup, then light mid-serve roster churn
EVENTS = [
    TenantEvent(0, "arrive", "fgA", "minver"),
    TenantEvent(0, "arrive", "fgB", "cubic"),
    TenantEvent(0, "arrive", "m1", "qrduino"),
    TenantEvent(1, "arrive", "m2", "edn"),
    TenantEvent(1, "arrive", "m3", "crc32"),
    TenantEvent(2, "arrive", "m4", "tarfind"),
    TenantEvent(4, "depart", "m3"),
    TenantEvent(4, "arrive", "m5", "tarfind"),
]

# the chaos variant adds a same-epoch storm losing TWO cores at once —
# on the two-host topology they sit in different hosts, so one epoch
# dirties multiple placement domains simultaneously
STORM = FaultPlan(events=(
    FaultEvent(3, "core_loss", 0, repair_epochs=2, degraded_slots=1),
    FaultEvent(3, "core_loss", 2, repair_epochs=2),
    FaultEvent(5, "slot_seu", 1, num_hit=2),
    FaultEvent(5, "bitstream_flush", 3),
), seed=11)

TOPOLOGIES = [
    pytest.param(Topology.flat(4), id="flat4"),
    pytest.param(Topology(num_hosts=2, sockets_per_host=1,
                          cores_per_socket=2), id="hosts2x2"),
]


@pytest.fixture(scope="module")
def model():
    return ContentionModel(PCFG)


def _serve(model, topo, faults, mode):
    cfg = OnlineConfig(topology=topo, epoch_steps=2_000, probe_steps=800,
                       placement=PCFG)
    rep = OnlineReplacer(cfg, model=model, policy="warm", faults=faults,
                         recovery="warm", resolve_mode=mode)
    report = rep.run(EVENTS, NUM_EPOCHS)
    return rep, report


# ---------------------------------------------------------------------------
# incremental == full, bit for bit (the tentpole's correctness criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo", TOPOLOGIES)
@pytest.mark.parametrize("stream", ["online_churn", "chaos_serve"])
def test_incremental_resolve_equals_full_bit_for_bit(model, topo, stream):
    faults = STORM if stream == "chaos_serve" else None
    rep_full, out_full = _serve(model, topo, faults, "full")
    rep_inc, out_inc = _serve(model, topo, faults, "incremental")
    assert out_inc.final_cores == out_full.final_cores
    assert out_inc.moves == out_full.moves
    assert out_inc.epoch_log == out_full.epoch_log
    assert out_inc.fault_log == out_full.fault_log
    assert out_inc.per_tenant == out_full.per_tenant
    assert out_inc.migrations == out_full.migrations
    assert out_inc.evacuations == out_full.evacuations
    # the cache did real work: full solved every domain every epoch,
    # incremental skipped clean domains on quiet epochs
    assert all(r["cached"] == 0 for r in rep_full.resolve_log)
    assert sum(r["cached"] for r in rep_inc.resolve_log) > 0
    assert sum(r["solved"] for r in rep_inc.resolve_log) < \
        sum(r["solved"] for r in rep_full.resolve_log)
    if faults is not None:
        # the storm epoch dirtied every lost core's host at once
        storm = [r for r in rep_inc.resolve_log if r["epoch"] == 3]
        assert storm and storm[0]["solved"] >= len(
            {topo.host_of(0), topo.host_of(2)})


def test_resolve_log_is_telemetry_only(model):
    """`resolve_log` never leaks into the report, the epoch log, or a
    snapshot — restored serves must stay bit-for-bit comparable."""
    rep, out = _serve(model, Topology.flat(4), None, "incremental")
    assert rep.resolve_log, "re-solve ran but logged nothing"
    for row in rep.resolve_log:
        assert set(row) == {"epoch", "mode", "solved", "cached", "seconds"}
    for row in out.epoch_log:
        assert "solved" not in row and "seconds" not in row
    assert "resolve_log" not in rep.snapshot()


# ---------------------------------------------------------------------------
# snapshot versioning (restore must reject what it cannot read)
# ---------------------------------------------------------------------------

def _mini_replacer(model, topo=None):
    cfg = OnlineConfig(topology=topo or Topology.flat(2),
                       epoch_steps=1_000, probe_steps=500, placement=PCFG)
    return OnlineReplacer(cfg, model=model, policy="never")


def test_restore_rejects_unknown_snapshot_version(model):
    rep = _mini_replacer(model)
    rep.run([TenantEvent(0, "arrive", "a", "minver")], 1)
    snap = rep.snapshot()
    assert snap["version"] == 2 and snap["topology"] == (1, 1, 2)
    for bad_version in (99, None, "2"):
        bad = dict(snap, version=bad_version)
        with pytest.raises(ValueError, match=(
                rf"unknown snapshot version {bad_version!r}.*"
                rf"supports versions \(1, 2\)")):
            _mini_replacer(model).restore(bad)


def test_restore_v1_snapshot_loads_onto_flat_topology_only(model):
    rep = _mini_replacer(model)
    rep.run([TenantEvent(0, "arrive", "a", "minver")], 2)
    v1 = rep.snapshot()
    v1["version"] = 1
    del v1["topology"]            # pre-topology writers never had it
    fresh = _mini_replacer(model)
    fresh.restore(v1)             # implicit flat geometry matches
    assert fresh._epoch == 2
    assert fresh.tenants["a"].bench == "minver"
    # every domain restarts dirty: the resumed re-solve is a full one
    assert fresh._dirty == {0} and fresh._domain_target == {}
    # same core count but different geometry must be rejected
    multi = _mini_replacer(model, Topology(num_hosts=2,
                                           sockets_per_host=1,
                                           cores_per_socket=1))
    with pytest.raises(ValueError, match=r"snapshot topology \(1, 1, 2\)"):
        multi.restore(v1)


def test_restore_rejects_mismatched_topology_geometry(model):
    topo = Topology(num_hosts=2, sockets_per_host=1, cores_per_socket=1)
    rep = _mini_replacer(model, topo)
    rep.run([TenantEvent(0, "arrive", "a", "minver")], 1)
    snap = rep.snapshot()
    assert snap["topology"] == (2, 1, 1)
    with pytest.raises(ValueError, match="does not match"):
        _mini_replacer(model).restore(snap)   # flat(2): same cores, no


# ---------------------------------------------------------------------------
# benchmark-merge provenance (BENCH_fleet.json legacy entries)
# ---------------------------------------------------------------------------

PROV = {"backend": "cpu", "device": "TFRT_CPU_0",
        "platform_version": "jax-0.9.0"}


def _entry(us, **extra):
    return {"us_per_call": us, "derived": "d", **extra}


def test_merge_drops_provenance_free_legacy_entries(tmp_path, capsys):
    from benchmarks.run import _record_fleet_json
    path = str(tmp_path / "BENCH_fleet.json")
    with open(path, "w") as f:
        json.dump({"legacy_bench": _entry(1),
                   "good_bench": _entry(2, **PROV)}, f)
    _record_fleet_json({"new_bench": _entry(3, **PROV)}, path)
    with open(path) as f:
        merged = json.load(f)
    # the pre-PR-9 provenance-free entry must not be resurrected
    assert set(merged) == {"good_bench", "new_bench"}
    assert "legacy_bench" in capsys.readouterr().out
    for entry in merged.values():
        assert all(k in entry for k in PROV)


def test_merge_rerecording_a_legacy_name_stamps_it(tmp_path):
    from benchmarks.run import _record_fleet_json
    path = str(tmp_path / "BENCH_fleet.json")
    with open(path, "w") as f:
        json.dump({"legacy_bench": _entry(1)}, f)
    _record_fleet_json({"legacy_bench": _entry(9, **PROV)}, path)
    with open(path) as f:
        merged = json.load(f)
    assert merged["legacy_bench"]["us_per_call"] == 9
    assert merged["legacy_bench"]["backend"] == "cpu"


def test_merge_asserts_every_entry_carries_provenance(tmp_path):
    from benchmarks.run import _record_fleet_json
    path = str(tmp_path / "BENCH_fleet.json")
    with pytest.raises(AssertionError, match="provenance"):
        _record_fleet_json({"bad_bench": _entry(1)}, path)


# ---------------------------------------------------------------------------
# the benchmark vehicle, at test size
# ---------------------------------------------------------------------------

def test_fleet_scale_study_smoke_tiny(monkeypatch):
    """The smoke entry (CI's reduced size) down-scaled further: parity
    asserts and the finding row must hold at any size."""
    from benchmarks import fleet_scale_study as study
    monkeypatch.setenv("REPRO_FLEET_SCALE", "smoke")
    monkeypatch.setattr(study, "SMOKE_SIZES", [
        ("16t_4c", 16, Topology(num_hosts=2, sockets_per_host=1,
                                cores_per_socket=2))])
    rows, out = study.run()
    assert any(r.startswith("# finding fleet-scale smoke") for r in rows)
    rep = out["16t_4c"]["incremental"]
    assert rep.final_cores == out["16t_4c"]["full"].final_cores
