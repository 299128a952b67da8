"""Slot-aware multi-tenant serving engine tests (paper §VI-C phenomenology
at the serving level)."""
import jax
import numpy as np
import pytest

from repro.configs import base as cb
from repro.models import transformer
from repro.serve.engine import EngineConfig, SlotServeEngine, Tenant

cb.load_all()


@pytest.fixture(scope="module")
def moe_setup():
    cfg = cb.get_config("arctic-480b").smoke()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def tenants_for(cfg, n=2):
    rng = np.random.default_rng(1)
    out = []
    e = cfg.num_experts
    per = e // n
    for i in range(n):
        bias = np.full((e,), -6.0, np.float32)
        bias[i * per:(i + 1) * per] = 6.0
        out.append(Tenant(
            name=f"t{i}",
            tokens=rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32),
            router_bias=bias))
    return out


def run_engine(cfg, params, steps=40, **ecfg_kw):
    base = dict(quantum_tokens=8, slots_per_shard=2, expert_shards=1)
    base.update(ecfg_kw)
    eng = SlotServeEngine(cfg, params, EngineConfig(**base),
                          tenants_for(cfg), max_len=steps + 4)
    return eng.run(steps)


def test_round_robin_shares_steps(moe_setup):
    cfg, params = moe_setup
    rep = run_engine(cfg, params, steps=40)
    per = rep["per_tenant"]
    assert abs(per["t0"] - per["t1"]) <= 8


def test_nonfinite_logits_are_counted(moe_setup):
    cfg, params = moe_setup
    assert run_engine(cfg, params, steps=4)["nonfinite_steps"] == 0
    nan_params = jax.tree_util.tree_map(lambda x: x * np.nan, params)
    assert run_engine(cfg, nan_params, steps=4)["nonfinite_steps"] == 4


def test_more_slots_fewer_fills(moe_setup):
    cfg, params = moe_setup
    r2 = run_engine(cfg, params, slots_per_shard=2)
    r8 = run_engine(cfg, params, slots_per_shard=8)
    assert r8["fills"] < r2["fills"]
    assert r8["hit_rate"] >= r2["hit_rate"]


def test_longer_quantum_amortises_fills(moe_setup):
    """The paper's 1K->20K scheduler-quantum effect."""
    cfg, params = moe_setup
    short = run_engine(cfg, params, quantum_tokens=4)
    long = run_engine(cfg, params, quantum_tokens=32)
    assert long["fills"] <= short["fills"]


def test_slot_hit_routing_reduces_fills(moe_setup):
    """Beyond-paper: biasing routing toward resident experts cuts fill
    traffic."""
    cfg, params = moe_setup
    plain = run_engine(cfg, params, hit_bias=0.0)
    biased = run_engine(cfg, params, hit_bias=4.0)
    assert biased["fills"] < plain["fills"]


def test_serve_online_churn_flow(moe_setup):
    """The dynamic counterpart of plan_coresidency: an event stream served
    with online re-placement, then the engine restricted to one core's
    final residents."""
    from repro.sched import OnlineConfig, PlacementConfig, TenantEvent

    cfg, params = moe_setup
    tenants = tenants_for(cfg, n=3)
    tenants[2].name = "t2"
    eng = SlotServeEngine(cfg, params,
                          EngineConfig(quantum_tokens=8, slots_per_shard=4),
                          tenants, max_len=20)
    ocfg = OnlineConfig(
        num_cores=2, epoch_steps=2_000, probe_steps=800,
        placement=PlacementConfig(num_slots=4, quantum_cycles=2_000,
                                  trace_len=2_000, steps_per_program=2_000))
    events = [TenantEvent(0, "arrive", "t0", "minver"),
              TenantEvent(0, "arrive", "t1", "crc32"),
              TenantEvent(1, "arrive", "t2", "nbody")]
    rep = eng.serve_online(events, online_cfg=ocfg, num_epochs=3,
                           apply_core=0)
    assert rep.policy == "warm"
    assert set(rep.per_tenant) == {"t0", "t1", "t2"}
    # the engine now serves exactly core 0's final residents
    kept = {t.name for t in eng.tenants}
    assert kept == set(rep.final_cores[0])
    assert {t.name for t in eng.deferred} == {"t0", "t1", "t2"} - kept
    if eng.tenants:
        assert eng.run(4)["steps"] == 4


def test_dense_arch_engine_runs(moe_setup):
    """Dense archs have no expert slots; the engine still serves."""
    cfg = cb.get_config("granite-3-2b").smoke()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tenants = [Tenant(name="t0",
                      tokens=rng.integers(0, cfg.vocab, (1, 8)).astype(
                          np.int32))]
    eng = SlotServeEngine(cfg, params, EngineConfig(), tenants, max_len=16)
    rep = eng.run(8)
    assert rep["steps"] == 8
    assert rep["hit_rate"] == 1.0  # nothing slotted
