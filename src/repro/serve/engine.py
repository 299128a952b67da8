"""Slot-aware multi-tenant serving engine — the paper's §VI-C at the
serving level.

Mapping (DESIGN.md §2): tenants are processes; each tenant's routing
distribution is its instruction mix; per-device expert slots are the
reconfigurable regions; the round-robin token quantum is FreeRTOS's timer
quantum.  Per decode step the engine:

  1. picks the active tenant (round-robin, `quantum_tokens` per turn);
  2. runs the jitted decode step on that tenant's batch/cache;
  3. feeds the per-layer expert-load vectors into each model-shard's
     block-LRU disambiguator (repro.core.expert_slots) — misses are slot
     fills costed at bytes/bandwidth;
  4. optionally computes a *slot-hit routing* bias from the resident sets
     (the beyond-paper knob): +hit_bias on resident experts' logits.

The report gives per-tenant tokens, hit rates, modelled fill seconds and
modelled step seconds — the quantities behind benchmarks/bench_expert_slots.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import expert_slots as es
from repro.core import isa, simulator
from repro.models import transformer


@dataclass
class Tenant:
    name: str
    tokens: np.ndarray            # (B, T) prompt/stream tokens
    # the tenant's "extension working set": a fixed router bias favouring
    # its preferred experts (the process binary carrying its own
    # instruction extensions, paper §IV)
    router_bias: np.ndarray | None = None
    position: int = 0
    done_tokens: int = 0
    cache: object = None


@dataclass
class EngineConfig:
    quantum_tokens: int = 32      # tokens per tenant turn (OS quantum)
    slots_per_shard: int = 4      # resident experts per model shard
    expert_shards: int = 1        # model-axis shards holding experts
    hit_bias: float = 0.0         # 0 = paper-faithful LRU (no reroute)
    fill_bandwidth: float = 50e9  # bytes/s for slot fills (PCIe-class)
    compute_s_per_token: float = 1e-3  # modelled decode compute time


class SlotServeEngine:
    def __init__(self, cfg, params, engine_cfg: EngineConfig,
                 tenants: list[Tenant], max_len: int = 128, shd=None):
        self.cfg = cfg
        self.params = params
        self.ecfg = engine_cfg
        self.tenants = tenants
        self.shd = shd
        self.max_len = max_len
        mlp_mats = 3 if cfg.mlp in ("swiglu", "gelu_glu") else 2
        expert_bytes = mlp_mats * cfg.d_model * cfg.d_ff * 2
        e_per_shard = max(cfg.num_experts // engine_cfg.expert_shards, 1)
        self.slot_cfg = es.ExpertSlotConfig(
            num_experts=e_per_shard,
            slots_per_device=engine_cfg.slots_per_shard,
            expert_bytes=expert_bytes,
            fill_bandwidth=engine_cfg.fill_bandwidth,
            hit_bias=engine_cfg.hit_bias)
        self.shard_states = [es.init_state(self.slot_cfg)
                             for _ in range(engine_cfg.expert_shards)]
        self.deferred: list[Tenant] = []   # tenants parked by admission
        self.stats = {"fills": 0, "accesses": 0, "fill_seconds": 0.0,
                      "steps": 0, "nonfinite_steps": 0,
                      "per_tenant": {t.name: 0 for t in tenants}}
        for t in tenants:
            t.cache = transformer.init_cache(cfg, t.tokens.shape[0], max_len)
        self._decode = jax.jit(
            lambda params, cache, batch: transformer.decode_step(
                self.cfg, params, batch, cache, shd=self.shd),
            donate_argnums=(1,))

    # ------------------------------------------------------------------
    def _router_bias(self, tenant: Tenant):
        if not self.cfg.is_moe:
            return None
        bias = np.zeros((self.cfg.num_experts,), np.float32)
        if tenant.router_bias is not None:
            bias += tenant.router_bias
        if self.ecfg.hit_bias != 0.0:
            e_per = self.slot_cfg.num_experts
            for s, st in enumerate(self.shard_states):
                res = np.asarray(st.resident)
                bias[s * e_per:(s + 1) * e_per] += res * self.ecfg.hit_bias
        if not bias.any():
            return None
        return jnp.asarray(bias)

    def _account(self, loads):
        """Feed per-layer global expert loads into the shard slot pools.
        Each aux entry is stacked (num_layers_in_segment, E) by the layer
        scan — account layer by layer (each MoE layer's slot pool is the
        same physical pool here; finer per-layer pools are a knob)."""
        e_per = self.slot_cfg.num_experts
        for stacked in loads:
            stacked = np.atleast_2d(np.asarray(stacked))
            for load in stacked:
                for s in range(self.ecfg.expert_shards):
                    shard_load = load[s * e_per:(s + 1) * e_per]
                    ids = np.nonzero(shard_load)[0]
                    if len(ids) == 0:
                        continue
                    st, stats = es.access_block(
                        self.shard_states[s], jnp.asarray(ids, jnp.int32),
                        self.slot_cfg)
                    self.shard_states[s] = st
                    self.stats["fills"] += int(stats.misses)
                    self.stats["accesses"] += int(stats.accessed)
                    self.stats["fill_seconds"] += float(stats.fill_seconds)

    def _decode_once(self, tenant: Tenant):
        b = tenant.tokens.shape[0]
        pos = min(tenant.position, self.max_len - 1)
        batch = {
            "positions": jnp.full((b,), pos, jnp.int32),
        }
        if self.cfg.embed_inputs:
            batch["tokens"] = jnp.asarray(
                tenant.tokens[:, pos % tenant.tokens.shape[1]][:, None])
        else:
            batch["embeds"] = jnp.zeros((b, 1, self.cfg.d_model),
                                        jnp.dtype(self.cfg.dtype))
        rb = self._router_bias(tenant)
        if rb is not None:
            batch["router_bias"] = rb
        logits, cache, aux = self._decode(self.params, tenant.cache, batch)
        if not bool(jnp.isfinite(logits).all()):
            self.stats["nonfinite_steps"] += 1
        tenant.cache = cache
        tenant.position += 1
        tenant.done_tokens += b
        loads = [a["expert_load"] for seg in aux for a in seg
                 if isinstance(a, dict) and "expert_load" in a]
        self._account(loads)

    # ------------------------------------------------------------------
    def fleet_contention(self, tenant_benches: dict[str, str],
                         **kw) -> dict:
        """Slot-contention estimate for this engine's tenant set.

        `tenant_benches` maps tenant name -> instruction-mix profile
        (benchmark name).  Slot count defaults to the engine's
        `slots_per_shard`; everything else forwards to
        `estimate_fleet_contention`.
        """
        benches = [tenant_benches[t.name] for t in self.tenants]
        kw.setdefault("num_slots", self.ecfg.slots_per_shard)
        return estimate_fleet_contention(benches, **kw)

    # ------------------------------------------------------------------
    def plan_coresidency(self, tenant_benches: dict[str, str], *,
                         slo: float = 1.5, num_cores: int = 1,
                         model=None, max_rounds: int = 8,
                         slo_weights: dict[str, float] | None = None):
        """Contention-aware admission plan for this engine's tenant set.

        Instead of taking tenant order as given, ask `repro.sched` which
        tenants should co-reside: tenants are placed onto `num_cores`
        model replicas minimising predicted worst-tenant slot contention,
        and any tenant whose best placement still violates the slowdown
        `slo` is deferred.  `slo_weights` (name -> positive weight)
        protects foreground tenants: deferral picks the worst
        slowdown/weight, so batch tenants absorb contention first.
        Returns the `AdmissionDecision`; use `apply_admission` to restrict
        this engine to one core's residents.
        """
        from repro.sched.admission import AdmissionController
        from repro.sched.placement import ContentionModel, PlacementConfig

        if model is None:
            model = ContentionModel(
                PlacementConfig(num_slots=self.ecfg.slots_per_shard))
        ctrl = AdmissionController(slo=slo, num_cores=num_cores,
                                   model=model, max_rounds=max_rounds)
        return ctrl.decide({t.name: tenant_benches[t.name]
                            for t in self.tenants},
                           slo_weights=slo_weights)

    def serve_online(self, events, *, policy: str = "warm",
                     num_cores: int = 2, model=None, online_cfg=None,
                     num_epochs: int | None = None, apply_core=None,
                     faults=None, recovery: str = "warm"):
        """Serve a churn workload (tenants arriving/leaving mid-serve)
        with online re-placement — the dynamic counterpart of the static
        `plan_coresidency` flow.

        `events` is a sequence of `repro.sched.TenantEvent`s; the epoch
        loop (`repro.sched.online.OnlineReplacer`) carries warm
        slot/bitstream state per core across epochs and, under the default
        "warm" policy, migrates a tenant only when the predicted
        contention saving beats the measured warm-state migration penalty.
        Every epoch is 100% fast path: the per-epoch advances and the
        migration probes resume `FleetState`s through the interleaved
        engine's resumable entry, and the contention model's one-shot
        sweeps ride its windowed entry — no cycle-by-cycle scan anywhere
        in the loop.  Returns the `OnlineReport`.  With `apply_core=<i>` the engine
        afterwards restricts itself to the tenants the final placement
        left on that core (deferred/other-core tenants are parked like
        `apply_admission` does).

        `faults` (a `repro.sched.FaultPlan`) injects a deterministic
        fault storm into the serve; `recovery` picks the reaction
        (`repro.sched.RECOVERY_POLICIES`: "warm" evacuation /
        "cold_restart" / "none") — the report's `fault_log` and
        `worst_lifetime_slowdown` quantify the outcome.  Faulted epochs
        may route segments through the cycle-by-cycle scan: SEU- or
        flush-mutated caches are not interleaved-seedable until they
        re-warm, and degraded (masked) cores always scan.
        """
        from repro.sched.online import OnlineConfig, OnlineReplacer
        from repro.sched.placement import PlacementConfig

        if online_cfg is None:
            online_cfg = OnlineConfig(
                num_cores=num_cores,
                placement=PlacementConfig(
                    num_slots=self.ecfg.slots_per_shard))
        rep = OnlineReplacer(online_cfg, model=model, policy=policy,
                             faults=faults,
                             recovery=recovery).run(events, num_epochs)
        if apply_core is not None:
            if not 0 <= apply_core < len(rep.final_cores):
                raise ValueError(
                    f"core index {apply_core} out of range for "
                    f"{len(rep.final_cores)} cores")
            keep_names = set(rep.final_cores[apply_core])
            keep = [t for t in self.tenants if t.name in keep_names]
            self.deferred += [t for t in self.tenants
                              if t.name not in keep_names]
            self.tenants = keep
        return rep

    def apply_admission(self, decision, core: int = 0) -> list[Tenant]:
        """Keep only `core`'s admitted co-residents; park everything else.

        Deferred (and other-core) tenants move to `self.deferred` so the
        caller can serve them in a later round or on another replica.
        Returns the retained tenant list (in placement order).
        """
        keep_names: tuple[str, ...] = ()
        if decision.placement is not None:
            if not 0 <= core < len(decision.placement.cores):
                raise ValueError(
                    f"core index {core} out of range for a placement with "
                    f"{len(decision.placement.cores)} cores")
            keep_names = decision.placement.cores[core]
        by_name = {t.name: t for t in self.tenants}
        keep = [by_name[n] for n in keep_names if n in by_name]
        kept = {t.name for t in keep}
        self.deferred += [t for t in self.tenants if t.name not in kept]
        self.tenants = keep
        return keep

    # ------------------------------------------------------------------
    def run(self, total_steps: int) -> dict:
        if not self.tenants:
            raise ValueError(
                "engine has no resident tenants (all deferred by "
                "admission?) — nothing to serve")
        ti = 0
        quantum_left = self.ecfg.quantum_tokens
        for _ in range(total_steps):
            tenant = self.tenants[ti]
            self._decode_once(tenant)
            self.stats["steps"] += 1
            self.stats["per_tenant"][tenant.name] += 1
            quantum_left -= tenant.tokens.shape[0]
            if quantum_left <= 0:
                ti = (ti + 1) % len(self.tenants)
                quantum_left = self.ecfg.quantum_tokens
        s = self.stats
        hit_rate = (1.0 - s["fills"] / s["accesses"]
                    if s["accesses"] else 1.0)
        compute_s = s["steps"] * self.ecfg.compute_s_per_token
        return {
            **s,
            "hit_rate": hit_rate,
            "modelled_compute_s": compute_s,
            "overhead_frac": s["fill_seconds"] /
            max(compute_s + s["fill_seconds"], 1e-12),
        }


def estimate_fleet_contention(benches: list[str], *, num_slots: int = 4,
                              miss_latency: int = 50,
                              quantum_cycles=20_000,
                              handler_cycles: int = 150,
                              priorities=None,
                              scenarios=None,
                              trace_len: int = 60_000,
                              total_steps: int = 160_000) -> dict:
    """Multi-tenant slot-contention estimate from the core fleet simulator.

    Maps each tenant to an instruction-mix profile (an Embench name from
    `repro.core.traces` or a model-zoo "<arch>:<phase>" workload from
    `repro.workloads`) and runs the SAME `simulate_many` machinery that
    produces the paper's Fig. 7 numbers: one reconfigurable core, round-robin
    quantum, slot state persisting across switches.  Per tenant it reports
    the fleet CPI, the solo (unpreempted) CPI, and their ratio — the
    contention slowdown a tenant should expect from co-residency — plus
    fleet-level switch/miss counters.

    `scenarios` may be one `SlotScenario` or a per-tenant list (tenants can
    disagree about which opcodes are slotted).  `quantum_cycles` may be a
    per-tenant vector and `priorities` a per-tenant weight tuple — the
    heterogeneous-quantum / weighted-round-robin axes of `SchedulerConfig`.
    """
    if scenarios is None:
        scenarios = isa.SCENARIO_2
    cfg = simulator.ReconfigConfig(num_slots=num_slots,
                                   miss_latency=miss_latency)
    sched = simulator.SchedulerConfig(quantum_cycles=quantum_cycles,
                                      handler_cycles=handler_cycles,
                                      priorities=priorities)
    # resolve_trace: Embench names pass through to core_traces bit-for-bit;
    # "<arch>:<phase>" names lower the model zoo (lazy import keeps the
    # serve layer importable without the model/configs stack)
    from repro import workloads

    tr = np.stack([workloads.resolve_trace(n, trace_len) for n in benches])
    # one-shot preempted fleet with a warm bitstream cache: the dispatcher
    # serves this from the interleave-aware stack-distance engine
    # (scheduler-window replay, bit-for-bit equal to the scan)
    fleet = simulator.simulate_many(tr, cfg, scenarios, sched, total_steps)

    # solo reference: each tenant alone on the core, never preempted — both
    # branches route through `sweep_fleet`, whose dispatcher collapses these
    # warm-cache unpreempted runs into stack-distance passes (no scan)
    solo_sched = simulator.SchedulerConfig.no_preempt(handler_cycles)
    if isinstance(scenarios, (list, tuple)):
        # per-tenant taxonomies: one P=1 sweep cell per (bench, scenario)
        solo_cpis = [
            float(np.asarray(simulator.sweep_fleet(
                tr[i:i + 1, None, :], [miss_latency], s, solo_sched,
                slot_counts=[num_slots],
                total_steps=trace_len).cpi)[0, 0, 0, 0])
            for i, s in enumerate(scenarios)]
    else:
        # shared taxonomy: all P solo runs as one batched sweep cell
        solo = simulator.sweep_fleet(
            tr[:, None, :], [miss_latency], scenarios, solo_sched,
            slot_counts=[num_slots], total_steps=trace_len)
        solo_cpis = [float(c) for c in np.asarray(solo.cpi)[:, 0, 0, 0]]
    per_tenant = {}
    fleet_cpi = np.asarray(fleet.cpi)
    fleet_instrs = np.asarray(fleet.instructions)
    for i, name in enumerate(benches):
        solo_cpi = solo_cpis[i]
        # a tenant the round-robin never reached (total_steps exhausted
        # inside earlier quanta) has no CPI — report NaN, not the
        # "zero slowdown" that a 0/instructions division would fake
        scheduled = int(fleet_instrs[i]) > 0
        cpi_i = float(fleet_cpi[i]) if scheduled else float("nan")
        per_tenant[f"{i}:{name}"] = {
            "fleet_cpi": cpi_i,
            "solo_cpi": solo_cpi,
            "contention_slowdown": cpi_i / solo_cpi,
            "slot_misses": int(np.asarray(fleet.slot_misses)[i]),
            "scheduled": scheduled,
        }
    return {
        "tenants": per_tenant,
        "switches": int(fleet.switches),
        "total_slot_misses": int(np.asarray(fleet.slot_misses).sum()),
        "num_slots": num_slots,
        "miss_latency": miss_latency,
        "quantum_cycles": quantum_cycles,
    }


def model_batcher(cfg, params, batch_size: int, max_len: int, shd=None):
    """A ContinuousBatcher wired to the real model: per-row prompt prefill
    writes the (1, T) prefill cache into the shared fixed-width decode
    cache; the decode callback is the jitted single-token step."""
    import jax.numpy as jnp

    from repro.serve.batching import ContinuousBatcher

    cache = transformer.init_cache(cfg, batch_size, max_len)
    decode_fn = jax.jit(
        lambda p, c, b: transformer.decode_step(cfg, p, b, c, shd=shd))

    def prefill_row(row, tokens):
        nonlocal cache
        t0 = len(tokens)
        _, row_cache, _ = transformer.prefill(
            cfg, params, {"tokens": jnp.asarray(tokens)[None, :]}, shd=shd)

        def write(dst, src):
            # dst: (n, B, S, ...) shared cache; src: (n, 1, t0, ...) row
            if dst.ndim >= 3 and src.shape[2] == t0 and \
                    dst.shape[2] >= t0 and dst.shape[1] == batch_size:
                return dst.at[:, row, :t0].set(src[:, 0].astype(dst.dtype))
            if dst.ndim >= 2 and dst.shape[1] == batch_size:
                return dst.at[:, row].set(src[:, 0].astype(dst.dtype))
            return dst

        cache = jax.tree_util.tree_map(write, cache, row_cache)

    def decode(tokens, positions):
        nonlocal cache
        logits, cache, _ = decode_fn(
            params, cache,
            {"tokens": jnp.asarray(tokens),
             "positions": jnp.asarray(positions)})
        return np.asarray(jnp.argmax(logits[:, 0], axis=-1))

    return ContinuousBatcher(batch_size, max_len, prefill_row=prefill_row,
                             decode=decode)
