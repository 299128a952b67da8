"""OpCount accounting: model-zoo configs -> per-op instruction-mix tables.

The repo's anchors all consume dynamic instruction traces over the
`repro.core.isa` RV32IMF alphabet.  This module produces the *mix* those
traces should realise for the models the repo actually ships: each
`repro.configs` architecture is lowered (smoke reduction, CPU-compilable)
through its prefill or decode step, the optimized HLO is walked with the
scan-corrected accounting in `repro.analysis.hlo`, and every executed HLO
op is charged to an isa group:

  * float elementwise ops map directly (add->fadd, multiply->fmul,
    divide->fdiv, sqrt/rsqrt->fsqrt, compare/select/min/max->fcmp,
    convert/floor/ceil/round->fcvt);
  * `dot`/`convolution` contractions are fused multiply-adds: FLOPs / 2
    `fma` ops — the dominant term of any prefill;
  * transcendentals (exp, log, tanh, logistic, sine, ...) have no RV32IMF
    instruction; each element expands into a documented soft sequence of
    4 `fma` (Horner polynomial) + 1 `fdiv` (range reduction / reciprocal);
  * integer multiply / divide / remainder map to the M groups (router
    top-k math, position arithmetic, address math the compiler emits);
  * every other integer/pred op, plus the HBM-traffic proxy converted at
    one RV32 word (4 bytes) per load/store, lands in `base` — which is
    what makes decode (memory-bound, low arithmetic intensity) lower as a
    base-heavy, slot-light tenant while prefill lowers F-hot.

The `OpCount` container follows the `FlopCount` accounting idiom
(per-category counts with `+` and scalar `*`, dict round-trip for
serialization); `repro.workloads` turns tables into `WorkloadSpec`s and
`benchmarks/model_serve_study.py` serializes the zoo-wide table to
``experiments/bench/workload_mix.csv`` so mixes are diffable across PRs.

Accounting runs on *smoke* reductions of each config: mixes are relative
fractions and the smoke configs preserve the family structure that shapes
them (MoE routing, rwkv6/RG-LRU recurrences, mrope, layer scans), while
staying compilable on the CPU backend in ~1s per phase.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np

from repro.core import isa

# ---------------------------------------------------------------------------
# HLO op -> isa-group mapping
# ---------------------------------------------------------------------------

# float-class elementwise ops with a direct RV32F counterpart group
F_OP_GROUP = {
    "add": "fadd", "subtract": "fadd",
    "reduce": "fadd", "reduce-window": "fadd",   # charged per input element
    "multiply": "fmul",
    "divide": "fdiv", "remainder": "fdiv",
    "sqrt": "fsqrt", "rsqrt": "fsqrt", "cbrt": "fsqrt",
    "compare": "fcmp", "select": "fcmp", "maximum": "fcmp",
    "minimum": "fcmp", "clamp": "fcmp", "abs": "fcmp", "negate": "fcmp",
    "sign": "fcmp", "is-finite": "fcmp",
    "convert": "fcvt", "floor": "fcvt", "ceil": "fcvt",
    "round-nearest-afz": "fcvt", "round-nearest-even": "fcvt",
}

# no RV32IMF instruction: expanded per element into a soft sequence
TRANSCENDENTALS = {
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "logistic", "tanh", "sine", "cosine", "tan", "atan2", "power",
    "erf", "erf-inv",
}
TRANSCENDENTAL_EXPANSION = {"fma": 4.0, "fdiv": 1.0}

# integer-class ops with an RV32M counterpart group
I_OP_GROUP = {"multiply": "mul", "divide": "div", "remainder": "rem"}

# HBM-traffic proxy -> base load/store ops: one RV32 word per 4 bytes
BYTES_PER_BASE_OP = 4.0


@dataclass
class OpCount:
    """Executed-op counts over the isa group alphabet (FlopCount idiom).

    `counts` maps isa group name -> dynamic op count; `flops`, `bytes` and
    `transcendental_elems` keep the raw accounting the mapping consumed,
    so serialized tables stay auditable against the HLO walk.
    """

    counts: dict = field(default_factory=dict)
    flops: float = 0.0
    bytes: float = 0.0
    transcendental_elems: float = 0.0

    def __add__(self, other: "OpCount") -> "OpCount":
        if not isinstance(other, OpCount):
            return NotImplemented
        merged = dict(self.counts)
        for g, v in other.counts.items():
            merged[g] = merged.get(g, 0.0) + v
        return OpCount(merged, self.flops + other.flops,
                       self.bytes + other.bytes,
                       self.transcendental_elems
                       + other.transcendental_elems)

    def __mul__(self, k: float) -> "OpCount":
        return OpCount({g: v * k for g, v in self.counts.items()},
                       self.flops * k, self.bytes * k,
                       self.transcendental_elems * k)

    __rmul__ = __mul__

    def total(self) -> float:
        return float(sum(self.counts.values()))

    def frac(self) -> np.ndarray:
        """(NUM_GROUPS,) stationary fractions — `repro.core.traces.Mix`
        layout, consumable by `paint_trace` / `analytic_cpi`."""
        v = np.zeros(isa.NUM_GROUPS)
        for g, c in self.counts.items():
            v[isa.GROUP_ID[g]] = c
        s = v.sum()
        if s <= 0:
            raise ValueError("OpCount has no executed ops to normalise")
        return v / s

    def to_dict(self) -> dict:
        return {"counts": dict(self.counts), "flops": self.flops,
                "bytes": self.bytes,
                "transcendental_elems": self.transcendental_elems}

    @classmethod
    def from_dict(cls, d: dict) -> "OpCount":
        return cls(dict(d["counts"]), float(d["flops"]), float(d["bytes"]),
                   float(d.get("transcendental_elems", 0.0)))


def opcount_from_hlo(hlo_text: str) -> OpCount:
    """Charge a compiled module's executed ops to isa groups.

    Consumes `hlo.op_histogram` (per-opcode executed elements, scan trip
    counts applied) and `hlo.analyze_module` (the HBM-traffic proxy that
    becomes the base-op load/store count).
    """
    from repro.analysis import hlo

    hist = hlo.op_histogram(hlo_text)
    walk = hlo.analyze_module(hlo_text)
    counts: dict[str, float] = {g: 0.0 for g in isa.GROUP_NAMES}
    trans = 0.0
    for key, n in hist.items():
        op, cls = key.rsplit(":", 1)
        if op == "dot":
            counts["fma"] += n / 2.0       # n carries FLOPs for dot ops
        elif cls == "f" and op in TRANSCENDENTALS:
            trans += n
            for g, k in TRANSCENDENTAL_EXPANSION.items():
                counts[g] += n * k
        elif cls == "f" and op in F_OP_GROUP:
            counts[F_OP_GROUP[op]] += n
        elif cls == "i" and op in I_OP_GROUP:
            counts[I_OP_GROUP[op]] += n
        else:
            counts["base"] += n
    counts["base"] += float(walk["bytes"]) / BYTES_PER_BASE_OP
    counts = {g: v for g, v in counts.items() if v > 0}
    return OpCount(counts, flops=float(walk["flops"]),
                   bytes=float(walk["bytes"]),
                   transcendental_elems=trans)


# ---------------------------------------------------------------------------
# model-zoo lowering: config -> compiled phase step -> OpCount
# ---------------------------------------------------------------------------

PHASES = ("prefill", "decode")

# small enough to compile in ~1s on CPU, large enough that per-token terms
# dominate per-call constants
MIX_BATCH = 2
MIX_SEQ_LEN = 64

_CACHE: dict[tuple[str, str], OpCount] = {}


def _abstract_batch(cfg, phase: str) -> dict:
    import jax
    import jax.numpy as jnp

    i32 = jnp.dtype(jnp.int32)
    act = jnp.dtype(cfg.dtype)
    b, t = MIX_BATCH, MIX_SEQ_LEN
    if phase == "prefill":
        batch: dict = {}
        if cfg.embed_inputs:
            batch["tokens"] = jax.ShapeDtypeStruct((b, t), i32)
        else:
            batch["embeds"] = jax.ShapeDtypeStruct((b, t, cfg.d_model), act)
        if cfg.pos == "mrope":
            batch["positions"] = jax.ShapeDtypeStruct((b, t, 3), i32)
        return batch
    # decode: one new token against a prefilled cache; positions are (B,)
    # for every pos scheme (mrope broadcasts t=h=w in text mode)
    batch = {"positions": jax.ShapeDtypeStruct((b,), i32)}
    if cfg.embed_inputs:
        batch["tokens"] = jax.ShapeDtypeStruct((b, 1), i32)
    else:
        batch["embeds"] = jax.ShapeDtypeStruct((b, 1, cfg.d_model), act)
    return batch


def _compiled_phase(arch: str, phase: str):
    """Lower + compile one (smoke config, phase) cell for the CPU device,
    whatever the default backend; returns Compiled.  One fixed target
    keeps the mix, and every trace lowered from it, the same bits on a
    chip host as in the CPU tests."""
    import jax

    from repro.configs import base as cb
    from repro.models import transformer

    cb.load_all()
    cfg = cb.get_config(arch).smoke()
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cpu = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    on_cpu = functools.partial(jax.jit, in_shardings=cpu, out_shardings=cpu)
    pre = _abstract_batch(cfg, "prefill")
    if phase == "prefill":
        fn = lambda p, bt: transformer.prefill(cfg, p, bt)[0]  # noqa: E731
        return on_cpu(fn).lower(params, pre).compile()
    _, cache, _ = jax.eval_shape(
        lambda p, bt: transformer.prefill(cfg, p, bt), params, pre)
    dec = _abstract_batch(cfg, "decode")
    fn = lambda p, c, bt: transformer.decode_step(cfg, p, bt, c)[0]  # noqa: E731
    return on_cpu(fn).lower(params, cache, dec).compile()


def model_opcount(arch: str, phase: str) -> OpCount:
    """Per-phase instruction-mix accounting for one model-zoo config.

    Compiles the smoke config's phase step, validates the backend actually
    reports cost properties (`hlo.xla_cost_analysis` raises a ValueError
    naming the backend otherwise — the contract this layer depends on),
    then charges the walked HLO to isa groups.  Cached per (arch, phase):
    compilation is the expensive part and mixes are pure functions of the
    pinned jax version.
    """
    if phase not in PHASES:
        raise ValueError(f"phase must be one of {PHASES}, got {phase!r}")
    key = (arch, phase)
    if key not in _CACHE:
        from repro.analysis import hlo

        compiled = _compiled_phase(arch, phase)
        hlo.xla_cost_analysis(compiled)   # backend capability gate
        _CACHE[key] = opcount_from_hlo(compiled.as_text())
    return _CACHE[key]
