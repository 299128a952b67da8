"""Partitioning plans: how every tensor of every arch lays out on the mesh.

Two attention strategies (DESIGN.md §5):

  * `heads` — classic Megatron TP: activations replicated over `model`,
    query heads / d_ff / vocab sharded.  Requires num_heads % tp == 0
    (granite, qwen1.5-110b, recurrentgemma, rwkv6).
  * `seq`  — sequence-parallel attention for awkward head counts (24/20/
    40/56/28): activations seq-sharded in the attention region (QKV weights
    replicated there), KV all-gathered for the flash scan, then the MLP
    region all-gathers tokens and runs d_ff TP with a reduce-scatter back.

Decode always runs a third layout: activations replicated over `model`
(T == 1 cannot shard), full KV caches sharded (batch -> data, seq -> model)
for the shard_map flash-decode, d_ff/vocab TP as usual.

FSDP (ZeRO-3) shards parameters over the data axes as well — switched on
automatically for >=20B-parameter archs; optimizer states always shard over
data (ZeRO-1) when divisibility allows.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


# At 256+ chips FSDP (params sharded over data) is strictly better for
# every assigned arch: the per-layer all-gather overlaps with compute and
# the replicated-params + replicated-grads footprint would otherwise
# dominate HBM even for 2.5B models (grad tree + fp32 update transients).
FSDP_THRESHOLD = 1e9


def _dp(data_axes: tuple) -> Any:
    return data_axes if len(data_axes) > 1 else data_axes[0]


@dataclass
class ShardingPlan:
    mesh: Mesh
    cfg: Any
    mode: str = "train"            # train | prefill | decode
    model_axis: str = "model"
    data_axes: tuple = ("data",)
    fsdp: bool | None = None

    # optional override: "dp" = pure data parallelism with ZeRO-3 (batch
    # sharded over EVERY mesh axis, weights gathered per layer).  The
    # §Perf hillclimb shows this beats TP+SP for small-and-mid dense
    # models at global batch 256 (see EXPERIMENTS.md).
    strategy_override: str | None = None

    def __post_init__(self):
        axes = self.mesh.axis_names
        self.data_axes = tuple(a for a in axes if a != self.model_axis)
        if self.fsdp is None:
            self.fsdp = self.cfg.param_count() > FSDP_THRESHOLD
        self.strategy = (self.cfg.attn_sharding
                         if self.mode != "decode" else "decode")
        if self.strategy_override and self.mode != "decode":
            self.strategy = self.strategy_override

    # -- helpers --------------------------------------------------------
    @property
    def dp(self):
        return _dp(self.data_axes)

    def _f(self, dim_size_ok=True):
        """The FSDP axis (or None) for weight dim 0/1."""
        return self.dp if self.fsdp else None

    def ns(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _divisible(self, n: int, axes) -> bool:
        if axes is None:
            return True
        axes = (axes,) if isinstance(axes, str) else tuple(
            a for t in ((axes,) if isinstance(axes, str) else axes)
            for a in ((t,) if isinstance(t, str) else t))
        size = int(np.prod([self.mesh.shape[a] for a in axes]))
        return n % size == 0

    # -- activation constraints ----------------------------------------
    def act(self, x, kind: str):
        if x is None:
            return x
        spec = self.act_spec(kind, x.ndim)
        if spec is None:
            return x
        spec = self._fit_cache(spec, x.shape)  # drop non-dividing axes
        return jax.lax.with_sharding_constraint(x, self.ns(spec))

    def act_spec(self, kind: str, ndim: int = 3):
        dp, m = self.dp, self.model_axis
        if self.strategy == "dp":
            # batch over every axis; nothing else sharded
            allax = tuple(self.data_axes) + (m,)
            table = {
                "hidden": P(allax, None, None),
                "attn_in": P(allax, None, None),
                "mlp_in": P(allax, None, None),
                "q_heads": P(allax, None, None, None),
                "kv_heads": P(allax, None, None, None),
                "attn_out": P(allax, None, None),
                "logits": P(allax, None, None),
            }
            return table.get(kind)
        seq = self.strategy == "seq"
        heads = self.strategy == "heads"
        table = {
            # (B, T, D) — the residual stream stays *sequence-sharded*
            # (Megatron-SP): the per-layer remat checkpoints are then 1/tp
            # of the replicated size, which is what lets the 80-layer /
            # 35-layer giants fit (DESIGN.md §5)
            "hidden": P(dp, m, None),
            # attention region: seq strategy computes QKV on the seq shards
            # directly; heads strategy all-gathers tokens first
            "attn_in": P(dp, m if seq else None, None),
            "mlp_in": P(dp, None, None),
            # (B, T, H, dh)
            "q_heads": P(dp, m if seq else None, m if heads else None, None),
            # (B, T, K, dh) — replicated for the flash scan
            "kv_heads": P(dp, None, None, None),
            # (B, T, H*dh)
            "attn_out": P(dp, m if seq else None, m if heads else None),
            # (B, T, V)
            "logits": P(dp, None, m),
        }
        if self.mode == "decode":  # T == 1: never shard the time dim
            table.update({
                "hidden": P(dp, None, None),
                "attn_in": P(dp, None, None),
                "q_heads": P(dp, None, None, None),
                "attn_out": P(dp, None, None),
            })
        return table.get(kind)

    # -- parameter specs ------------------------------------------------
    def param_specs(self, params_shapes) -> Any:
        """Map a (possibly eval_shape'd) param tree to PartitionSpecs."""
        if self.strategy == "dp":
            allax = tuple(self.data_axes) + (self.model_axis,)

            def dp_spec(path, leaf):
                # shard the largest dim over all axes (ZeRO-3 storage);
                # XLA all-gathers per layer for compute
                if leaf.ndim == 0:
                    return P()
                dims = list(leaf.shape)
                big = max(range(leaf.ndim), key=lambda i: dims[i])
                ent = [None] * leaf.ndim
                if dims[big] % (np.prod([self.mesh.shape[a]
                                         for a in allax])) == 0:
                    ent[big] = allax
                else:
                    f = self.dp
                    if self._divisible(dims[big], f):
                        ent[big] = f
                return P(*ent)

            return jax.tree_util.tree_map_with_path(dp_spec, params_shapes)
        f = self._f()
        m = self.model_axis
        seq = self.cfg.attn_sharding == "seq"

        rules = [
            # attention
            (r"attn/w[qkv]$", P(f, None) if seq else None),  # resolved below
            (r"attn/wq$", P(f, None if seq else m)),
            (r"attn/w[kv]$", P(f, None)),
            (r"attn/wo$", P(None if seq else m, f)),
            (r"attn/b[qkv]$", P(None)),
            # dense mlp / arctic residual
            (r"(mlp|dense)/w[ig]$", P(f, m)),
            (r"(mlp|dense)/wo$", P(m, f)),
            # moe
            (r"moe/router$", P(None, None)),
            (r"moe/w[ig]$", P(m, f, None)),
            (r"moe/wo$", P(m, None, f)),
            # rwkv time mix / channel mix
            (r"(wr|wk|wv|wg)$", P(f, m)),
            (r"wo$", P(m, f)),
            (r"ck$", P(f, m)),
            (r"cv$", P(m, f)),
            (r"cr$", P(f, None)),  # gate output replicated to match the
                                   # psum'd (kk @ cv) product elementwise
            (r"lora_a$", P(f, None)),
            (r"lora_b$", P(None, None)),
            (r"(u|ln_o|ln_o_b)$", P(m, None)),
            (r"(w0|mu|mu_cm)$", P(None)),
            # rg-lru
            (r"rec/wx$", P(f, m)),
            (r"rec/wgate$", P(f, m)),
            (r"rec/wout$", P(m, f)),
            (r"rec/conv$", P(None, m)),
            (r"rec/(w_r|b_r|w_i|b_i|lam)$", P(m,)),
            # embeddings / head
            (r"^embed$", P(m, None)),
            (r"^head$", P(f, m)),
            (r"(ln1|ln2|final_norm)$", P(None)),
        ]

        def spec_for(path, leaf):
            name = jax.tree_util.keystr(path, simple=True, separator="/")
            # strip list indices like segments/0/1/... and factored-moment
            # suffixes (opt v = {r, c}) so they inherit the parent's rule
            clean = re.sub(r"/\d+", "", name)
            clean = re.sub(r"/(r|c)$", "", clean)
            stacked = "segments" in name
            for pat, spec in rules:
                if spec is None:
                    continue
                if re.search(pat, clean):
                    spec = self._fit(spec, leaf.shape, stacked)
                    return spec
            return P(*((None,) * leaf.ndim))

        return jax.tree_util.tree_map_with_path(spec_for, params_shapes)

    def _fit(self, spec: P, shape, stacked: bool) -> P:
        """Prepend None for the stacked layer dim, pad to rank, and drop
        axes that do not divide the dimension."""
        entries = list(spec)
        if stacked:
            entries = [None] + entries
        while len(entries) < len(shape):
            entries.append(None)
        entries = entries[:len(shape)]
        out = []
        for dim, ax in zip(shape, entries):
            if ax is not None and not self._divisible(
                    dim, ax if isinstance(ax, tuple) else (ax,)):
                ax = None
            out.append(ax)
        return P(*out)

    def param_shardings(self, params_shapes):
        return jax.tree_util.tree_map(
            self.ns, self.param_specs(params_shapes))

    # -- inputs / cache --------------------------------------------------
    def input_shardings(self, specs: dict) -> dict:
        dp = self.dp
        if self.strategy == "dp":
            dp = tuple(self.data_axes) + (self.model_axis,)
        out = {}
        for k, v in specs.items():
            spec = P(dp) if v.ndim == 1 else P(*([dp] + [None] * (v.ndim - 1)))
            out[k] = self.ns(self._fit_cache(spec, v.shape))
        return out

    def cache_specs(self, cache_shapes):
        """Full attn caches: (n, B, S, K, dh) -> (None, dp, model, ...);
        everything else: batch over data, channel/head dims over model
        where divisible."""
        dp, m = self.dp, self.model_axis

        def spec_for(path, leaf):
            name = jax.tree_util.keystr(path, simple=True, separator="/")
            shape = leaf.shape
            if re.search(r"/(k|v)$", name):
                if shape[2] > max(self.cfg.window, 1):  # full cache
                    return self._fit_cache(P(None, dp, m, None, None), shape)
                return self._fit_cache(P(None, dp, None, None, None), shape)
            if re.search(r"/s$", name):      # rwkv state (n,B,H,N,N)
                return self._fit_cache(P(None, dp, m, None, None), shape)
            if re.search(r"/h$", name):      # rg-lru (n,B,W)
                return self._fit_cache(P(None, dp, m), shape)
            if re.search(r"/conv$", name):   # (n,B,cw-1,W)
                return self._fit_cache(P(None, dp, None, m), shape)
            return self._fit_cache(P(None, dp), shape)

        return jax.tree_util.tree_map_with_path(spec_for, cache_shapes)

    def _fit_cache(self, spec, shape):
        entries = list(spec)
        while len(entries) < len(shape):
            entries.append(None)
        entries = entries[:len(shape)]
        out = []
        for dim, ax in zip(shape, entries):
            if ax is not None and not self._divisible(
                    dim, ax if isinstance(ax, tuple) else (ax,)):
                ax = None
            out.append(ax)
        return P(*out)

    def cache_shardings(self, cache_shapes):
        return jax.tree_util.tree_map(self.ns, self.cache_specs(cache_shapes))
