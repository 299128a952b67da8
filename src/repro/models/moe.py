"""Mixture-of-Experts layer with expert parallelism over the `model` axis.

Placement: experts are statically sharded over `model` (E/tp per device,
stacked leading axis).  Activations at MoE entry are replicated across
`model` (the TP convention used by the attention path), so dispatch needs
NO all-to-all: every shard gathers the tokens routed to *its* experts into a
capacity buffer, runs its expert matmuls, scatter-adds its partial output
and the shard partials merge in the same `psum` that TP-MLP would need
anyway.  Token order is deterministic (first-come capacity, paper-faithful
"first-served slots").

The paper hook: the per-layer expert load vector (`aux["expert_load"]`) is
the opcode-access set of `repro.core.expert_slots` — the serving engine
feeds it to the disambiguator to track slot residency and fill traffic.

The gather/scatter index machinery is mirrored 1:1 by the Pallas dispatch
kernel (`repro.kernels.moe_dispatch`); `moe_apply_dense` is its oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def init_moe(key, cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * d ** -0.5,
        "wi": jax.random.normal(ks[1], (e, d, f), dt) * d ** -0.5,
        "wg": jax.random.normal(ks[2], (e, d, f), dt) * d ** -0.5,
        "wo": jax.random.normal(ks[3], (e, f, d), dt) * f ** -0.5,
    }


def _capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(8, -(-c // 8) * 8)


def route(x2d: jnp.ndarray, router_w: jnp.ndarray, cfg,
          router_bias: jnp.ndarray | None = None):
    """x2d: (N, D) -> expert ids (N,k), gates (N,k) f32.

    router_bias (E,) implements *slot-hit routing* (DESIGN.md §2): the
    serving engine biases selection toward slot-resident experts; gates are
    renormalised from the UNBIASED logits so mixture weights stay faithful
    to the learned router."""
    logits = (x2d.astype(jnp.float32) @ router_w)
    sel = logits if router_bias is None else logits + router_bias
    _, ids = jax.lax.top_k(sel, cfg.top_k)
    orig = jnp.take_along_axis(logits, ids, axis=-1)
    gates = jax.nn.softmax(orig, axis=-1)
    return ids, gates


def _dispatch_indices(ids: jnp.ndarray, n_experts: int, capacity: int):
    """First-come positions within each expert's capacity buffer.

    ids: (N, k) -> (pos (N,k) int32, kept (N,k) bool).
    """
    n, k = ids.shape
    flat = ids.reshape(-1)                                   # (N*k,)
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                # exclusive
    pos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    kept = pos < capacity
    return pos.reshape(n, k), kept.reshape(n, k)


def _expert_ffn(buf, wi, wg, wo, cfg):
    """buf: (E?, C, D) through stacked experts."""
    if cfg.mlp in ("swiglu", "gelu_glu"):
        act = jax.nn.silu if cfg.mlp == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
            jnp.einsum("ecd,edf->ecf", buf, wi)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, wi))
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _gather_compute_scatter(x2d, ids, gates, pos, kept, wi, wg, wo, cfg,
                            e_lo: int, e_local: int, capacity: int):
    """Dispatch the tokens routed to experts [e_lo, e_lo+e_local) and return
    this shard's partial output (N, D)."""
    n, d = x2d.shape
    k = ids.shape[1]
    local = (ids >= e_lo) & (ids < e_lo + e_local) & kept    # (N,k)
    e_loc = jnp.where(local, ids - e_lo, 0)
    p_loc = jnp.where(local, pos, 0)
    w = local.astype(x2d.dtype)

    buf = jnp.zeros((e_local, capacity, d), x2d.dtype)
    xk = jnp.broadcast_to(x2d[:, None, :], (n, k, d)) * w[..., None]
    buf = buf.at[e_loc.reshape(-1), p_loc.reshape(-1)].add(
        xk.reshape(n * k, d))

    out_buf = _expert_ffn(buf, wi, wg, wo, cfg)              # (E_loc, C, D)

    y = out_buf[e_loc.reshape(-1), p_loc.reshape(-1)].reshape(n, k, d)
    y = y * (gates.astype(x2d.dtype) * w)[..., None]
    return y.sum(axis=1)


def moe_apply_dense(p, x, cfg, router_bias=None):
    """Single-device reference path (smoke tests / kernel oracle)."""
    b, t, d = x.shape
    x2d = x.reshape(-1, d)
    cap = _capacity(x2d.shape[0], cfg)
    ids, gates = route(x2d, p["router"], cfg, router_bias)
    pos, kept = _dispatch_indices(ids, cfg.num_experts, cap)
    y = _gather_compute_scatter(
        x2d, ids, gates, pos, kept, p["wi"], p["wg"], p["wo"], cfg,
        0, cfg.num_experts, cap)
    load = jnp.zeros((cfg.num_experts,), jnp.int32).at[ids.reshape(-1)].add(
        kept.reshape(-1).astype(jnp.int32))
    return y.reshape(b, t, d), {"expert_load": load}


MOE_TOKEN_CHUNK = 16_384


def moe_apply_sharded(p, x, cfg, mesh, data_axes=("data",),
                      model_axis="model", router_bias=None):
    """Expert-parallel path: experts sharded over `model`, x replicated
    over `model` and sharded over data axes on batch.

    Tokens are processed in chunks of MOE_TOKEN_CHUNK inside a lax.scan so
    the dispatch transients (one-hot cumsum, gathered (N,k,D) buffers)
    never scale with the full B*T token count — this is what keeps the
    400B-class train_4k cells inside HBM."""
    tp = mesh.shape[model_axis]
    e_local = cfg.num_experts // tp
    dp = P(data_axes if len(data_axes) > 1 else data_axes[0])
    x_spec = P(dp[0], None, None)
    w_spec = P(model_axis, None, None)

    def body(router_w, wi, wg, wo, xs):
        b, t, d = xs.shape
        x2d = xs.reshape(-1, d)
        n = x2d.shape[0]
        shard = jax.lax.axis_index(model_axis)
        e_lo = shard * e_local

        def one_chunk(xc):
            cap = _capacity(xc.shape[0], cfg)
            ids, gates = route(xc, router_w, cfg, router_bias)
            pos, kept = _dispatch_indices(ids, cfg.num_experts, cap)
            y = _gather_compute_scatter(
                xc, ids, gates, pos, kept, wi, wg, wo, cfg,
                e_lo, e_local, cap)
            load = jnp.zeros((cfg.num_experts,), jnp.int32).at[
                ids.reshape(-1)].add(kept.reshape(-1).astype(jnp.int32))
            return y, load

        if n > MOE_TOKEN_CHUNK and n % MOE_TOKEN_CHUNK == 0:
            nc = n // MOE_TOKEN_CHUNK
            xr = x2d.reshape(nc, MOE_TOKEN_CHUNK, d)
            chunk_fn = jax.checkpoint(
                one_chunk, policy=jax.checkpoint_policies.nothing_saveable)
            y, load = jax.lax.map(chunk_fn, xr)
            y = y.reshape(n, d)
            load = load.sum(axis=0)
        else:
            y, load = one_chunk(x2d)
        y = jax.lax.psum(y, model_axis)
        load = jax.lax.psum(load, data_axes)  # global per-layer expert load
        return y.reshape(b, t, d), load

    y, load = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), w_spec, w_spec, w_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p["router"], p["wi"], p["wg"], p["wo"], x)
    return y, {"expert_load": load}


def moe_apply(p, x, cfg, mesh=None, data_axes=("data",),
              router_bias=None):
    if mesh is None:
        return moe_apply_dense(p, x, cfg, router_bias)
    return moe_apply_sharded(p, x, cfg, mesh, data_axes,
                             router_bias=router_bias)
