"""KV caches: full (global attention) and circular-window (local attention),
plus the flash-decode combine for sequence-sharded caches.

Decode memory layout (DESIGN.md §5): the full cache is sharded
(batch -> data, seq -> model).  One decode step must (a) write the new K/V
into whichever model-shard owns position `pos` and (b) attend over all
shards.  Both happen inside one `shard_map`: each shard computes partial
flash statistics (m, l, o) over its sequence chunk and the shards merge via
a logsumexp-weighted `psum` — the collective is O(B*H*Dh), never O(S).

The circular window cache (RecurrentGemma local attention) is only
`window` long, so it stays replicated across `model`; no collective at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def init_full_cache(cfg, batch: int, length: int):
    kh, dh = cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros((batch, length, kh, dh), dt),
            "v": jnp.zeros((batch, length, kh, dh), dt)}


def init_window_cache(cfg, batch: int):
    kh, dh, w = cfg.num_kv_heads, cfg.head_dim, cfg.window
    dt = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros((batch, w, kh, dh), dt),
            "v": jnp.zeros((batch, w, kh, dh), dt)}


def _write_slot(buf, new, idx):
    """buf: (B,S,K,dh); new: (B,K,dh); idx: (B,) — one-slot write per batch
    row, tolerant of out-of-range idx (writes the existing value back)."""
    s = buf.shape[1]
    idx_c = jnp.clip(idx, 0, s - 1)
    in_range = (idx >= 0) & (idx < s)

    def one(b, n, i, ok):
        cur = jax.lax.dynamic_slice_in_dim(b, i, 1, axis=0)
        val = jnp.where(ok, n[None], cur)
        return jax.lax.dynamic_update_slice_in_dim(b, val, i, axis=0)

    return jax.vmap(one)(buf, new, idx_c, in_range)


# ---------------------------------------------------------------------------
# single-device decode attention (oracle + smoke path)
# ---------------------------------------------------------------------------

def decode_attention_local(q, cache, k_new, v_new, pos, cfg):
    """q: (B,1,H,dh); cache k/v: (B,S,K,dh); pos: (B,) absolute position of
    the new token.  Returns (out (B,1,H,dh), new cache)."""
    b, _, h, dh = q.shape
    s = cache["k"].shape[1]
    kh = cfg.num_kv_heads
    g = h // kh
    ck = _write_slot(cache["k"], k_new[:, 0], pos)
    cv = _write_slot(cache["v"], v_new[:, 0], pos)
    qr = (q[:, 0].reshape(b, kh, g, dh) * dh ** -0.5).astype(jnp.float32)
    sc = jnp.einsum("bkgd,bskd->bkgs", qr, ck.astype(jnp.float32))
    valid = jnp.arange(s)[None, :] <= pos[:, None]
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, cv.astype(jnp.float32))
    return o.reshape(b, 1, h, dh).astype(q.dtype), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# sharded flash-decode (seq-sharded cache, psum combine)
# ---------------------------------------------------------------------------

def _scatter_token(buf, new, pos):
    """buf: (B,S,K,dh); new: (B,1,K,dh); pos: (B,).  An HLO scatter — GSPMD
    partitions it in place on the (data, model)-sharded cache and the
    donated buffer aliases (no full-cache copy, unlike in-shard_map
    updates)."""
    b = buf.shape[0]
    idx = jnp.stack([jnp.arange(b, dtype=pos.dtype), pos], axis=1)
    return jax.lax.scatter(
        buf, idx, new[:, 0],
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2),
            inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1)),
        indices_are_sorted=True, unique_indices=True)


def decode_attention_sharded(q, cache, k_new, v_new, pos, cfg, mesh,
                             data_axes=("data",), model_axis="model"):
    b_spec = data_axes if len(data_axes) > 1 else data_axes[0]
    cache_spec = P(b_spec, model_axis, None, None)
    q_spec = P(b_spec, None, None, None)
    kh = cfg.num_kv_heads

    # cache write OUTSIDE shard_map: scatter partitions in place
    ck_all = _scatter_token(cache["k"], k_new, pos)
    cv_all = _scatter_token(cache["v"], v_new, pos)

    def body(qs, ck, cv, ps):
        b, _, h, dh = qs.shape
        s_loc = ck.shape[1]
        g = h // kh
        shard = jax.lax.axis_index(model_axis)
        lo = shard * s_loc
        qr = (qs[:, 0].reshape(b, kh, g, dh) * dh ** -0.5).astype(jnp.float32)
        sc = jnp.einsum("bkgd,bskd->bkgs", qr, ck.astype(jnp.float32))
        valid = (lo + jnp.arange(s_loc))[None, :] <= ps[:, None]
        sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
        # partial flash statistics + logsumexp-weighted combine
        m_loc = sc.max(-1)                                   # (B,K,G)
        p = jnp.exp(sc - m_loc[..., None])
        l_loc = p.sum(-1)
        o_loc = jnp.einsum("bkgs,bskd->bkgd", p, cv.astype(jnp.float32))
        m_glob = jax.lax.pmax(m_loc, model_axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = jax.lax.psum(l_loc * corr, model_axis)
        o_glob = jax.lax.psum(o_loc * corr[..., None], model_axis)
        o = o_glob / jnp.maximum(l_glob[..., None], 1e-30)
        return o.reshape(b, 1, h, dh).astype(qs.dtype)

    o = jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, P(b_spec)),
        out_specs=q_spec,
        check_vma=False,
    )(q, ck_all, cv_all, pos)
    return o, {"k": ck_all, "v": cv_all}


def decode_attention(q, cache, k_new, v_new, pos, cfg, mesh=None,
                     data_axes=("data",)):
    if mesh is None:
        return decode_attention_local(q, cache, k_new, v_new, pos, cfg)
    return decode_attention_sharded(q, cache, k_new, v_new, pos, cfg, mesh,
                                    data_axes)


# ---------------------------------------------------------------------------
# circular window cache (local attention decode)
# ---------------------------------------------------------------------------

def window_decode_attention(q, cache, k_new, v_new, pos, cfg):
    """Rolling-buffer local attention; buffer slot = abs_pos % window."""
    b, _, h, dh = q.shape
    w = cfg.window
    kh = cfg.num_kv_heads
    g = h // kh
    slot = pos % w
    ck = _write_slot(cache["k"], k_new[:, 0], slot)
    cv = _write_slot(cache["v"], v_new[:, 0], slot)
    # absolute position held by each slot after the write
    sl = jnp.arange(w)[None, :]
    abs_pos = pos[:, None] - ((pos[:, None] - sl) % w)
    valid = abs_pos >= 0  # window recency is implied by the buffer size
    qr = (q[:, 0].reshape(b, kh, g, dh) * dh ** -0.5).astype(jnp.float32)
    sc = jnp.einsum("bkgd,bskd->bkgs", qr, ck.astype(jnp.float32))
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, cv.astype(jnp.float32))
    return o.reshape(b, 1, h, dh).astype(q.dtype), {"k": ck, "v": cv}
