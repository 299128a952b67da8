"""Lookups over small axes as compare-and-select, with no gather or scatter.

The engines index three axes that are small by construction: the opcode
alphabet (`isa.NUM_INSTRUCTIONS` entries) through the per-opcode tag and
cost tables, the disambiguator-tag axis (at most one tag per opcode), and
the `num_tags + 1` buckets of a stack-distance histogram.  On a TPU an
element gather or scatter runs one element at a time, about a hundred
times slower than an elementwise pass over the same array; over an axis
this short, comparing every index against every entry and selecting is
an elementwise pass that XLA fuses with its neighbours.

Every function returns exactly what the indexing op it replaces returns
(int32 in, int32 out), on every backend.  Each refuses an indexed axis of
more than `MAX_AXIS` entries: the cost grows with the axis, and every
axis the engines index is bounded by the RV32IMF alphabet.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["MAX_AXIS", "pick_along_tags", "small_bincount", "table_lookup"]

MAX_AXIS = 128


def _check_axis(n: int, what: str) -> None:
    if n > MAX_AXIS:
        raise ValueError(
            f"{what} has {n} entries; compare-and-select lookups serve at "
            f"most {MAX_AXIS}")


def table_lookup(table, idx):
    """`table[..., idx]`: a (T,) table indexed by any `idx`, or a (P, T)
    per-program table whose rows align with `idx`'s second-last axis (a
    (..., P, N) stream).  `idx` must lie in [0, T)."""
    table = jnp.asarray(table)
    idx = jnp.asarray(idx)
    n = table.shape[-1]
    _check_axis(n, "table")
    entries = jnp.moveaxis(table, -1, 0)
    if table.ndim > 1:
        entries = entries[..., None]
    out = jnp.broadcast_to(entries[0], idx.shape)
    for j in range(1, n):
        out = jnp.where(idx == j, entries[j], out)
    return out


def pick_along_tags(prev, tags):
    """`prev[..., i, tags[..., i]]` over the last (tag) axis of `prev`; a
    tag outside [0, T) picks 0."""
    prev = jnp.asarray(prev)
    n = prev.shape[-1]
    _check_axis(n, "tag axis")
    hit = jnp.asarray(tags)[..., None] == jnp.arange(n, dtype=jnp.int32)
    return jnp.sum(jnp.where(hit, prev, 0), axis=-1, dtype=prev.dtype)


def small_bincount(bucket, length: int):
    """`jnp.bincount(bucket, length=length)` for a 1-D `bucket`: a one-hot
    compare summed over the access axis; values outside [0, length) are
    not counted."""
    _check_axis(length, "histogram")
    hit = jnp.asarray(bucket)[:, None] == jnp.arange(length, dtype=jnp.int32)
    return jnp.sum(hit, axis=0, dtype=jnp.int32)
