"""Oracles for decode attention (shared with models.attention).

``paged_decode_attention_ref`` is the XLA-gather adaptation of the paged
pointer walk: index the dense block pool with the block table (one gather)
and run the regular masked decode attention over the result. It is both
the correctness oracle for the Pallas paged kernel and the non-TPU
dispatch path of ``paged_decode_attention_op``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import decode_attention as decode_attention_ref  # noqa: F401


def gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """One layer's pool [n_blocks,KV,bs,hd] through block_tables [S,mb]
    (-1 = unmapped, read as block 0) -> contiguous [S, mb*bs, KV, hd]."""
    S, mb = block_tables.shape
    _, KV, bs, hd = pool.shape
    pages = pool[jnp.maximum(block_tables, 0)]       # [S, mb, KV, bs, hd]
    return pages.swapaxes(2, 3).reshape(S, mb * bs, KV, hd)


def paged_decode_attention_ref(q: jax.Array, pool_k: jax.Array,
                               pool_v: jax.Array, block_tables: jax.Array,
                               lengths: jax.Array) -> jax.Array:
    """q [S,H,hd]; pool_k/v [n_blocks,KV,bs,hd]; block_tables [S,mb]
    (-1 = unmapped); lengths [S] valid-token counts -> [S,H,hd]."""
    k = gather_pages(pool_k, block_tables)
    v = gather_pages(pool_v, block_tables)
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    return decode_attention_ref(q, k, v, valid)
