"""Dispatch wrappers for decode attention (dense-cache and paged)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attn.kernel import decode_attention_pallas
from repro.kernels.decode_attn.paged_kernel import (
    paged_decode_attention_pallas,
)
from repro.kernels.decode_attn.ref import paged_decode_attention_ref
from repro.models.attention import decode_attention as _ref


def decode_attention_op(q: jax.Array, k_cache: jax.Array,
                        v_cache: jax.Array, lengths: jax.Array, *,
                        interpret: bool = False) -> jax.Array:
    """q [B,H,hd]; caches [B,L,KV,hd]; lengths [B] valid-token counts."""
    if jax.default_backend() == "tpu" or interpret:
        return decode_attention_pallas(
            q, k_cache, v_cache, lengths,
            interpret=jax.default_backend() != "tpu")
    L = k_cache.shape[1]
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    return _ref(q, k_cache, v_cache, valid)


def paged_decode_attention_op(q: jax.Array, pool_k: jax.Array,
                              pool_v: jax.Array, block_tables: jax.Array,
                              lengths: jax.Array, *,
                              interpret: bool = False) -> jax.Array:
    """Block-table-aware decode attention over one layer's paged pool.

    q [S,H,hd]; pool_k/v [n_blocks,KV,bs,hd]; block_tables [S,max_blocks]
    (-1 = unmapped); lengths [S] valid-token counts -> [S,H,hd].

    TPU: the Pallas kernel gathers K/V through the block table inside the
    kernel (no dense ``max_blocks * bs`` materialization per slot).
    Elsewhere: the XLA-gather reference (or the kernel in interpret mode
    when ``interpret=True``, for tests).
    """
    if jax.default_backend() == "tpu" or interpret:
        return paged_decode_attention_pallas(
            q, pool_k, pool_v, block_tables, lengths,
            interpret=jax.default_backend() != "tpu")
    return paged_decode_attention_ref(q, pool_k, pool_v, block_tables,
                                      lengths)
