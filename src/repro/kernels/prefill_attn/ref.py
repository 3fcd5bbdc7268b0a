"""Oracle for chunked paged prefill attention.

A prefill chunk is a batch of ``C`` query rows, each tagged with the
serving slot it belongs to (``seg_ids``) and its absolute position in
that slot's sequence (``q_pos``). Row ``i`` must attend exactly the keys
a decode step at position ``q_pos[i]`` would see: everything its slot
has resident in the paged pool up to and *including* itself (the chunk
writes each row's K/V into the pool before attending). That makes the
reference a one-liner on top of ``paged_decode_attention_ref`` — give
every row its own slot's block table and an inclusive length — and makes
the per-row math bit-identical to the per-token decode-replay path the
chunk lane replaces. Causal masking within the chunk and isolation
between packed prompts both fall out of the per-row lengths/tables: a
row can never see positions past its own, nor blocks outside its slot's
table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attn.ref import paged_decode_attention_ref


def paged_prefill_attention_ref(q: jax.Array, pool_k: jax.Array,
                                pool_v: jax.Array, block_tables: jax.Array,
                                seg_ids: jax.Array, q_pos: jax.Array
                                ) -> jax.Array:
    """q [C,H,hd]; pool_k/v [n_blocks,KV,bs,hd]; block_tables [S,mb]
    (-1 = unmapped); seg_ids [C] slot per row (-1 = padding row);
    q_pos [C] absolute position per row -> [C,H,hd] (0 for padding)."""
    row_tables = block_tables[jnp.maximum(seg_ids, 0)]       # [C, mb]
    out = paged_decode_attention_ref(q, pool_k, pool_v, row_tables,
                                     q_pos + 1)
    return jnp.where((seg_ids >= 0)[:, None, None], out,
                     jnp.zeros_like(out))
