"""Dispatch wrapper for chunked paged prefill attention."""
from __future__ import annotations

import jax

from repro.kernels.prefill_attn.kernel import paged_prefill_attention_pallas
from repro.kernels.prefill_attn.ref import paged_prefill_attention_ref


def paged_prefill_attention_op(q: jax.Array, pool_k: jax.Array,
                               pool_v: jax.Array, block_tables: jax.Array,
                               seg_ids: jax.Array, q_pos: jax.Array,
                               kv_lens: jax.Array, *,
                               interpret: bool = False) -> jax.Array:
    """Segment-packed prefill attention over one layer's paged pool.

    q [C,H,hd]; pool_k/v [n_blocks,KV,bs,hd]; block_tables [S,max_blocks]
    (-1 = unmapped); seg_ids [C] slot per row (-1 = padding); q_pos [C]
    absolute positions; kv_lens [S] resident-token counts -> [C,H,hd].

    TPU: the Pallas kernel walks the block table inside the kernel (no
    dense per-slot materialization). Elsewhere: the XLA-gather reference
    (or the kernel in interpret mode when ``interpret=True``, for tests).
    The reference ignores ``kv_lens`` — per-row inclusive lengths already
    mask everything; the kernel uses it only to skip empty key blocks.
    """
    if jax.default_backend() == "tpu" or interpret:
        return paged_prefill_attention_pallas(
            q, pool_k, pool_v, block_tables, seg_ids, q_pos, kv_lens,
            interpret=jax.default_backend() != "tpu")
    return paged_prefill_attention_ref(q, pool_k, pool_v, block_tables,
                                       seg_ids, q_pos)
