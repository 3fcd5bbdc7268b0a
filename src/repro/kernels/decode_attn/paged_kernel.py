"""Pallas TPU kernel: paged (block-table) single-token decode attention.

GPU PagedAttention walks the block table with pointer indirection inside
the kernel; the XLA fallback in ``rollout.paged_cache.gather_kv``
materializes a dense ``[S, max_blocks * block_size, KV, hd]`` view per
layer instead — fine for toy pools, ruinous for production ones. This
kernel is the TPU-native middle ground: the block table and sequence
lengths ride in as *scalar-prefetch* operands, so the k/v ``index_map``
selects the physical pool block for each (sequence, key-block) grid cell
and only ``block_size`` rows of K/V ever stream through VMEM at a time.
No dense per-slot materialization of the pool happens at any point.

The pool is ``[n_blocks, KV, block_size, hd]``: one (block, kv-head) tile
is a contiguous ``[block_size, hd]`` slab, the shape the TPU's
(sublane, lane) tiling accepts as a block.

Grid: ``(n_seqs, n_kv_heads, max_blocks_per_seq)`` with an online-softmax
accumulator over the innermost (key-block) axis, masked by the
per-sequence valid-token count. GQA rides in the query block: each grid
cell carries the ``G = H // KV`` query heads that share one kv head, so a
K/V tile is read once per group, not once per query head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, len_ref, q_ref, k_ref, v_ref, o_ref, acc, m_ref,
            l_ref, *, bs: int, n_b: int, scale: float):
    s_i = pl.program_id(0)
    j = pl.program_id(2)
    n_valid = len_ref[s_i]

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # key blocks past the valid count hold nothing to attend (unmapped
    # table entries are clamped to block 0 by the wrapper and land here)
    @pl.when(j * bs < n_valid)
    def _accumulate():
        q = q_ref[...]                               # [G, hd]
        k = k_ref[...]                               # [bs, hd]
        v = v_ref[...]                               # [bs, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, bs]
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < n_valid, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]      # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_b - 1)
    def _done():
        o_ref[...] = (acc[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q: jax.Array, pool_k: jax.Array,
                                  pool_v: jax.Array, block_tables: jax.Array,
                                  lengths: jax.Array, *,
                                  interpret: bool = False) -> jax.Array:
    """q [S,H,hd]; pool_k/v [n_blocks,KV,bs,hd] (one layer's pool);
    block_tables [S,max_blocks] int32 (-1 = unmapped); lengths [S]
    valid-token counts -> [S,H,hd]."""
    S, H, hd = q.shape
    KV, bs = pool_k.shape[1], pool_k.shape[2]
    mb = block_tables.shape[1]
    G = H // KV
    tables = jnp.maximum(block_tables, 0).astype(jnp.int32)
    kernel = functools.partial(_kernel, bs=bs, n_b=mb, scale=hd ** -0.5)
    # the paged gather: physical block straight from the table
    kv_spec = pl.BlockSpec((None, None, bs, hd),
                           lambda s, g, j, tbl, ln: (tbl[s, j], g, 0, 0))
    q_spec = pl.BlockSpec((None, None, G, hd),
                          lambda s, g, j, tbl, ln: (s, g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, KV, mb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lengths.astype(jnp.int32),
      q.reshape(S, KV, G, hd).astype(pool_k.dtype), pool_k, pool_v)
    return out.reshape(S, H, hd)
