"""Paged KV cache (vLLM-style) adapted to JAX/TPU.

The paper's rollout engines (SGLang/vLLM) rely on paged attention for
memory efficiency under continuous batching. GPU PagedAttention walks a
block table with pointer indirection inside the kernel; the TPU-native
adaptation keeps a *dense block pool* as one array and turns the block
table into a gather index — XLA lowers the page gather + attention to
contiguous DMA-friendly reads, and freed blocks are recycled by index
bookkeeping on the host.

Layout:
  pool_k/pool_v : [n_layers, n_blocks, KV, block_size, hd] — one (block,
                  kv-head) page is a contiguous [block_size, hd] tile, the
                  block shape the paged Pallas kernels stream through VMEM
  block_tables  : [max_seqs, max_blocks_per_seq] int32 (-1 = unmapped)
  seq_lens      : [max_seqs] int32
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.decode_attn.ref import gather_pages


@dataclasses.dataclass
class PagedCacheState:
    pool_k: jax.Array
    pool_v: jax.Array
    block_tables: jax.Array  # [max_seqs, max_blocks]
    seq_lens: jax.Array      # [max_seqs]

    @property
    def block_size(self) -> int:
        return self.pool_k.shape[3]

    @property
    def max_blocks(self) -> int:
        return self.block_tables.shape[1]


class BlockAllocator:
    """Host-side free-list over pool blocks (shared across layers).

    Blocks are reference-counted so the radix prefix cache and multiple
    sequences can share one physical block (GRPO group members sharing a
    prefilled prompt). ``alloc`` hands out blocks at refcount 1;
    ``release`` decrements and only returns a block to the free list when
    its count reaches zero.
    """

    def __init__(self, n_blocks: int):
        self.n_blocks = n_blocks
        self.free: List[int] = list(range(n_blocks - 1, -1, -1))
        self.refcount: Dict[int, int] = {}
        self.forks = 0  # copy-on-write forks performed (metrics)

    def alloc(self, n: int) -> List[int]:
        if len(self.free) < n:
            raise RuntimeError(f"paged cache OOM: need {n} blocks, "
                               f"have {len(self.free)}")
        blocks = [self.free.pop() for _ in range(n)]
        for b in blocks:
            self.refcount[b] = 1
        return blocks

    def incref(self, block: int) -> None:
        assert block in self.refcount, f"incref of unallocated block {block}"
        self.refcount[block] += 1

    def decref(self, block: int) -> bool:
        """Drop one reference; returns True when the block was freed."""
        rc = self.refcount.get(block)
        assert rc is not None and rc > 0, \
            f"decref of unallocated block {block}"
        if rc == 1:
            del self.refcount[block]
            self.free.append(block)
            return True
        self.refcount[block] = rc - 1
        return False

    def refs(self, block: int) -> int:
        return self.refcount.get(block, 0)

    def release(self, blocks: List[int]) -> None:
        for b in blocks:
            if b >= 0:
                self.decref(b)

    @property
    def n_free(self) -> int:
        return len(self.free)


def init_paged_cache(cfg: ModelConfig, *, n_blocks: int, block_size: int,
                     max_seqs: int, max_blocks_per_seq: int,
                     dtype=None) -> PagedCacheState:
    assert cfg.mla is None, \
        "paged cache supports GQA/MHA attention stacks (no MLA yet)"
    dtype = dtype or jnp.dtype(cfg.dtype)
    # attention-free (pure SSM) stacks get a zero-layer pool: block/length
    # bookkeeping stays uniform across architectures at zero memory cost.
    n_attn = sum(1 for k in cfg.block_kinds() if k == "attn")
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (n_attn, n_blocks, kv, block_size, hd)
    return PagedCacheState(
        pool_k=jnp.zeros(shape, dtype),
        pool_v=jnp.zeros(shape, dtype),
        block_tables=jnp.full((max_seqs, max_blocks_per_seq), -1, jnp.int32),
        seq_lens=jnp.zeros((max_seqs,), jnp.int32),
    )


# ------------------------------------------------------------ SSM state pool
@dataclasses.dataclass
class SSMStateCache:
    """Constant-size per-slot recurrent state for SSM/hybrid decode.

    Unlike KV, Mamba2 state does not grow with sequence length, so no
    block table is needed: engine slot ``i`` owns row ``i`` of each pool.

      conv  : [n_ssm_layers, max_seqs, d_conv-1, conv_dim]  (model dtype)
      state : [n_ssm_layers, max_seqs, nh, hd, d_state]     (float32)
    """
    conv: jax.Array
    state: jax.Array

    @property
    def max_seqs(self) -> int:
        return self.conv.shape[1]

    @property
    def n_layers(self) -> int:
        return self.conv.shape[0]


def init_ssm_state_cache(cfg: ModelConfig, *, max_seqs: int,
                         dtype=None) -> SSMStateCache:
    assert cfg.ssm is not None, "SSM state cache needs cfg.ssm"
    dtype = dtype or jnp.dtype(cfg.dtype)
    s, d = cfg.ssm, cfg.d_model
    n_ssm = sum(1 for k in cfg.block_kinds() if k == "ssm")
    conv_dim = s.d_inner(d) + 2 * s.d_state
    return SSMStateCache(
        conv=jnp.zeros((n_ssm, max_seqs, s.d_conv - 1, conv_dim), dtype),
        state=jnp.zeros((n_ssm, max_seqs, s.num_heads(d), s.head_dim,
                         s.d_state), jnp.float32),
    )


def ssm_reset_slots(cache: SSMStateCache, slots) -> SSMStateCache:
    """Zero conv window + state for ``slots`` (fresh sequences)."""
    slots = jnp.asarray(slots, jnp.int32)
    return SSMStateCache(conv=cache.conv.at[:, slots].set(0),
                         state=cache.state.at[:, slots].set(0.0))


def ssm_fork_slot(cache: SSMStateCache, src: int, dst: int) -> SSMStateCache:
    """Clone slot ``src``'s recurrent state into ``dst``.

    The SSM analogue of ``fork_block``: recurrent state is private per
    slot (nothing is refcounted), so a fork is a plain copy.
    """
    return SSMStateCache(conv=cache.conv.at[:, dst].set(cache.conv[:, src]),
                         state=cache.state.at[:, dst].set(
                             cache.state[:, src]))


class SSMSlotPool:
    """Host-side lifecycle mirror for SSM-state slots.

    Constant-size state needs no free-list — slot ids are the engine's
    own — but the *lifecycle* must mirror ``BlockAllocator``'s: map on
    admit, release on finish/preempt (a released slot is re-zeroed before
    reuse), fork when a mapped slot's state is cloned. The pool tracks
    the mapped set and turns double-map / double-release bookkeeping bugs
    into immediate assertions, the way the KV path surfaces them as
    refcount errors.
    """

    def __init__(self, max_seqs: int):
        self.max_seqs = max_seqs
        self.mapped: set = set()
        self.forks = 0  # state clones performed (metrics)

    def map(self, slot: int) -> None:
        assert 0 <= slot < self.max_seqs, f"SSM slot {slot} out of range"
        assert slot not in self.mapped, f"double map of SSM slot {slot}"
        self.mapped.add(slot)

    def release(self, slot: int) -> None:
        assert slot in self.mapped, f"release of unmapped SSM slot {slot}"
        self.mapped.discard(slot)

    def fork(self, src: int, dst: int) -> None:
        assert src in self.mapped, f"fork from unmapped SSM slot {src}"
        self.map(dst)
        self.forks += 1

    def is_mapped(self, slot: int) -> bool:
        return slot in self.mapped

    @property
    def n_free(self) -> int:
        return self.max_seqs - len(self.mapped)


# ------------------------------------------------------------------ device ops
def write_pages(pool: jax.Array, layer: Optional[int], blocks: jax.Array,
                offsets: jax.Array, x: jax.Array) -> jax.Array:
    """Scatter per-token K or V into the pool at (block, offset) pairs.

    ``x`` is [N, KV, hd] for one ``layer``, or [L, N, KV, hd] for every
    layer at once when ``layer`` is None.
    """
    if layer is None:
        x = jnp.moveaxis(x, 0, 1)  # the advanced (block, offset) axis leads
        return pool.at[:, blocks, :, offsets].set(x.astype(pool.dtype))
    return pool.at[layer, blocks, :, offsets].set(x.astype(pool.dtype))


def write_token(state: PagedCacheState, layer: int, k: jax.Array,
                v: jax.Array, slot_ids: jax.Array) -> PagedCacheState:
    """Write one token's K/V for active slots.

    k, v: [B_active, KV, hd]; slot_ids: [B_active] rows of block_tables.
    The target block/offset come from seq_lens (position = current len).
    """
    bs = state.block_size
    lens = state.seq_lens[slot_ids]
    block_idx = lens // bs
    offset = lens % bs
    blocks = state.block_tables[slot_ids, block_idx]  # [B_active]
    # Unmapped (-1) positions are routed to the scratch block — the last
    # pool block, which the engine reserves as a write sink (the prefill
    # lane uses the same convention) — never to live block 0: a
    # bookkeeping bug then wastes a write instead of corrupting KV.
    unmapped = blocks < 0
    blocks = jnp.where(unmapped, state.pool_k.shape[1] - 1, blocks)
    offset = jnp.where(unmapped, 0, offset)

    pool_k = write_pages(state.pool_k, layer, blocks, offset, k)
    pool_v = write_pages(state.pool_v, layer, blocks, offset, v)
    return dataclasses.replace(state, pool_k=pool_k, pool_v=pool_v)


def gather_kv(state: PagedCacheState, layer: int, slot_ids: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Materialize per-slot K/V views [B, max_blocks*bs, KV, hd] + validity.

    This is the TPU adaptation of the paged-attention pointer walk: a
    gather over the block pool (one XLA gather per layer), letting the
    regular decode attention run on the result.
    """
    tables = state.block_tables[slot_ids]            # [B, max_blocks]
    k = gather_pages(state.pool_k[layer], tables)    # [B, mb*bs, KV, hd]
    v = gather_pages(state.pool_v[layer], tables)
    lens = state.seq_lens[slot_ids]
    valid = jnp.arange(k.shape[1])[None, :] < lens[:, None]
    # tokens in unmapped blocks are never valid (len bound covers them)
    return k, v, valid


def bump_lens(state: PagedCacheState, slot_ids: jax.Array
              ) -> PagedCacheState:
    return dataclasses.replace(
        state, seq_lens=state.seq_lens.at[slot_ids].add(1))


# ------------------------------------------------------------------- host ops
def map_sequence(state: PagedCacheState, allocator: BlockAllocator,
                 slot: int, n_tokens: int) -> PagedCacheState:
    """Allocate blocks for a new sequence of n_tokens (prefill) + growth."""
    bs = state.block_size
    n_needed = -(-n_tokens // bs)
    blocks = allocator.alloc(n_needed)
    table = np.asarray(state.block_tables[slot]).copy()
    table[:] = -1
    table[: n_needed] = blocks
    return dataclasses.replace(
        state,
        block_tables=state.block_tables.at[slot].set(jnp.asarray(table)),
        seq_lens=state.seq_lens.at[slot].set(0),
    )


def ensure_capacity(state: PagedCacheState, allocator: BlockAllocator,
                    slot: int) -> PagedCacheState:
    """Grow the sequence's table by one block if the next token needs it."""
    bs = state.block_size
    length = int(state.seq_lens[slot])
    block_idx = length // bs
    if block_idx >= state.max_blocks:
        raise RuntimeError("sequence exceeded max_blocks_per_seq")
    if int(state.block_tables[slot, block_idx]) < 0:
        (blk,) = allocator.alloc(1)
        state = dataclasses.replace(
            state, block_tables=state.block_tables.at[slot, block_idx].set(
                blk))
    return state


def write_range(length: int, n_tokens: int, block_size: int,
                max_blocks: int) -> Tuple[int, int]:
    """(first, last) block indices the next ``n_tokens`` writes of a
    sequence at ``length`` will touch — the single definition both the
    headroom estimate and the actual allocation use."""
    first = length // block_size
    last = (length + n_tokens - 1) // block_size
    if last >= max_blocks:
        raise RuntimeError("sequence exceeded max_blocks_per_seq")
    return first, last


def alloc_horizon_blocks(allocator: BlockAllocator, tables: np.ndarray,
                         lens: np.ndarray, slot_tokens: Dict[int, int],
                         block_size: int) -> bool:
    """Pre-map every block the next ``n`` writes of each slot will touch.

    ``slot_tokens`` maps slot -> upcoming token count (a decode horizon).
    ``tables``/``lens`` are the caller's *host mirrors* of the device
    block tables and sequence lengths: the mirror is edited in place and
    no device readback happens here, so a fused multi-token decode can be
    prepared with zero blocking transfers (the caller pushes the mirror
    to the device once, if anything changed). Returns True when at least
    one block was mapped.
    """
    changed = False
    for slot, n_tokens in slot_tokens.items():
        if n_tokens <= 0:
            continue
        first, last = write_range(int(lens[slot]), n_tokens, block_size,
                                  tables.shape[1])
        for i in range(first, last + 1):
            if tables[slot, i] < 0:
                (blk,) = allocator.alloc(1)
                tables[slot, i] = blk
                changed = True
    return changed


def map_sequence_prefixed(state: PagedCacheState, allocator: BlockAllocator,
                          slot: int, prefix_blocks: List[int],
                          n_prefix_tokens: int, n_tokens: int
                          ) -> PagedCacheState:
    """Map a sequence whose first ``n_prefix_tokens`` live in shared blocks.

    ``prefix_blocks`` must already carry a reference for this sequence
    (the prefix cache increfs on match); only the remainder of the table
    is freshly allocated. ``seq_lens`` starts at ``n_prefix_tokens`` —
    the cached KV is already resident, so prefill only has to run the
    suffix.
    """
    bs = state.block_size
    n_needed = -(-n_tokens // bs)
    assert n_needed <= state.max_blocks, "sequence exceeds max_blocks_per_seq"
    assert len(prefix_blocks) <= n_needed, (prefix_blocks, n_tokens)
    fresh = allocator.alloc(n_needed - len(prefix_blocks))
    table = np.full((state.max_blocks,), -1, np.int32)
    table[: len(prefix_blocks)] = prefix_blocks
    table[len(prefix_blocks): n_needed] = fresh
    return dataclasses.replace(
        state,
        block_tables=state.block_tables.at[slot].set(jnp.asarray(table)),
        seq_lens=state.seq_lens.at[slot].set(n_prefix_tokens),
    )


def fork_block(state: PagedCacheState, allocator: BlockAllocator,
               block: int) -> Tuple[PagedCacheState, int]:
    """Copy-on-write: clone ``block`` into a fresh private block.

    Copies the pool contents across all layers and drops one reference on
    the shared original.
    """
    (new,) = allocator.alloc(1)
    pool_k = state.pool_k.at[:, new].set(state.pool_k[:, block])
    pool_v = state.pool_v.at[:, new].set(state.pool_v[:, block])
    allocator.decref(block)
    allocator.forks += 1
    return dataclasses.replace(state, pool_k=pool_k, pool_v=pool_v), new


def ensure_writable(state: PagedCacheState, allocator: BlockAllocator,
                    slot: int) -> PagedCacheState:
    """CoW guard: fork the block the next token writes into if shared.

    A slot resuming on top of radix-cached prompt blocks may have its
    write position inside a block other sequences (or the cache itself)
    still reference; writing there would corrupt the shared prefix.
    """
    bs = state.block_size
    length = int(state.seq_lens[slot])
    block_idx = length // bs
    if block_idx >= state.max_blocks:
        return state  # ensure_capacity raises the real error
    blk = int(state.block_tables[slot, block_idx])
    if blk >= 0 and allocator.refs(blk) > 1:
        state, new = fork_block(state, allocator, blk)
        state = dataclasses.replace(
            state, block_tables=state.block_tables.at[slot, block_idx].set(
                new))
    return state


def release_sequence(state: PagedCacheState, allocator: BlockAllocator,
                     slot: int) -> PagedCacheState:
    table = [int(b) for b in np.asarray(state.block_tables[slot])]
    allocator.release([b for b in table if b >= 0])
    return dataclasses.replace(
        state,
        block_tables=state.block_tables.at[slot].set(
            jnp.full((state.max_blocks,), -1, jnp.int32)),
        seq_lens=state.seq_lens.at[slot].set(0),
    )
