"""Multi-architecture paged serving: SSM-state cache + hybrid decode.

Pins the PR's core property — mamba2 (pure SSM) and zamba2-style hybrid
stacks decode through the continuous-batching engine (``step_horizon``,
chunked prefill, slot reuse, preemption, publish-resume) with greedy
bit-parity against the whole-sequence ``model.prefill`` +
``model.decode_step`` reference — plus the serving-layer bug-sweep
regressions (scratch-block ``write_token`` routing, admission eviction
accounting, SSM slot-pool lifecycle).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.data import tokenizer as tok
from repro.kernels.ssd.kernel import ssd_decode_step_pallas
from repro.kernels.ssd.ref import ssd_decode_step_ref, ssd_sequential_ref
from repro.models import model as M
from repro.models.layers import logits_from_hidden
from repro.rollout import paged_cache as pc
from repro.rollout.continuous import ContinuousBatchingEngine


@pytest.fixture(scope="module")
def ssm_setup():
    cfg = dataclasses.replace(get_config("mamba2-370m-reduced"),
                              dtype="float32")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def hybrid_setup():
    # zamba2-style, shrunk: kinds (ssm, ssm, attn) exercises the shared
    # attention layer without the reduced config's full 6-layer stack
    cfg = dataclasses.replace(get_config("zamba2-1.2b-reduced"),
                              num_layers=3, attn_every=3, dtype="float32")
    assert cfg.block_kinds() == ("ssm", "ssm", "attn")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(2))


def _engine(cfg, **kw):
    base = dict(max_seqs=2, block_size=4, n_blocks=33,
                max_blocks_per_seq=16, greedy=True, decode_horizon=4,
                prefill_chunk=8)
    base.update(kw)
    return ContinuousBatchingEngine(cfg, **base)


def _prompts(cfg, n, seed=0, lo=4, hi=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(4, cfg.vocab_size,
                         size=rng.integers(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _ref_greedy(cfg, params, prompt, max_new, publish=None):
    """Whole-sequence reference: prefill + per-token decode_step.

    ``publish``: optional (token_index, new_params) — the decode steps
    from that token boundary on run with the new weights, matching an
    engine that swapped params between horizons.
    """
    toks = jnp.asarray(np.asarray(prompt)[None, :])
    hidden, cache = M.prefill(params, cfg, toks,
                              max_len=len(prompt) + max_new)
    logits = logits_from_hidden(params["embedding"], hidden[:, -1], cfg)
    out = []
    for i in range(max_new):
        if publish is not None and i >= publish[0]:
            params = publish[1]
        t = int(jnp.argmax(logits[0]))
        out.append(t)
        if t == tok.EOS:
            break
        logits, cache = M.decode_step(params, cfg, cache,
                                      jnp.asarray([t]))
    return out


def _run_engine(cfg, params, prompts, max_new, **kw):
    eng = _engine(cfg, **kw)
    rids = [eng.submit(p, max_new=max_new) for p in prompts]
    done = eng.run(params, jax.random.PRNGKey(0))
    assert len(done) == len(prompts)
    by_rid = {r.rid: r.generated for r in done}
    return [by_rid[r] for r in rids], eng


# --------------------------------------------------------------- ssd op
def test_ssd_decode_step_matches_sequential_ref():
    """Iterated O(1) decode steps == the scan over the full sequence."""
    rng = np.random.default_rng(0)
    B, S, nh, hd, ds = 2, 5, 3, 4, 8
    x = jnp.asarray(rng.normal(size=(B, S, nh, hd)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 1.0, size=(B, S, nh)), jnp.float32)
    a_log = jnp.asarray(rng.normal(size=(nh,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, S, ds)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(B, S, ds)), jnp.float32)
    ys_ref, final_ref = ssd_sequential_ref(x, dt, a_log, b, c)
    state = jnp.zeros((B, nh, hd, ds), jnp.float32)
    for t in range(S):
        y, state = ssd_decode_step_ref(state, x[:, t], dt[:, t], a_log,
                                       b[:, t], c[:, t])
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(ys_ref[:, t]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(final_ref),
                               atol=1e-5)


def test_ssd_decode_step_pallas_interpret_matches_ref():
    rng = np.random.default_rng(1)
    B, nh, hd, ds = 3, 2, 8, 16
    state = jnp.asarray(rng.normal(size=(B, nh, hd, ds)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(B, nh, hd)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 1.0, size=(B, nh)), jnp.float32)
    a_log = jnp.asarray(rng.normal(size=(nh,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, ds)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(B, ds)), jnp.float32)
    y_ref, s_ref = ssd_decode_step_ref(state, x, dt, a_log, b, c)
    y_pl, s_pl = ssd_decode_step_pallas(state, x, dt, a_log, b, c,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref),
                               atol=1e-5)


# ------------------------------------------------------- engine parity
def test_mamba2_engine_matches_reference(ssm_setup):
    """4 prompts through 2 slots (forces slot reuse + SSM state re-zero):
    every generation greedy-matches the whole-sequence reference."""
    cfg, params = ssm_setup
    prompts = _prompts(cfg, 4, seed=3)
    got, eng = _run_engine(cfg, params, prompts, max_new=10)
    for p, g in zip(prompts, got):
        assert g == _ref_greedy(cfg, params, p, 10)
    assert eng.allocator.n_free == 33 - 1  # all pages back (minus scratch)
    assert eng.ssm_pool.n_free == 2        # all SSM slots released
    assert eng.supports_prefix_cache is False


def test_hybrid_engine_matches_reference(hybrid_setup):
    """Hybrid (SSM + shared attention) decode: SSM slots and the paged
    KV pool advance together through chunked prefill + fused horizons."""
    cfg, params = hybrid_setup
    prompts = _prompts(cfg, 4, seed=4, lo=3, hi=13)
    got, eng = _run_engine(cfg, params, prompts, max_new=10)
    for p, g in zip(prompts, got):
        assert g == _ref_greedy(cfg, params, p, 10)
    assert eng.allocator.n_free == 33 - 1
    assert eng.ssm_pool.n_free == 2


def test_hybrid_multiple_attn_layers():
    """attn_every=2 over 4 layers: two shared-attention layers, so the
    attention-position indexing into the KV pool (layer ai) is exercised
    beyond ai=0."""
    cfg = dataclasses.replace(get_config("zamba2-1.2b-reduced"),
                              num_layers=4, attn_every=2, dtype="float32")
    assert cfg.block_kinds() == ("ssm", "attn", "ssm", "attn")
    params = M.init_params(cfg, jax.random.PRNGKey(5))
    prompts = _prompts(cfg, 2, seed=5)
    got, _ = _run_engine(cfg, params, prompts, max_new=8)
    for p, g in zip(prompts, got):
        assert g == _ref_greedy(cfg, params, p, 8)


def test_ssm_preemption_and_slot_reuse_no_stale_state(ssm_setup):
    """Preempting a mid-decode sequence and reusing its SSM slot must not
    leak recurrent state into the next occupant."""
    cfg, params = ssm_setup
    eng = _engine(cfg)
    p0, p1 = _prompts(cfg, 2, seed=6)
    eng.submit(p0, max_new=12)
    eng._admit(params)
    while eng.prefilling_slots():
        eng.prefill_step(params)
    key = jax.random.PRNGKey(0)
    key, sub = jax.random.split(key)
    eng.step_horizon(params, sub)          # decode a few tokens
    [slot] = [s for s, r in eng.slots.items() if r is not None]
    victim = eng.release_slot(slot)        # preempt mid-generation
    assert victim is not None
    assert eng.ssm_pool.n_free == 2
    # the freed slot's next occupant decodes from clean state
    rid = eng.submit(p1, max_new=10)
    done = eng.run(params, jax.random.PRNGKey(7))
    by_rid = {r.rid: r.generated for r in done}
    assert by_rid[rid] == _ref_greedy(cfg, params, p1, 10)
    # and the preempted prompt resubmitted fresh regenerates exactly
    rid2 = eng.submit(p0, max_new=12)
    done2 = eng.run(params, jax.random.PRNGKey(8))
    assert {r.rid: r.generated for r in done2}[rid2] == \
        _ref_greedy(cfg, params, p0, 12)


@pytest.mark.parametrize("setup_name", ["ssm_setup", "hybrid_setup"])
def test_publish_resume_parity(setup_name, request):
    """A weight publish between horizons: tokens decoded after the swap
    match a reference that switches params at the same token boundary
    (carried logits from the old weights sample the boundary token)."""
    cfg, params0 = request.getfixturevalue(setup_name)
    params1 = M.init_params(cfg, jax.random.PRNGKey(99))
    H = 4
    prompt = _prompts(cfg, 1, seed=9)[0]
    eng = _engine(cfg, decode_horizon=H)
    rid = eng.submit(prompt, max_new=3 * H)
    eng._admit(params0)
    while eng.prefilling_slots():
        eng.prefill_step(params0)
    key = jax.random.PRNGKey(3)
    done = []
    for i in range(3):
        key, sub = jax.random.split(key)
        done += eng.step_horizon(params0 if i == 0 else params1, sub)
    gen = {r.rid: r.generated for r in done}[rid]
    assert gen == _ref_greedy(cfg, params0, prompt, 3 * H,
                              publish=(H, params1))


# -------------------------------------------------------- bug-sweep units
def test_ssm_slot_pool_lifecycle():
    pool = pc.SSMSlotPool(2)
    pool.map(0)
    with pytest.raises(AssertionError, match="double map"):
        pool.map(0)
    pool.fork(0, 1)
    assert pool.forks == 1 and pool.n_free == 0
    pool.release(1)
    with pytest.raises(AssertionError, match="unmapped"):
        pool.release(1)
    with pytest.raises(AssertionError, match="fork from unmapped"):
        pool.fork(1, 0)
    assert pool.is_mapped(0) and not pool.is_mapped(1)


def test_write_token_routes_unmapped_to_scratch():
    """A write against an unmapped (-1) block-table entry lands in the
    reserved scratch block (last pool block), never in live block 0."""
    cfg = dataclasses.replace(get_config("toy-2m"), dtype="float32")
    state = pc.init_paged_cache(cfg, n_blocks=4, block_size=2, max_seqs=2,
                                max_blocks_per_seq=2)
    # slot 0 mapped to block 0; slot 1 left unmapped with a nonzero len,
    # so its block_idx lookup hits -1
    state = dataclasses.replace(
        state,
        block_tables=jnp.asarray([[0, -1], [-1, -1]], jnp.int32),
        seq_lens=jnp.asarray([0, 1], jnp.int32))
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = jnp.ones((2, kv, hd), jnp.float32)
    out = pc.write_token(state, 0, k, 2 * k, jnp.asarray([0, 1]))
    pool_k = np.asarray(out.pool_k)
    # pool_k is [layer, block, kv, offset, hd]
    assert pool_k[0, 0, :, 0].any()       # slot 0's legit write
    assert pool_k[0, 3, :, 0].any()       # unmapped write -> scratch
    assert not pool_k[0, 0, :, 1].any()   # block 0 slot-1 offset untouched
    assert not pool_k[0, 1].any() and not pool_k[0, 2].any()


def test_pop_admissible_skips_pointless_eviction():
    """Admission must not destroy cached prefixes for a request that
    cannot be admitted even after full eviction."""
    from repro.serving import AdmissionScheduler, SchedulerConfig
    from repro.serving.prefix_cache import RadixPrefixCache
    from repro.rollout.continuous import Request

    class FakeAllocator:
        def __init__(self):
            self.n_free = 2
            self._refs = {}

        def refs(self, b):
            return self._refs.get(b, 0)

        def incref(self, b):
            self._refs[b] = self._refs.get(b, 0) + 1

        def decref(self, b):
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self.n_free += 1

    class FakeEngine:
        def __init__(self):
            self.allocator = FakeAllocator()
            self.prefix_cache = RadixPrefixCache(self.allocator,
                                                 block_size=2)

        def blocks_needed(self, prompt, max_new):
            return -(-(len(prompt) + max_new) // 2)

    eng = FakeEngine()
    # two cache-only blocks (evictable), two free blocks
    eng.prefix_cache.insert([1, 2, 3, 4], [10, 11])
    assert eng.prefix_cache.evictable_count() == 2
    sched = AdmissionScheduler(SchedulerConfig())
    # needs 8 blocks; 2 free + 2 evictable can never cover it
    sched.enqueue(Request(1, np.arange(12), 4))
    assert sched.pop_admissible(0, engine=eng) is None
    assert eng.prefix_cache.n_cached_blocks == 2      # cache untouched
    assert eng.prefix_cache.evicted_blocks == 0
    # a coverable shortfall (needs 3) does evict and admits (fresh
    # scheduler: the giant request above still blocks the FIFO head)
    sched = AdmissionScheduler(SchedulerConfig())
    sched.enqueue(Request(2, np.arange(4), 2))
    got = sched.pop_admissible(0, engine=eng)
    assert got is not None and got[0].rid == 2
    assert eng.allocator.n_free >= 3


def test_evictable_count_pins_ancestors():
    """An in-use leaf pins its whole chain: only fully-reclaimable
    subtrees count toward what eviction could ever free."""
    from repro.rollout.paged_cache import BlockAllocator
    from repro.serving.prefix_cache import RadixPrefixCache

    alloc = BlockAllocator(8)
    cache = RadixPrefixCache(alloc, block_size=2)
    blocks = alloc.alloc(3)                        # sequence-owned, rc=1
    cache.insert([1, 2, 3, 4, 5, 6], blocks)       # chain of 3 nodes, rc=2
    for b in blocks:
        alloc.decref(b)                            # cache now sole owner
    assert cache.evictable_count() == 3
    # a sequence holds the deepest block -> entire chain pinned
    alloc.incref(blocks[2])
    assert cache.evictable_count() == 0
    alloc.decref(blocks[2])
    # holding only the middle block keeps the leaf evictable
    alloc.incref(blocks[1])
    assert cache.evictable_count() == 1


def test_control_plane_skips_prefix_cache_for_ssm(ssm_setup):
    from repro.async_rl.weights import WeightStore
    from repro.serving import (AdmissionScheduler, SchedulerConfig,
                               ServingControlPlane)
    cfg, params = ssm_setup
    eng = _engine(cfg)
    cp = ServingControlPlane(eng, WeightStore(params, 0),
                             AdmissionScheduler(SchedulerConfig()),
                             use_prefix_cache=True)
    assert eng.prefix_cache is None  # gated off: recurrent state is
    #                                  per-slot, prefixes are unshareable
    cp.submit(_prompts(cfg, 1, seed=10)[0], max_new=4)
    key = jax.random.PRNGKey(0)
    for _ in range(12):
        key, sub = jax.random.split(key)
        cp.step(sub)
        if not cp.n_inflight and not len(cp.scheduler):
            break
    assert cp.metrics.completed == 1
