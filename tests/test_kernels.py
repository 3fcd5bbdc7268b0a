"""Per-kernel correctness sweeps: Pallas (interpret=True) vs pure-jnp
oracle across shapes and dtypes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.a3po_loss.kernel import a3po_loss_pallas
from repro.kernels.a3po_loss.ref import a3po_loss_ref
from repro.kernels.flash_attn.kernel import flash_attention_pallas
from repro.kernels.flash_attn.ref import flash_attention_ref
from repro.kernels.logprob.kernel import logprob_stats_pallas
from repro.kernels.logprob.ref import token_logprob_entropy_ref
from repro.kernels.ssd.kernel import ssd_intra_chunk_pallas
from repro.kernels.ssd.ops import ssd_scan
from repro.kernels.ssd.ref import ssd_sequential_ref


# ------------------------------------------------------------------ logprob
@pytest.mark.parametrize("T,d,V", [
    (16, 32, 50), (300, 130, 1000), (64, 512, 513), (7, 48, 22),
    (128, 64, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_logprob_kernel_vs_ref(T, d, V, dtype):
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (T, d), dtype)
    w = (jax.random.normal(jax.random.PRNGKey(1), (d, V), jnp.float32)
         * 0.05).astype(dtype)
    t = jax.random.randint(jax.random.PRNGKey(2), (T,), 0, V)
    lp_k, en_k, _ = logprob_stats_pallas(h, w, t, bt=64, bv=128, bd=64,
                                         interpret=True)
    lp_r, en_r = token_logprob_entropy_ref(h, w, t)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(lp_k, lp_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(en_k, en_r, rtol=tol, atol=tol)


_COTANGENTS = {"logp": (1.0, 0.0), "entropy": (0.0, 1.0), "both": (1.0, 0.5)}


def _logprob_problem(T, d, V, dtype):
    h = jax.random.normal(jax.random.PRNGKey(0), (T, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, V), jnp.float32) * 0.2
    t = jax.random.randint(jax.random.PRNGKey(2), (T,), 0, V)
    # per-token cotangent weights, so a row-mixing bug cannot cancel out
    g = jax.random.uniform(jax.random.PRNGKey(3), (2, T), minval=0.5,
                           maxval=1.5)
    return h.astype(dtype), w.astype(dtype), t, g


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)


@pytest.mark.parametrize("T,d,V", [(16, 32, 50), (40, 64, 300),
                                   (24, 128, 4099)])
@pytest.mark.parametrize("cot", sorted(_COTANGENTS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_logprob_vjp_vs_ref_grad(T, d, V, cot, dtype):
    """The kernel path's custom_vjp (interpret-mode forward + blocked
    analytic backward) matches jax.grad of the jnp oracle w.r.t. hidden
    and w, for the logp cotangent, the entropy cotangent, and both."""
    from repro.kernels.logprob.ops import token_logprob_entropy
    h, w, t, g = _logprob_problem(T, d, V, dtype)
    c_lp, c_en = _COTANGENTS[cot]

    def objective(fn):
        def f(h, w):
            lp, en = fn(h, w, t)
            return jnp.sum(c_lp * g[0] * lp + c_en * g[1] * en)
        return f

    kernel = functools.partial(token_logprob_entropy, interpret=True)
    dh_k, dw_k = jax.grad(objective(kernel), argnums=(0, 1))(h, w)
    dh_r, dw_r = jax.grad(objective(token_logprob_entropy_ref),
                          argnums=(0, 1))(h, w)
    assert dh_k.dtype == h.dtype and dw_k.dtype == w.dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert _rel_err(dh_k, dh_r) < tol
    assert _rel_err(dw_k, dw_r) < tol


@pytest.mark.parametrize("V,bv", [(50, 16), (64, 16), (300, 128),
                                  (130, 200)])
def test_logprob_blocked_bwd_vs_ref_grad(V, bv):
    """The vocab-blocked backward is exact for any block size, including
    a last block shifted back over columns an earlier block covered."""
    from repro.kernels.logprob.kernel import token_logprob_entropy_bwd
    h, w, t, g = _logprob_problem(12, 32, V, jnp.float32)
    lp, en = token_logprob_entropy_ref(h, w, t)
    logz = jax.scipy.special.logsumexp(h @ w, axis=-1)
    dh, dw = token_logprob_entropy_bwd(h, w, t, logz, en, g[0], g[1],
                                       bv=bv)
    _, vjp = jax.vjp(lambda h, w: token_logprob_entropy_ref(h, w, t), h, w)
    dh_r, dw_r = vjp((g[0], g[1]))
    np.testing.assert_allclose(dh, dh_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dw, dw_r, rtol=1e-5, atol=1e-6)


def test_logprob_is_valid_distribution():
    """exp(logp) must be <= 1 and entropy >= 0."""
    key = jax.random.PRNGKey(3)
    h = jax.random.normal(key, (32, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (16, 97), jnp.float32)
    t = jax.random.randint(jax.random.PRNGKey(5), (32,), 0, 97)
    lp, en, _ = logprob_stats_pallas(h, w, t, interpret=True)
    assert np.all(np.asarray(lp) <= 1e-5)
    assert np.all(np.asarray(en) >= -1e-5)


# --------------------------------------------------------------- flash attn
@pytest.mark.parametrize("B,H,KV,S,hd,window", [
    (2, 4, 2, 64, 32, None),   # GQA
    (1, 4, 4, 128, 16, None),  # MHA
    (2, 2, 1, 64, 32, None),   # MQA
    (2, 2, 1, 64, 32, 32),     # sliding window
    (1, 8, 2, 96, 64, None),   # non-power-of-two seq (96 = 3*32)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(B, H, KV, S, hd, window, dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, S, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, KV, S, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, KV, S, hd), dtype)
    o_k = flash_attention_pallas(q, k, v, bq=32, bk=32, window=window,
                                 interpret=True)
    o_r = flash_attention_ref(q, k, v, window=window)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------- ssd
@pytest.mark.parametrize("B,S,nh,hd,ds,cs", [
    (2, 64, 4, 16, 8, 16), (1, 48, 2, 8, 4, 16), (2, 32, 1, 4, 4, 32),
    (1, 128, 2, 32, 16, 32)])
def test_ssd_kernel_vs_sequential(B, S, nh, hd, ds, cs):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, S, nh, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3),
                                           (B, S, nh)))
    a_log = jnp.log(jnp.linspace(1.0, 8.0, nh))
    b = jax.random.normal(jax.random.PRNGKey(4), (B, S, ds)) * 0.3
    c = jax.random.normal(jax.random.PRNGKey(5), (B, S, ds)) * 0.3
    y_k, f_k = ssd_scan(x, dt, a_log, b, c, chunk=cs, interpret=True)
    y_r, f_r = ssd_sequential_ref(x, dt, a_log, b, c)
    np.testing.assert_allclose(y_k, y_r, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(f_k, f_r, rtol=1e-3, atol=1e-3)


def test_ssd_initial_state_continuity():
    """Splitting a sequence at a chunk boundary and carrying the state must
    equal one contiguous scan (the decode-handoff invariant)."""
    key = jax.random.PRNGKey(0)
    B, S, nh, hd, ds = 1, 64, 2, 8, 4
    x = jax.random.normal(key, (B, S, nh, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (B, S, nh)))
    a_log = jnp.zeros((nh,))
    b = jax.random.normal(jax.random.PRNGKey(2), (B, S, ds)) * 0.3
    c = jax.random.normal(jax.random.PRNGKey(3), (B, S, ds)) * 0.3
    y_full, f_full = ssd_sequential_ref(x, dt, a_log, b, c)
    y1, f1 = ssd_sequential_ref(x[:, :32], dt[:, :32], a_log, b[:, :32],
                                c[:, :32])
    y2, f2 = ssd_sequential_ref(x[:, 32:], dt[:, 32:], a_log, b[:, 32:],
                                c[:, 32:], initial_state=f1)
    np.testing.assert_allclose(np.concatenate([y1, y2], 1), y_full,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(f2, f_full, rtol=1e-4, atol=1e-4)


def test_ssd_intra_chunk_outputs():
    """Kernel intra-chunk output matches a one-chunk sequential scan."""
    key = jax.random.PRNGKey(7)
    B, S, nh, hd, ds = 1, 16, 2, 8, 4
    x = jax.random.normal(key, (B, S, nh, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(8),
                                           (B, S, nh)))
    a_log = jnp.log(jnp.array([1.0, 2.0]))
    b = jax.random.normal(jax.random.PRNGKey(9), (B, S, ds)) * 0.3
    c = jax.random.normal(jax.random.PRNGKey(10), (B, S, ds)) * 0.3
    la = dt * (-jnp.exp(a_log))
    xdt = x * dt[..., None]
    y, s_local, cdec = ssd_intra_chunk_pallas(xdt, la, b, c, chunk=16,
                                              interpret=True)
    y_r, f_r = ssd_sequential_ref(x, dt, a_log, b, c)
    np.testing.assert_allclose(y[:, :, 0], y_r[:, :, 0], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(s_local[:, 0], f_r, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- a3po loss
@pytest.mark.parametrize("T", [64, 1000, 4096])
def test_a3po_loss_kernel_vs_ref(T):
    key = jax.random.PRNGKey(0)
    lp = -jax.random.uniform(key, (T,)) * 3
    bl = -jax.random.uniform(jax.random.PRNGKey(6), (T,)) * 3
    al = jax.random.uniform(jax.random.PRNGKey(7), (T,))
    adv = jax.random.normal(jax.random.PRNGKey(8), (T,))
    mask = (jax.random.uniform(jax.random.PRNGKey(9), (T,)) > 0.3
            ).astype(jnp.float32)
    l_k, c_k, iw_k, r_k = a3po_loss_pallas(lp, bl, al, adv, mask, bt=128,
                                           interpret=True)
    l_r, c_r, iw_r, r_r = a3po_loss_ref(lp, bl, al, adv, mask, clip_eps=0.2,
                                        iw_cap=5.0)
    np.testing.assert_allclose(l_k, l_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(c_k, c_r)
    np.testing.assert_allclose(iw_k, iw_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(r_k, r_r, rtol=2e-5, atol=2e-5)


def test_a3po_fused_matches_modular_loss():
    """The fused kernel must agree with core.objective.decoupled_ppo_loss."""
    from repro.configs.base import RLConfig
    from repro.core.a3po import compute_prox_logp_approximation
    from repro.core.objective import decoupled_ppo_loss

    key = jax.random.PRNGKey(0)
    B, T = 4, 32
    cfg = RLConfig()
    logp = -jax.random.uniform(key, (B, T)) * 3
    behav = -jax.random.uniform(jax.random.PRNGKey(1), (B, T)) * 3
    adv = jax.random.normal(jax.random.PRNGKey(2), (B, T))
    mask = jnp.ones((B, T))
    versions = jnp.array([0, 1, 2, 3])
    prox = compute_prox_logp_approximation(behav, logp, versions, 3, cfg)
    l_mod, m = decoupled_ppo_loss(logp, behav, prox, adv, mask, cfg)

    from repro.core.a3po import alpha_from_staleness, staleness
    alpha = jnp.broadcast_to(
        alpha_from_staleness(staleness(versions, 3), cfg)[:, None], (B, T))
    l_tok, clip_tok, iw_tok, _ = a3po_loss_pallas(
        logp.reshape(-1), behav.reshape(-1), alpha.reshape(-1),
        adv.reshape(-1), mask.reshape(-1), clip_eps=cfg.clip_eps,
        iw_cap=cfg.behav_weight_cap, interpret=True)
    np.testing.assert_allclose(l_tok.sum() / mask.sum(), l_mod,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(clip_tok.sum(), m["clipped_tokens"])
    np.testing.assert_allclose(iw_tok.max(), m["iw_max"], rtol=1e-6)


# --------------------------------------------------------------- decode attn
@pytest.mark.parametrize("B,H,KV,L,hd,bk", [
    (2, 4, 2, 64, 32, 32), (1, 8, 1, 128, 16, 64), (3, 4, 4, 96, 32, 32)])
def test_decode_attention_kernel_vs_ref(B, H, KV, L, hd, bk):
    from repro.kernels.decode_attn.kernel import decode_attention_pallas
    from repro.models.attention import decode_attention as ref
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, hd), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, L, KV, hd))
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, L, KV, hd))
    lengths = jax.random.randint(jax.random.PRNGKey(3), (B,), 1, L + 1)
    o_k = decode_attention_pallas(q, kc, vc, lengths, bk=bk, interpret=True)
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    o_r = ref(q, kc, vc, valid)
    np.testing.assert_allclose(o_k, o_r, rtol=2e-4, atol=2e-4)


def _paged_pool(rng_seed, S, KV, n_blocks, bs, mb, hd):
    """Random pool + disjoint per-sequence block tables + lengths."""
    rng = np.random.default_rng(rng_seed)
    pool_k = jax.random.normal(jax.random.PRNGKey(1),
                               (n_blocks, KV, bs, hd), jnp.float32)
    pool_v = jax.random.normal(jax.random.PRNGKey(2),
                               (n_blocks, KV, bs, hd), jnp.float32)
    tables = rng.permutation(n_blocks)[: S * mb].reshape(S, mb)
    lengths = rng.integers(1, mb * bs + 1, size=S)
    # entries past the mapped region are -1, as in the serving engine
    for s in range(S):
        tables[s, -(-int(lengths[s]) // bs):] = -1
    return (pool_k, pool_v, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("S,H,KV,n_blocks,bs,mb,hd", [
    (2, 4, 2, 16, 8, 4, 32),    # GQA
    (3, 4, 4, 32, 16, 2, 16),   # MHA
    (1, 8, 1, 8, 4, 6, 32),     # MQA
])
def test_paged_decode_attention_kernel_vs_ref(S, H, KV, n_blocks, bs, mb,
                                              hd):
    """Paged Pallas kernel (block-table gather inside the kernel) matches
    the XLA-gather oracle over shuffled, partially-mapped block tables."""
    from repro.kernels.decode_attn.paged_kernel import (
        paged_decode_attention_pallas,
    )
    from repro.kernels.decode_attn.ref import paged_decode_attention_ref
    pool_k, pool_v, tables, lengths = _paged_pool(0, S, KV, n_blocks, bs,
                                                  mb, hd)
    q = jax.random.normal(jax.random.PRNGKey(0), (S, H, hd), jnp.float32)
    o_k = paged_decode_attention_pallas(q, pool_k, pool_v, tables, lengths,
                                        interpret=True)
    o_r = paged_decode_attention_ref(q, pool_k, pool_v, tables, lengths)
    np.testing.assert_allclose(o_k, o_r, rtol=2e-4, atol=2e-4)


def test_paged_decode_attention_op_dispatch():
    """The op's non-TPU path equals both oracles (shared kernel coverage
    between the fused horizon and the single-step fallback)."""
    from repro.kernels.decode_attn.ops import paged_decode_attention_op
    from repro.kernels.decode_attn.ref import paged_decode_attention_ref
    pool_k, pool_v, tables, lengths = _paged_pool(1, 2, 2, 16, 8, 3, 16)
    q = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 16), jnp.float32)
    o_op = paged_decode_attention_op(q, pool_k, pool_v, tables, lengths)
    o_ref = paged_decode_attention_ref(q, pool_k, pool_v, tables, lengths)
    np.testing.assert_allclose(o_op, o_ref, rtol=1e-6, atol=1e-6)
    o_int = paged_decode_attention_op(q, pool_k, pool_v, tables, lengths,
                                      interpret=True)
    np.testing.assert_allclose(o_int, o_ref, rtol=2e-4, atol=2e-4)


# -------------------------------------------------------------- prefill attn
def _prefill_chunk(rng_seed, C, S, KV, n_blocks, bs, mb, hd, *,
                   pad_rows=0):
    """A packed prefill chunk over ``_paged_pool``: rows round-robin the
    segments, each taking that segment's next positions; trailing rows
    are padding (seg -1)."""
    pool_k, pool_v, tables, lengths = _paged_pool(rng_seed, S, KV, n_blocks,
                                                  bs, mb, hd)
    rng = np.random.default_rng(rng_seed + 100)
    seg = np.full((C,), -1, np.int32)
    pos = np.zeros((C,), np.int32)
    # each segment contributes a contiguous run of its last positions
    # (kv_lens[s] keys resident -> rows at positions < lengths[s])
    cursor = {s: max(int(lengths[s]) - rng.integers(1, 4), 0)
              for s in range(S)}
    for i in range(C - pad_rows):
        s = i % S
        if cursor[s] >= int(lengths[s]):
            continue  # segment exhausted; leave row as padding
        seg[i] = s
        pos[i] = cursor[s]
        cursor[s] += 1
    q = jax.random.normal(jax.random.PRNGKey(rng_seed + 7), (C, 8, hd),
                          jnp.float32)
    return (q, pool_k, pool_v, tables, jnp.asarray(seg), jnp.asarray(pos),
            lengths)


@pytest.mark.parametrize("C,S,KV,n_blocks,bs,mb,hd", [
    (8, 2, 2, 16, 8, 4, 32),    # GQA, packed 2 segments
    (16, 3, 4, 32, 16, 2, 16),  # MHA, 3-way packing
    (4, 1, 1, 8, 4, 6, 32),     # MQA, single segment
])
def test_paged_prefill_attention_kernel_vs_ref(C, S, KV, n_blocks, bs, mb,
                                               hd):
    """Chunked prefill Pallas kernel (block-table walk + per-row causal
    segment mask) matches the per-row decode-replay oracle, padding rows
    emit zeros."""
    from repro.kernels.prefill_attn.kernel import (
        paged_prefill_attention_pallas,
    )
    from repro.kernels.prefill_attn.ref import paged_prefill_attention_ref
    q, pool_k, pool_v, tables, seg, pos, lengths = _prefill_chunk(
        0, C, S, KV, n_blocks, bs, mb, hd, pad_rows=1)
    o_k = paged_prefill_attention_pallas(q, pool_k, pool_v, tables, seg,
                                         pos, lengths, interpret=True)
    o_r = paged_prefill_attention_ref(q, pool_k, pool_v, tables, seg, pos)
    np.testing.assert_allclose(o_k, o_r, rtol=2e-4, atol=2e-4)
    pad = np.asarray(seg) < 0
    assert pad.any()
    assert np.all(np.asarray(o_k)[pad] == 0.0)


def test_paged_prefill_attention_matches_decode_per_row():
    """Each chunk row must equal a single decode query at its position —
    the invariant that makes the chunk lane a drop-in for per-token
    suffix replay."""
    from repro.kernels.decode_attn.ref import paged_decode_attention_ref
    from repro.kernels.prefill_attn.ref import paged_prefill_attention_ref
    q, pool_k, pool_v, tables, seg, pos, _ = _prefill_chunk(
        2, 8, 2, 2, 16, 8, 4, 32)
    o = paged_prefill_attention_ref(q, pool_k, pool_v, tables, seg, pos)
    for i in range(8):
        s = int(seg[i])
        if s < 0:
            continue
        o_dec = paged_decode_attention_ref(
            q[i: i + 1], pool_k, pool_v, tables[s: s + 1],
            pos[i: i + 1] + 1)
        np.testing.assert_array_equal(np.asarray(o[i]),
                                      np.asarray(o_dec[0]))


def test_paged_prefill_attention_op_dispatch():
    """Op non-TPU path equals the oracle; interpret path within kernel
    tolerance."""
    from repro.kernels.prefill_attn.ops import paged_prefill_attention_op
    from repro.kernels.prefill_attn.ref import paged_prefill_attention_ref
    q, pool_k, pool_v, tables, seg, pos, lengths = _prefill_chunk(
        1, 8, 2, 2, 16, 8, 3, 16)
    o_op = paged_prefill_attention_op(q, pool_k, pool_v, tables, seg, pos,
                                      lengths)
    o_ref = paged_prefill_attention_ref(q, pool_k, pool_v, tables, seg, pos)
    np.testing.assert_allclose(o_op, o_ref, rtol=1e-6, atol=1e-6)
    o_int = paged_prefill_attention_op(q, pool_k, pool_v, tables, seg, pos,
                                       lengths, interpret=True)
    np.testing.assert_allclose(o_int, o_ref, rtol=2e-4, atol=2e-4)
