"""Property-based tests (hypothesis) for the paper's Theorem 1 invariants:

1. Sandwich: min(pi_b, pi_t) <= pi_prox <= max(pi_b, pi_t)
2. Contractive closed form: pi_t/pi_prox == (pi_t/pi_b)^alpha
3. Variance contraction: Var[w^alpha] decreases as staleness grows
plus system invariants (masking, group normalization).
"""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs.base import RLConfig
from repro.core.a3po import alpha_from_staleness, compute_prox_logp_approximation
from repro.core.advantages import group_normalized_advantages
from repro.core.algorithms import LossInputs, get_algorithm

logp_arrays = st.lists(
    st.floats(min_value=-20.0, max_value=-1e-3), min_size=1, max_size=32)
staleness_vals = st.integers(min_value=0, max_value=100)


@settings(max_examples=60, deadline=None)
@given(logp_arrays, logp_arrays, staleness_vals)
def test_sandwich_property(behav, target, d):
    """Theorem 1.1: pi_prox lies between pi_behav and pi_theta."""
    n = min(len(behav), len(target))
    b = jnp.array(behav[:n])[None, :]
    t = jnp.array(target[:n])[None, :]
    prox = compute_prox_logp_approximation(
        b, t, jnp.array([0]), d)
    lo = jnp.minimum(b, t) - 1e-5
    hi = jnp.maximum(b, t) + 1e-5
    assert bool(jnp.all(prox >= lo)), (prox, lo)
    assert bool(jnp.all(prox <= hi)), (prox, hi)


@settings(max_examples=60, deadline=None)
@given(logp_arrays, logp_arrays, st.integers(min_value=1, max_value=50))
def test_contractive_closed_form(behav, target, d):
    """Theorem 1.2: r = pi_t/pi_prox = w^alpha."""
    n = min(len(behav), len(target))
    b = np.array(behav[:n])
    t = np.array(target[:n])
    prox = np.asarray(compute_prox_logp_approximation(
        jnp.array(b)[None], jnp.array(t)[None], jnp.array([0]), d))[0]
    alpha = 1.0 / d
    r = np.exp(t - prox)
    w_alpha = np.exp(alpha * (t - b))
    np.testing.assert_allclose(r, w_alpha, rtol=1e-5, atol=1e-6)


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_ratio_tends_to_one_with_staleness(seed):
    """Theorem 1.2 limit: r -> 1 as d -> infinity."""
    rng = np.random.default_rng(seed)
    b = -rng.uniform(0.1, 10.0, size=16)
    t = -rng.uniform(0.1, 10.0, size=16)
    for d_small, d_big in [(1, 10), (10, 1000)]:
        r_small = np.exp(
            (t - b) * float(alpha_from_staleness(jnp.array(float(d_small)))))
        r_big = np.exp(
            (t - b) * float(alpha_from_staleness(jnp.array(float(d_big)))))
        assert np.all(np.abs(np.log(r_big)) <= np.abs(np.log(r_small)) + 1e-9)


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_variance_contraction(seed):
    """Theorem 1.2: Var[w^alpha] vanishes as d grows."""
    rng = np.random.default_rng(seed)
    w = np.exp(rng.normal(0, 1.0, size=512))  # lognormal IS weights
    variances = []
    for d in [1, 2, 5, 20, 100]:
        alpha = 1.0 / d
        variances.append(np.var(w ** alpha))
    assert all(v2 <= v1 + 1e-9
               for v1, v2 in zip(variances, variances[1:])), variances
    assert variances[-1] < 0.05 * variances[0] + 1e-9


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=1, max_value=8), st.integers(0, 10_000))
def test_alpha_monotone_decreasing(n, seed):
    """Eq. 4: alpha monotonically decreases in d (fresher data weighted
    more toward behavior)."""
    d = jnp.arange(1, n + 1, dtype=jnp.float32)
    a = np.asarray(alpha_from_staleness(d))
    assert np.all(np.diff(a) <= 1e-9)
    assert np.all((a > 0) & (a <= 1.0))


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=6), st.integers(0, 10_000))
def test_group_norm_invariants(groups, seed):
    rng = np.random.default_rng(seed)
    g = 4
    r = rng.uniform(0, 1, size=groups * g).astype(np.float32)
    adv = np.asarray(group_normalized_advantages(jnp.array(r), g))
    adv_g = adv.reshape(groups, g)
    np.testing.assert_allclose(adv_g.mean(axis=1), 0.0, atol=1e-5)
    assert np.all(np.isfinite(adv))


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000))
def test_masked_tokens_have_no_gradient_influence(seed):
    """Loss is invariant to values at masked positions."""
    import jax
    rng = np.random.default_rng(seed)
    B, T = 4, 8
    cfg = RLConfig()
    mask = (rng.uniform(size=(B, T)) > 0.5).astype(np.float32)
    behav = jnp.array(-rng.uniform(0.1, 5, (B, T)), jnp.float32)
    adv = jnp.array(rng.normal(size=(B, T)), jnp.float32)
    logp = jnp.array(-rng.uniform(0.1, 5, (B, T)), jnp.float32)
    garbage = jnp.where(mask > 0, logp, logp * 7 - 3)
    vs = jnp.array(rng.integers(0, 3, B), jnp.int32)
    a3po = get_algorithm("a3po")
    batch = LossInputs(advantages=adv * mask, mask=jnp.array(mask),
                       behav_logp=behav, versions=vs, current_version=5)
    l1, _ = a3po.loss(logp, batch, cfg)
    l2, _ = a3po.loss(garbage, batch, cfg)
    if mask.sum() > 0:
        np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-6)
