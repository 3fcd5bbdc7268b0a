"""Unit tests for the A-3PO core: alpha schedule, prox approximation,
loss semantics (paper Eqs. 1-4 + Listing 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RLConfig
from repro.core.a3po import (
    alpha_from_staleness,
    compute_prox_logp_approximation,
    staleness,
)
from repro.core.advantages import group_normalized_advantages
from repro.core.algorithms import LossInputs, get_algorithm
from repro.core.objective import coupled_ppo_loss, decoupled_ppo_loss


def algo_loss(name, logp, behav, adv, mask, cfg, **kw):
    """The registered algorithm ``name``'s loss over these tensors."""
    return get_algorithm(name).loss(
        logp, LossInputs(advantages=adv, mask=mask, behav_logp=behav, **kw),
        cfg)


def test_alpha_schedule_paper_eq4():
    d = jnp.array([0.0, 1.0, 2.0, 4.0, 10.0])
    a = alpha_from_staleness(d)
    np.testing.assert_allclose(a, [0.0, 1.0, 0.5, 0.25, 0.1])


def test_staleness_clips_negative():
    v = jnp.array([5, 3, 7])
    d = staleness(v, 5)
    np.testing.assert_allclose(d, [0.0, 2.0, 0.0])


def test_prox_interpolation_listing1():
    """log pi_prox = alpha log pi_b + (1-alpha) log pi_theta."""
    old = jnp.array([[-1.0, -2.0]])
    new = jnp.array([[-3.0, -1.0]])
    # d=2 => alpha=0.5
    prox = compute_prox_logp_approximation(old, new, jnp.array([2]), 4)
    np.testing.assert_allclose(prox, [[-2.0, -1.5]])
    # d=0 => alpha=0 => prox == target logprobs (Listing 1: at staleness 0
    # the target IS the behavior policy, so this recovers standard PPO)
    prox0 = compute_prox_logp_approximation(old, new, jnp.array([4]), 4)
    np.testing.assert_allclose(prox0, new)
    # d=1 => alpha=1 => prox == behavior exactly
    prox1 = compute_prox_logp_approximation(old, new, jnp.array([3]), 4)
    np.testing.assert_allclose(prox1, old)


def test_prox_is_stop_gradient():
    old = jnp.array([[-1.0]])

    def f(logp):
        prox = compute_prox_logp_approximation(old, logp, jnp.array([2]), 4)
        return jnp.sum(prox)

    g = jax.grad(f)(jnp.array([[-2.0]]))
    np.testing.assert_allclose(g, 0.0)


def test_decoupled_equals_coupled_when_prox_is_behav():
    """With pi_prox == pi_behav, Eq. 2 degenerates to Eq. 1."""
    key = jax.random.PRNGKey(0)
    B, T = 4, 8
    logp = -jax.random.uniform(key, (B, T)) * 2
    behav = -jax.random.uniform(jax.random.PRNGKey(1), (B, T)) * 2
    adv = jax.random.normal(jax.random.PRNGKey(2), (B, T))
    mask = jnp.ones((B, T))
    cfg = RLConfig()
    l_coupled, _ = coupled_ppo_loss(logp, behav, adv, mask, cfg)
    l_dec, _ = decoupled_ppo_loss(logp, behav, behav, adv, mask, cfg)
    np.testing.assert_allclose(l_coupled, l_dec, rtol=1e-6)


def test_loglinear_d0_recovers_sync():
    """A-3PO at staleness 0 == standard PPO (paper §3): at d=0 the target
    equals the behavior policy (logp == behav on the first minibatch), and
    alpha=0 makes prox == logp == behav — the coupled loss."""
    key = jax.random.PRNGKey(0)
    B, T = 8, 6
    logp = -jax.random.uniform(key, (B, T)) * 2
    behav = logp  # d=0: data was just generated by this exact policy
    adv = jax.random.normal(jax.random.PRNGKey(2), (B, T))
    mask = jnp.ones((B, T))
    cfg = RLConfig()
    versions = jnp.full((B,), 7, jnp.int32)
    l_ll, _ = algo_loss("a3po", logp, behav, adv, mask, cfg,
                          versions=versions, current_version=7)
    l_sync, _ = algo_loss("sync", logp, behav, adv, mask, cfg)
    np.testing.assert_allclose(l_ll, l_sync, rtol=1e-6)


def test_loss_gradient_direction():
    """Positive advantage => gradient pushes logp up."""
    cfg = RLConfig()
    behav = jnp.array([[-1.0]])
    adv = jnp.array([[1.0]])
    mask = jnp.ones((1, 1))

    def f(logp):
        l, _ = algo_loss("a3po", logp, behav, adv, mask, cfg,
                           versions=jnp.array([2]), current_version=4)
        return l

    g = jax.grad(f)(jnp.array([[-1.0]]))
    assert g[0, 0] < 0  # minimizing loss raises logp


def test_clipping_activates():
    cfg = RLConfig(clip_eps=0.2)
    # ratio >> 1+eps with positive advantage -> clipped
    logp = jnp.array([[0.0]])
    behav = jnp.array([[-2.0]])
    adv = jnp.array([[1.0]])
    mask = jnp.ones((1, 1))
    _, m = algo_loss("sync", logp, behav, adv, mask, cfg)
    assert float(m["clipped_frac"]) == 1.0


def test_group_normalized_advantages():
    r = jnp.array([1.0, 0.0, 0.0, 1.0, 1.0, 1.0, 1.0, 1.0])
    adv = group_normalized_advantages(r, group_size=4)
    g0 = adv[:4]
    assert abs(float(g0.mean())) < 1e-6
    assert float(g0[0]) > 0 > float(g0[1])
    # degenerate group (all equal) -> zero advantage, no NaN
    np.testing.assert_allclose(adv[4:], 0.0, atol=1e-4)


def test_metrics_shapes():
    cfg = RLConfig()
    B, T = 4, 4
    logp = behav = jnp.full((B, T), -1.0)
    adv = jnp.ones((B, T))
    mask = jnp.ones((B, T))
    _, m = algo_loss("a3po", logp, behav, adv, mask, cfg,
                       versions=jnp.ones((B,), jnp.int32),
                       current_version=3)
    for k in ("iw_max", "iw_min", "clipped_tokens", "ratio_mean"):
        assert np.isfinite(float(m[k])), k


@pytest.mark.parametrize("schedule,expected", [
    ("exp", 0.25), ("clipped", 0.5), ("const", 0.5)])
def test_alpha_schedules_beyond_paper(schedule, expected):
    cfg = RLConfig(alpha_gamma=0.5, alpha_clip=(0.1, 1.0), alpha_const=0.5)
    a = alpha_from_staleness(jnp.array([2.0]), cfg, schedule)
    np.testing.assert_allclose(a, [expected])
    # all schedules agree at d=0
    np.testing.assert_allclose(
        alpha_from_staleness(jnp.array([0.0]), cfg, schedule), [0.0])


def test_kl_adaptive_prox_beyond_paper():
    """KL-adaptive alpha: anchor sits at a fixed KL distance from the
    target; sandwich property still holds (alpha in [0,1])."""
    from repro.core.a3po import compute_prox_logp_kl_adaptive
    key = jax.random.PRNGKey(0)
    B, T = 4, 16
    behav = -jax.random.uniform(key, (B, T)) * 3
    # vary divergence per sequence: row 0 tiny drift, row 3 huge drift
    scale = jnp.array([0.001, 0.05, 0.5, 3.0])[:, None]
    logp = behav + scale * jax.random.normal(jax.random.PRNGKey(1), (B, T))
    mask = jnp.ones((B, T))
    prox = compute_prox_logp_kl_adaptive(behav, logp, mask, target_kl=0.05)
    # sandwich: between behav and logp everywhere
    assert bool(jnp.all(prox >= jnp.minimum(behav, logp) - 1e-5))
    assert bool(jnp.all(prox <= jnp.maximum(behav, logp) + 1e-5))
    # tiny drift -> alpha clipped at 1 -> anchor == behavior;
    # huge drift -> alpha small -> anchor near target
    np.testing.assert_allclose(prox[0], behav[0], atol=1e-5)
    d_to_target = jnp.abs(prox[3] - logp[3]).mean()
    d_to_behav = jnp.abs(prox[3] - behav[3]).mean()
    assert float(d_to_target) < float(d_to_behav)
    # gradient does not flow through the anchor
    g = jax.grad(lambda lp: compute_prox_logp_kl_adaptive(
        behav, lp, mask).sum())(logp)
    np.testing.assert_allclose(g, 0.0)


def test_policy_loss_kl_adaptive_dispatch():
    cfg = RLConfig(alpha_schedule="kl_adaptive")
    B, T = 4, 8
    logp = -jax.random.uniform(jax.random.PRNGKey(0), (B, T)) * 2
    behav = logp + 0.1
    adv = jnp.ones((B, T))
    mask = jnp.ones((B, T))
    loss, m = algo_loss("a3po", logp, behav, adv, mask, cfg)
    assert np.isfinite(float(loss))
