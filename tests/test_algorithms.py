"""Algorithm-registry tests: built-in coverage and aliases, requires-flags
contracts, parity of Algorithm objects with the PR-2 scan engine, custom
registration end-to-end, and the kl_coef wiring."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RLConfig
from repro.configs.registry import get_config
from repro.core.algorithms import (
    A3PO,
    BUILTINS,
    Algorithm,
    LossInputs,
    available,
    get_algorithm,
    register,
    registry_table,
    resolve_algorithm,
    unregister,
)
from repro.core.objective import (
    apply_regularizers,
    common_metrics,
    masked_mean,
)
from repro.training.trainer import Trainer, TrainState

from test_training_engine import (
    PARITY_KEYS,
    make_batch,
    reference_loop_step,
)


@pytest.fixture(scope="module")
def toy():
    return dataclasses.replace(get_config("toy-2m"), dtype="float32")


@pytest.fixture(scope="module")
def rl():
    return RLConfig(group_size=4, num_minibatches=2, learning_rate=3e-4)


def rand_loss_inputs(B=4, T=10, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    logp = -jax.random.uniform(ks[0], (B, T)) * 2
    behav = logp + 0.2 * jax.random.normal(ks[1], (B, T))
    adv = jax.random.normal(ks[2], (B, T))
    mask = (jax.random.uniform(ks[3], (B, T)) > 0.2).astype(jnp.float32)
    versions = jnp.arange(B, dtype=jnp.int32)
    return logp, LossInputs(advantages=adv, mask=mask, behav_logp=behav,
                            versions=versions, current_version=B)


# ----------------------------------------------------------------- registry
def test_registry_builtins_and_aliases():
    assert set(available()) == set(BUILTINS)
    assert get_algorithm("loglinear").name == "a3po"
    assert isinstance(get_algorithm("loglinear"), A3PO)
    with pytest.raises(ValueError, match="unknown algorithm"):
        get_algorithm("nope")
    # frozen instances hash/compare by value -> stable jit-static keys
    assert hash(get_algorithm("a3po")) == hash(A3PO())
    assert get_algorithm("grpo_mu") == get_algorithm("grpo_mu")
    assert A3PO(schedule="exp") != A3PO()
    names = {r["name"] for r in registry_table()}
    assert names == set(BUILTINS)


def test_requires_flags_contract():
    """`recompute` is the only built-in that triggers the extra prox
    forward pass; `asympo` the only one that needs no behavior logps."""
    prox_users = [n for n in BUILTINS
                  if get_algorithm(n).needs_prox_forward]
    assert prox_users == ["recompute"]
    no_behav = [n for n in BUILTINS
                if not get_algorithm(n).needs_behav_logp]
    assert no_behav == ["asympo"]
    on_policy = [n for n in BUILTINS if get_algorithm(n).on_policy]
    assert on_policy == ["sync"]
    version_users = {n for n in BUILTINS
                     if get_algorithm(n).needs_versions}
    assert version_users == {"a3po", "grpo_mu"}


def test_resolve_algorithm_fallbacks():
    a = A3PO(schedule="exp")
    assert resolve_algorithm(a) is a
    assert resolve_algorithm("sync").name == "sync"
    # nested per-algorithm config in RLConfig, else the paper default
    assert resolve_algorithm(None, RLConfig(algo=a)) is a
    assert resolve_algorithm(None, RLConfig()).name == "a3po"
    assert resolve_algorithm(None, None).name == "a3po"
    with pytest.raises(TypeError):
        resolve_algorithm(42)


# ------------------------------------------------- scan-engine parity pins
@pytest.mark.parametrize("name", ["sync", "recompute", "a3po"])
def test_algorithm_objects_pin_scan_engine(toy, rl, name):
    """Algorithm *objects* reproduce the PR-2 scan-engine outputs that the
    seed loop oracle pins (same oracle as the method-string parity test)."""
    batch = make_batch(False, seed=1)
    legacy = {"a3po": "loglinear"}.get(name, name)
    trainer = Trainer(toy, rl, get_algorithm(name))
    s_scan = trainer.init_state(jax.random.PRNGKey(3))
    s_ref = trainer.init_state(jax.random.PRNGKey(3))
    s_scan = TrainState(s_scan.params, s_scan.opt, jnp.asarray(2, jnp.int32))
    s_ref = TrainState(s_ref.params, s_ref.opt, jnp.asarray(2, jnp.int32))

    s_ref, m_ref = reference_loop_step(toy, rl, legacy, s_ref, batch)
    s_scan, m_scan = trainer.step(s_scan, batch)
    for k in PARITY_KEYS:
        np.testing.assert_allclose(m_scan[k], m_ref[k], rtol=2e-4,
                                   atol=1e-5, err_msg=k)
    for a, b in zip(jax.tree.leaves(s_scan.params),
                    jax.tree.leaves(s_ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)


def test_flags_gate_scan_operands(toy, rl):
    """Tensors an algorithm does not require never enter the compiled
    minibatch scan: asympo trains through NaN behavior logps."""
    batch = make_batch(False, seed=2)
    poisoned = dataclasses.replace(
        batch, behav_logp=jnp.full_like(batch.behav_logp, jnp.nan))
    tr = Trainer(toy, rl, "asympo")
    state = tr.init_state(jax.random.PRNGKey(0))
    state, m = tr.step(state, poisoned)
    assert np.isfinite(m["loss"])
    assert m["host_syncs"] == 1.0  # and no prox forward pass
    # a behav-requiring algorithm does propagate the NaNs (sanity check
    # that the poisoning is real)
    tr2 = Trainer(toy, rl, "grpo_mu")
    s2 = tr2.init_state(jax.random.PRNGKey(0))
    _, m2 = tr2.step(s2, poisoned)
    assert not np.isfinite(m2["loss"])


# --------------------------------------------------- custom registration
def test_custom_algorithm_end_to_end(toy, rl):
    """A one-class plugin registers and trains through the full engine."""

    @register("test_reinforce")
    @dataclasses.dataclass(frozen=True)
    class TestReinforce(Algorithm):
        adv_cap: float = 5.0
        needs_behav_logp = False
        needs_versions = False

        def loss(self, logp, batch, cfg):
            logp = logp.astype(jnp.float32)
            adv = jnp.clip(batch.advantages, -self.adv_cap, self.adv_cap)
            loss = -masked_mean(logp * adv, batch.mask)
            ratio = jnp.ones_like(logp)
            metrics = common_metrics(ratio, ratio, jnp.zeros_like(logp),
                                     batch.mask, batch.entropy)
            return apply_regularizers(loss, metrics, logp, logp,
                                      batch.mask, cfg, batch.entropy)

    try:
        assert "test_reinforce" in available()
        tr = Trainer(toy, rl, "test_reinforce")
        assert tr.algo == TestReinforce()
        state = tr.init_state(jax.random.PRNGKey(0))
        state, m = tr.step(state, make_batch(False, seed=3))
        assert np.isfinite(m["loss"]) and int(state.version) == 1
    finally:
        unregister("test_reinforce")
    assert "test_reinforce" not in available()


def test_registry_tolerates_plugin_edge_cases():
    """Docstring-less plugins don't break --algo list, and unregistering
    by alias removes the whole entry (canonical + aliases) cleanly."""

    # a plain (non-dataclass) subclass carries __doc__ = None — the
    # sparsest plugin registration must not break the table
    @register("test_nodoc", aliases=("test_nodoc_alias",))
    class NoDoc(Algorithm):
        def loss(self, logp, batch, cfg):  # pragma: no cover - unused
            raise NotImplementedError

    try:
        row = [r for r in registry_table() if r["name"] == "test_nodoc"][0]
        assert row["doc"] == ""
        assert row["aliases"] == ["test_nodoc_alias"]
    finally:
        unregister("test_nodoc_alias")  # by alias, not canonical name
    assert "test_nodoc" not in available()
    with pytest.raises(ValueError) as e:
        get_algorithm("test_nodoc_alias")
    # the advertised alias list no longer contains the stale alias
    assert "test_nodoc_alias" not in str(e.value).split("aliases:")[1]

    # a colliding registration must leave the registry untouched — no
    # half-inserted canonical name pointing at an unstamped class
    before = available()
    with pytest.raises(ValueError, match="already registered"):
        @register("test_orphan", aliases=("sync",))
        @dataclasses.dataclass(frozen=True)
        class Colliding(Algorithm):
            def loss(self, logp, batch, cfg):  # pragma: no cover
                raise NotImplementedError
    assert available() == before
    with pytest.raises(ValueError):
        get_algorithm("test_orphan")


# ------------------------------------------------------------ kl_coef wire
def test_kl_coef_wired_into_every_builtin(toy):
    logp, b = rand_loss_inputs(seed=5)
    for name in BUILTINS:
        algo = get_algorithm(name)
        bb = b._replace(prox_logp=(b.behav_logp
                                   if algo.needs_prox_forward else None))
        l0, m0 = algo.loss(logp, bb, RLConfig(kl_coef=0.0))
        l1, m1 = algo.loss(logp, bb, RLConfig(kl_coef=0.7))
        assert "kl" in m0 and np.isfinite(float(m0["kl"])), name
        np.testing.assert_allclose(float(l1), float(l0)
                                   + 0.7 * float(m0["kl"]),
                                   rtol=1e-5, atol=1e-7, err_msg=name)


def test_kl_metric_through_trainer(toy, rl):
    tr = Trainer(toy, dataclasses.replace(rl, kl_coef=0.1), "a3po")
    state = tr.init_state(jax.random.PRNGKey(0))
    _, m = tr.step(state, make_batch(False, seed=4))
    assert np.isfinite(m["kl"])


def test_kl_penalty_pulls_toward_anchor():
    """With zero advantages the sync loss is flat; the KL penalty alone
    must push logp toward the behavior anchor (k1 gradient = +1/denom)."""
    cfg = RLConfig(kl_coef=1.0)
    behav = jnp.full((1, 4), -1.0)
    mask = jnp.ones((1, 4))
    algo = get_algorithm("sync")

    def f(lp):
        return algo.loss(lp, LossInputs(
            advantages=jnp.zeros((1, 4)), mask=mask, behav_logp=behav),
            cfg)[0]

    g = jax.grad(f)(jnp.full((1, 4), -0.5))
    assert bool(jnp.all(g > 0))  # descending lowers logp toward behav


# ------------------------------------------------- beyond-paper built-ins
def test_asympo_is_behavior_free():
    logp, b = rand_loss_inputs(seed=6)
    algo = get_algorithm("asympo")
    # no behavior logps, no versions — the minimal LossInputs suffices
    loss, m = algo.loss(logp, LossInputs(advantages=b.advantages,
                                         mask=b.mask), RLConfig())
    assert np.isfinite(float(loss))
    np.testing.assert_allclose(float(m["iw_mean"]), 1.0, atol=1e-6)
    # asymmetric scales: negative advantages weigh neg_scale/pos_scale
    # harder in the gradient
    adv = jnp.ones((1, 1))
    mask = jnp.ones((1, 1))

    def g_of(a, adv_sign):
        return jax.grad(lambda lp: a.loss(lp, LossInputs(
            advantages=adv_sign * adv, mask=mask), RLConfig())[0]
        )(jnp.full((1, 1), -1.0))

    a = get_algorithm("asympo", pos_scale=1.0, neg_scale=2.0)
    g_pos = g_of(a, +1.0)
    g_neg = g_of(a, -1.0)
    np.testing.assert_allclose(np.asarray(g_neg), -2.0 * np.asarray(g_pos),
                               rtol=1e-6)


def test_grpo_mu_staleness_gated_truncation():
    cfg = RLConfig(clip_eps=0.2)
    algo = get_algorithm("grpo_mu", mu=0.5)
    mask = jnp.ones((1, 1))
    adv = jnp.ones((1, 1))
    behav = jnp.full((1, 1), -0.15)
    logp0 = jnp.zeros((1, 1))  # ratio ~ 1.16, inside the fresh cap 1.2

    def loss_at(d):
        return lambda lp: algo.loss(lp, LossInputs(
            advantages=adv, mask=mask, behav_logp=behav,
            versions=jnp.array([5 - d]), current_version=5), cfg)[0]

    # fresh (d=0): cap = 1 + eps = 1.2 — full PPO range, live gradient
    g_fresh = jax.grad(loss_at(0))(logp0)
    assert abs(float(g_fresh[0, 0])) > 1e-4
    # stale (d=4): cap = 1 + 0.2 * 0.5^4 = 1.0125 < ratio — truncated,
    # the stale sample cannot be up-weighted and carries no gradient
    g_stale = jax.grad(loss_at(4))(logp0)
    np.testing.assert_allclose(np.asarray(g_stale), 0.0, atol=1e-8)
    _, m = algo.loss(logp0, LossInputs(
        advantages=adv, mask=mask, behav_logp=behav,
        versions=jnp.array([1]), current_version=5), cfg)
    np.testing.assert_allclose(float(m["iw_max"]),
                               1.0 + 0.2 * 0.5 ** 4, rtol=1e-6)


def test_nested_algo_config_schedule_override(toy, rl):
    """A3PO(schedule=...) overrides cfg.alpha_schedule per instance."""
    logp, b = rand_loss_inputs(seed=7)
    cfg = RLConfig(alpha_schedule="inverse", alpha_const=0.25)
    l_inv, _ = get_algorithm("a3po").loss(logp, b, cfg)
    l_const, _ = A3PO(schedule="const").loss(logp, b, cfg)
    l_const_direct, _ = get_algorithm("a3po").loss(
        logp, b, dataclasses.replace(cfg, alpha_schedule="const"))
    assert float(l_inv) != float(l_const)
    np.testing.assert_allclose(float(l_const), float(l_const_direct),
                               rtol=1e-7)
    # and it threads through RLConfig.algo into the Trainer
    rl2 = dataclasses.replace(rl, algo=A3PO(schedule="const"))
    assert Trainer(toy, rl2).algo == A3PO(schedule="const")
