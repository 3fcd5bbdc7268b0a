"""Training-engine tests: scan-pipeline parity vs the seed loop-trainer,
fused-vs-ref a3po gradients, single host transfer, microbatch accumulation,
sharded state placement, and the unified alpha dispatch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RLConfig
from repro.configs.registry import get_config
from repro.core.algorithms import LossInputs, get_algorithm
from repro.core.a3po import (
    alpha_from_staleness,
    compute_prox_logp_approximation,
    staleness,
)
from repro.core.advantages import group_normalized_advantages
from repro.core.objective import (
    coupled_ppo_loss,
    decoupled_ppo_loss,
    fused_a3po_loss,
    resolve_alpha,
)
from repro.training import trainer as trainer_mod
from repro.training.optimizer import adam_update
from repro.training.trainer import (
    TrainBatch,
    Trainer,
    TrainState,
    _score_tokens,
    recompute_prox_logp,
)

B, T = 8, 12
# real tokens per row of a ragged batch: each minibatch of 4 packs into 3
# rows of T - 1 input positions; ``make_batch``'s random mask reads fewer
# of them, so its minibatches pack into 2
RAGGED = (6, 7, 12, 5, 4, 8, 6, 10)


@pytest.fixture(scope="module")
def toy():
    return dataclasses.replace(get_config("toy-2m"), dtype="float32")


@pytest.fixture(scope="module")
def rl():
    return RLConfig(group_size=4, num_minibatches=2, learning_rate=3e-4)


def make_batch(per_token_versions: bool, seed: int = 0,
               ragged: bool = False) -> TrainBatch:
    """Every row full, or ``ragged``: rows of ``RAGGED`` real tokens, PAD
    after them and no response past them."""
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 5)
    tokens = jax.random.randint(ks[0], (B, T), 4, 60)
    mask = (jnp.arange(T - 1)[None, :] >= 4).astype(jnp.float32) \
        * (jax.random.uniform(ks[1], (B, T - 1)) > 0.2)
    if ragged:
        lengths = jnp.asarray(RAGGED)[:, None]
        tokens = jnp.where(jnp.arange(T)[None, :] < lengths, tokens, 0)
        mask = mask * (jnp.arange(T - 1)[None, :] >= 2) \
            * (jnp.arange(T - 1)[None, :] <= lengths - 2)
    behav = -jax.random.uniform(ks[2], (B, T - 1)) * 2 * mask
    if per_token_versions:
        versions = jax.random.randint(ks[3], (B, T - 1), 0, 4)
    else:
        versions = jax.random.randint(ks[3], (B,), 0, 4)
    rewards = jax.random.uniform(ks[4], (B,)).astype(jnp.float32)
    return TrainBatch(tokens=tokens, response_mask=mask, behav_logp=behav,
                      versions=versions, rewards=rewards)


def reference_loop_step(cfg, rl, method, state, batch):
    """The seed PR-1 loop trainer, reimplemented over the modular jnp
    losses (no fused kernel, Python minibatch loop, host-side metric
    aggregation) — the parity oracle for the compiled scan engine."""
    adv_seq = group_normalized_advantages(batch.rewards, rl.group_size)
    advantages = adv_seq[:, None] * batch.response_mask
    prox_full = (recompute_prox_logp(state.params, cfg, batch.tokens)
                 if method == "recompute" else None)
    params, opt = state.params, state.opt
    nmb = min(rl.num_minibatches, B)
    mb = B // nmb
    mets = []
    for i in range(nmb):
        sl = slice(i * mb, (i + 1) * mb)

        def loss_fn(p):
            logp, entropy, aux, _ = _score_tokens(p, cfg, batch.tokens[sl])
            behav, adv = batch.behav_logp[sl], advantages[sl]
            mask = batch.response_mask[sl]
            if method == "sync":
                loss, m = coupled_ppo_loss(logp, behav, adv, mask, rl,
                                           entropy)
            elif method == "recompute":
                loss, m = decoupled_ppo_loss(logp, behav, prox_full[sl],
                                             adv, mask, rl, entropy)
            else:
                prox = compute_prox_logp_approximation(
                    behav, logp, batch.versions[sl], state.version, rl)
                loss, m = decoupled_ppo_loss(logp, behav, prox, adv, mask,
                                             rl, entropy)
            return loss + aux, m

        (loss, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt, gnorm = adam_update(grads, opt, params, rl)
        mets.append({k: float(v)
                     for k, v in dict(m, loss=loss, grad_norm=gnorm).items()})
    out = {k: float(np.mean([m[k] for m in mets])) for k in mets[0]}
    out["iw_max"] = float(np.max([m["iw_max"] for m in mets]))
    out["iw_min"] = float(np.min([m["iw_min"] for m in mets]))
    out["clipped_tokens"] = float(np.sum([m["clipped_tokens"]
                                          for m in mets]))
    d = state.version - batch.versions
    if batch.versions.ndim == 2:
        msum = float(jnp.sum(batch.response_mask))
        out["staleness_mean"] = float(
            jnp.sum(d * batch.response_mask) / max(msum, 1.0))
    else:
        out["staleness_mean"] = float(d.mean())
    out["reward_mean"] = float(batch.rewards.mean())
    return TrainState(params, opt, state.version + 1), out


PARITY_KEYS = ("loss", "grad_norm", "iw_max", "iw_min", "iw_mean",
               "ratio_mean", "clipped_tokens", "clipped_frac", "entropy",
               "kl", "staleness_mean", "reward_mean")


@pytest.mark.parametrize("method", ["loglinear", "recompute", "sync"])
@pytest.mark.parametrize("per_token", [False, True])
@pytest.mark.parametrize("ragged", [False, True])
def test_scan_engine_matches_seed_loop(toy, rl, method, per_token, ragged):
    """The compiled scan pipeline reproduces the seed loop-trainer's
    metrics and parameters for all three methods, [B] and [B,T] stamps;
    on ragged rows its decoder runs packed against the loop's padding."""
    batch = make_batch(per_token, ragged=ragged)
    trainer = Trainer(toy, rl, method)
    s_scan = trainer.init_state(jax.random.PRNGKey(3))
    s_ref = trainer.init_state(jax.random.PRNGKey(3))
    # non-zero target version so loglinear sees real staleness
    s_scan = TrainState(s_scan.params, s_scan.opt, jnp.asarray(3, jnp.int32))
    s_ref = TrainState(s_ref.params, s_ref.opt, jnp.asarray(3, jnp.int32))

    s_ref, m_ref = reference_loop_step(toy, rl, method, s_ref, batch)
    s_scan, m_scan = trainer.step(s_scan, batch)
    assert m_scan["padded_rows"] == B
    assert m_scan["packed_rows"] == (4 if ragged else B)

    for k in PARITY_KEYS:
        np.testing.assert_allclose(m_scan[k], m_ref[k], rtol=2e-4,
                                   atol=1e-5, err_msg=k)
    for a, b in zip(jax.tree.leaves(s_scan.params),
                    jax.tree.leaves(s_ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)


def assembled_batch() -> TrainBatch:
    """A ragged batch as ``assemble_train_batch`` builds it from rollouts:
    the decoder's rows pack."""
    from repro.rollout.engine import RolloutBatch
    from repro.training.trainer import assemble_train_batch

    rng = np.random.default_rng(0)
    P, N = 4, T - 4
    gen = np.array([L - P for L in RAGGED])
    gen_mask = (np.arange(N)[None, :] < gen[:, None]).astype(np.float32)
    tokens = rng.integers(4, 60, (B, T)).astype(np.int32)
    tokens[np.arange(T)[None, :] >= np.array(RAGGED)[:, None]] = 0
    rollout = RolloutBatch(
        tokens=tokens, prompt_lengths=np.full(B, P, np.int32),
        gen_logp=(-rng.uniform(size=(B, N)) * gen_mask).astype(np.float32),
        gen_mask=gen_mask, version=1)
    return assemble_train_batch([rollout], rng.uniform(size=B))


def test_one_host_transfer_per_step(toy, rl, monkeypatch):
    """The scan engine performs exactly one device->host transfer per
    training step (the packed metrics vector), with its decoder packed."""
    batch = assembled_batch()
    trainer = Trainer(toy, rl, "loglinear")
    state = trainer.init_state(jax.random.PRNGKey(0))
    trainer.step(state, batch)  # warm the compile cache

    state = trainer.init_state(jax.random.PRNGKey(0))
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (calls.append(1), real(x))[1])
    _, m = trainer.step(state, batch)
    assert len(calls) == 1
    assert m["host_syncs"] == 1.0
    assert m["packed_rows"] < m["padded_rows"]
    # recompute pays its explicit prox sync on top
    tr = Trainer(toy, rl, "recompute")
    s2 = tr.init_state(jax.random.PRNGKey(0))
    _, m2 = tr.step(s2, batch)
    assert m2["host_syncs"] == 2.0


def test_batch_without_lengths_pays_one_transfer_on_first_step(
        toy, rl, monkeypatch):
    """A batch built by hand, not by ``assemble_train_batch``, packs on the
    device from its mask: its first step, as every later one, pays only
    the metrics' transfer, and every step runs one compiled program."""
    batch = make_batch(False, ragged=True)
    trainer = Trainer(toy, rl, "loglinear")
    state = trainer.init_state(jax.random.PRNGKey(0))
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (calls.append(1), real(x))[1])
    state, m = trainer.step(state, batch)
    assert len(calls) == 1 and m["host_syncs"] == 1.0
    assert m["packed_rows"] == 4.0
    programs = trainer_mod._train_step._cache_size()
    # other lengths, every row full: the padded branch of the same program
    _, m = trainer.step(state, make_batch(False, seed=1))
    assert len(calls) == 2 and m["host_syncs"] == 1.0
    assert m["packed_rows"] == m["padded_rows"] == B
    assert trainer_mod._train_step._cache_size() == programs


def test_fused_gradient_matches_jnp_reference():
    """Fused kernel custom_vjp == jnp decoupled loss gradient to 1e-5."""
    cfg = RLConfig()
    key = jax.random.PRNGKey(0)
    Bt, Tt = 4, 33  # odd T exercises kernel padding
    logp = -jax.random.uniform(key, (Bt, Tt)) * 3
    behav = -jax.random.uniform(jax.random.PRNGKey(1), (Bt, Tt)) * 3
    adv = jax.random.normal(jax.random.PRNGKey(2), (Bt, Tt))
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (Bt, Tt)) > 0.3
            ).astype(jnp.float32)
    versions = jnp.array([0, 1, 2, 5])

    def ref(lp):
        prox = compute_prox_logp_approximation(behav, lp, versions, 5, cfg)
        return decoupled_ppo_loss(lp, behav, prox, adv, mask, cfg)[0]

    def fused(lp):
        alpha = resolve_alpha(cfg, versions=versions, current_version=5)
        return fused_a3po_loss(lp, behav, alpha, adv, mask, cfg)[0]

    np.testing.assert_allclose(float(ref(logp)), float(fused(logp)),
                               rtol=1e-6)
    g_ref = jax.grad(ref)(logp)
    g_fused = jax.grad(fused)(logp)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-7)
    # ... and at staleness 0 (alpha=0, the systematic clip-tie case)
    g0_ref = jax.grad(lambda lp: decoupled_ppo_loss(
        lp, behav, compute_prox_logp_approximation(
            behav, lp, jnp.full((Bt,), 5), 5, cfg),
        adv, mask, cfg)[0])(logp)
    g0_fused = jax.grad(lambda lp: fused_a3po_loss(
        lp, behav, resolve_alpha(cfg, versions=jnp.full((Bt,), 5),
                                 current_version=5),
        adv, mask, cfg)[0])(logp)
    np.testing.assert_allclose(np.asarray(g0_fused), np.asarray(g0_ref),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("nmi", [2, 4])
def test_microbatch_accumulation_matches_single(toy, rl, nmi):
    """num_microbatches=nmi (grad accumulation inside the scan) == 1, also
    with heavily skewed response-token counts across microbatches (the
    accumulation is token-weighted, not an equal average of masked means).
    nmi=4 puts one sequence in each microbatch.
    """
    batch = make_batch(False)
    # skew: rows 0-3 keep ~7 response tokens, rows 4-7 keep exactly one
    skew = np.asarray(batch.response_mask).copy()
    skew[4:, :] = 0.0
    skew[4:, 5] = 1.0
    skewed = dataclasses.replace(
        batch, response_mask=jnp.asarray(skew),
        behav_logp=batch.behav_logp * jnp.asarray(skew))
    for b in (batch, skewed):
        outs = {}
        for n in (1, nmi):
            tr = Trainer(toy, rl, "loglinear", num_microbatches=n)
            s = tr.init_state(jax.random.PRNGKey(1))
            s, m = tr.step(s, b)
            outs[n] = (s.params, m)
        np.testing.assert_allclose(outs[1][1]["loss"], outs[nmi][1]["loss"],
                                   rtol=1e-5, atol=1e-7)
        for x, y in zip(jax.tree.leaves(outs[1][0]),
                        jax.tree.leaves(outs[nmi][0])):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=5e-3, atol=5e-5)


def test_microbatch_indivisible_raises(toy, rl):
    tr = Trainer(toy, rl, "loglinear", num_microbatches=3)  # mb_size=4
    state = tr.init_state(jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="does not divide"):
        tr.step(state, make_batch(False))


def test_step_donates_opt_keeps_params(toy, rl):
    """The step's contract over 3 chained steps: each step consumes the
    old Adam state (donated, its buffers deleted) while the old params stay
    readable, as the async runtime's behavior policies need."""
    tr = Trainer(toy, rl, "sync")
    state = tr.init_state(jax.random.PRNGKey(0))
    for i in range(3):
        old = state
        before = [np.asarray(x) for x in jax.tree.leaves(old.params)]
        state, m = tr.step(state, make_batch(False, seed=i))
        assert all(x.is_deleted() for x in jax.tree.leaves(old.opt))
        for x, b in zip(jax.tree.leaves(old.params), before):
            assert not x.is_deleted()
            np.testing.assert_array_equal(np.asarray(x), b)
    assert int(state.version) == 3
    assert np.isfinite(m["loss"])


def test_init_state_places_with_sharding_env(toy, rl):
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.launch.mesh import make_local_mesh
    from repro.models import model as M

    mesh = make_local_mesh()
    env = ShardingEnv(mesh)
    trainer = Trainer(toy, rl)
    with mesh, use_sharding(env):
        state = trainer.init_state(jax.random.PRNGKey(0))
    psh = M.param_shardings(toy, env)
    for leaf, sh in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(psh)):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.spec == sh.spec
    # Adam moments ride the same placements as their params
    for leaf, sh in zip(jax.tree.leaves(state.opt["m"]),
                        jax.tree.leaves(psh)):
        assert leaf.sharding.spec == sh.spec


def test_alpha_kl_adaptive_graceful_and_unified_dispatch():
    """alpha_from_staleness no longer raises on kl_adaptive (falls back to
    the staleness-only inverse schedule); resolve_alpha is the one place
    the KL controller actually dispatches from."""
    cfg = RLConfig(alpha_schedule="kl_adaptive")
    d = jnp.array([0.0, 1.0, 2.0, 4.0])
    np.testing.assert_allclose(alpha_from_staleness(d, cfg),
                               [0.0, 1.0, 0.5, 0.25])
    key = jax.random.PRNGKey(0)
    logp = -jax.random.uniform(key, (4, 8)) * 2
    behav = logp + 0.1
    mask = jnp.ones((4, 8))
    a = resolve_alpha(cfg, logp=logp, behav_logp=behav, mask=mask)
    assert a.shape == (4, 1)
    assert bool(jnp.all((a >= 0) & (a <= 1)))
    # staleness schedules still need stamps through the same entry point
    a2 = resolve_alpha(RLConfig(), versions=jnp.array([1, 1, 3, 3]),
                       current_version=3)
    np.testing.assert_allclose(a2, [0.5, 0.5, 0.0, 0.0])
    loss, m = get_algorithm("a3po").loss(logp, LossInputs(
        advantages=jnp.ones((4, 8)), mask=mask, behav_logp=behav), cfg)
    assert np.isfinite(float(loss))


def test_trainer_step_kl_adaptive_end_to_end(toy):
    rl = RLConfig(group_size=4, num_minibatches=2,
                  alpha_schedule="kl_adaptive")
    tr = Trainer(toy, rl, "loglinear")
    state = tr.init_state(jax.random.PRNGKey(0))
    state, m = tr.step(state, make_batch(True))
    assert np.isfinite(m["loss"])
    assert int(state.version) == 1


def test_assemble_vectorized_matches_loop_semantics(toy, rl):
    """Vectorized scatter == the seed per-sequence loop, [B] and [B,T]."""
    from repro.rollout.engine import RolloutBatch
    from repro.training.trainer import assemble_train_batch
    rng = np.random.default_rng(0)

    def mk(Bp, P, N, version, per_token):
        lengths = rng.integers(2, P + 1, Bp)
        gen_mask = (np.arange(N)[None, :]
                    < rng.integers(1, N + 1, Bp)[:, None]).astype(np.float32)
        return RolloutBatch(
            tokens=rng.integers(0, 50, (Bp, P + N)).astype(np.int32),
            prompt_lengths=lengths.astype(np.int32),
            gen_logp=(-rng.uniform(size=(Bp, N)) * gen_mask
                      ).astype(np.float32),
            gen_mask=gen_mask,
            version=version,
            gen_versions=(rng.integers(version, version + 3, (Bp, N))
                          .astype(np.int32) if per_token else None))

    def loop_reference(rollouts):
        tokens = np.concatenate([r.tokens for r in rollouts], axis=0)
        Bt, Tt = tokens.shape
        behav = np.zeros((Bt, Tt - 1), np.float32)
        mask = np.zeros((Bt, Tt - 1), np.float32)
        per_token = any(r.gen_versions is not None for r in rollouts)
        versions = (np.zeros((Bt, Tt - 1), np.int32) if per_token
                    else np.zeros((Bt,), np.int32))
        row = 0
        for r in rollouts:
            N = r.gen_logp.shape[1]
            for b in range(r.batch_size):
                L = int(r.prompt_lengths[b])
                behav[row, L - 1: L - 1 + N] = r.gen_logp[b]
                mask[row, L - 1: L - 1 + N] = r.gen_mask[b]
                if per_token:
                    versions[row, :] = r.version
                    if r.gen_versions is not None:
                        versions[row, L - 1: L - 1 + N] = np.where(
                            r.gen_mask[b] > 0, r.gen_versions[b], r.version)
                else:
                    versions[row] = r.version
                row += 1
        return behav, mask, versions

    for per_token in (False, True):
        rollouts = [mk(3, 6, 4, 1, per_token), mk(2, 6, 4, 2, False)]
        rewards = np.ones(5, np.float32)
        tb = assemble_train_batch(rollouts, rewards)
        behav, mask, versions = loop_reference(rollouts)
        np.testing.assert_array_equal(np.asarray(tb.behav_logp), behav)
        np.testing.assert_array_equal(np.asarray(tb.response_mask), mask)
        np.testing.assert_array_equal(np.asarray(tb.versions), versions)
