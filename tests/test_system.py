"""End-to-end system behaviour tests: rollout engine, trainer, async
orchestration, checkpointing, sharding rules."""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RLConfig
from repro.configs.registry import get_config, list_archs
from repro.data import tokenizer as tok
from repro.data.tasks import ArithmeticTask
from repro.rollout.engine import RolloutEngine
from repro.training.checkpoints import load_checkpoint, save_checkpoint
from repro.training.trainer import (
    Trainer,
    assemble_train_batch,
    recompute_prox_logp,
    score_tokens,
)


@pytest.fixture(scope="module")
def toy():
    return dataclasses.replace(get_config("toy-2m"), dtype="float32")


@pytest.fixture(scope="module")
def task():
    return ArithmeticTask(max_operand=9, n_terms=2, prompt_len=8, seed=0)


@pytest.fixture(scope="module")
def rl():
    return RLConfig(group_size=4, num_minibatches=2, learning_rate=3e-4)


def test_registry_covers_assignment():
    archs = list_archs(assigned_only=True)
    assert len(archs) == 10
    families = {get_config(a).arch_type for a in archs}
    assert families == {"dense", "moe", "ssm", "hybrid", "vlm", "audio"}


def test_tokenizer_roundtrip():
    text = "12+34=46"
    assert tok.decode(tok.encode(text)) == text


def test_task_rewards_verifiable(task):
    b = task.sample(4)
    for i, ans in enumerate(b.answers):
        ids = tok.encode(ans) + [tok.EOS]
        assert task.reward(np.array(ids), ans) == 1.0
        assert task.reward(np.array(tok.encode("999")), ans) == 0.0


def test_rollout_engine_contract(toy, task, rl):
    engine = RolloutEngine(toy, rl, max_new_tokens=4)
    params = Trainer(toy, rl).init_state(jax.random.PRNGKey(0)).params
    b = task.sample(3)
    rb = engine.generate(params, b.prompts, b.prompt_lengths,
                         jax.random.PRNGKey(1), version=5)
    assert rb.version == 5
    assert rb.tokens.shape == (3, 8 + 4)
    assert rb.gen_logp.shape == (3, 4)
    # behavior logps must be valid log-probabilities at sampled tokens
    assert np.all(rb.gen_logp <= 1e-5)
    # mask is a prefix (1s then 0s)
    for row in rb.gen_mask:
        assert np.all(np.diff(row) <= 0)


def test_behavior_logp_matches_scoring(toy, task, rl):
    """Rollout-engine behavior logps == trainer scoring of the same tokens
    (no behav/target numerical mismatch, unlike vLLM-vs-trainer gaps)."""
    engine = RolloutEngine(toy, rl, max_new_tokens=4)
    params = Trainer(toy, rl).init_state(jax.random.PRNGKey(0)).params
    b = task.sample(2)
    rb = engine.generate(params, b.prompts, b.prompt_lengths,
                         jax.random.PRNGKey(1))
    tb = assemble_train_batch([rb], np.zeros(2, np.float32))
    logp, _, _ = score_tokens(params, toy, tb.tokens)
    sel = tb.response_mask > 0
    np.testing.assert_allclose(np.asarray(logp)[sel],
                               np.asarray(tb.behav_logp)[sel],
                               rtol=1e-4, atol=1e-4)


def test_assemble_scatters_correctly(toy, task, rl):
    engine = RolloutEngine(toy, rl, max_new_tokens=4)
    params = Trainer(toy, rl).init_state(jax.random.PRNGKey(2)).params
    b = task.sample(2)
    rb = engine.generate(params, b.prompts, b.prompt_lengths,
                         jax.random.PRNGKey(3), version=2)
    tb = assemble_train_batch([rb], np.ones(2, np.float32))
    for i in range(2):
        L = int(b.prompt_lengths[i])
        n = int(rb.gen_mask[i].sum())
        row_mask = np.asarray(tb.response_mask[i])
        assert row_mask[L - 1: L - 1 + n].sum() == n
        assert row_mask.sum() == n
    assert np.all(np.asarray(tb.versions) == 2)


@pytest.mark.parametrize("method", ["loglinear", "recompute", "sync"])
def test_trainer_step_all_methods(toy, task, rl, method):
    trainer = Trainer(toy, rl, method)
    state = trainer.init_state(jax.random.PRNGKey(0))
    engine = RolloutEngine(toy, rl, max_new_tokens=4)
    b = task.sample(4)
    prompts = np.repeat(b.prompts, rl.group_size, axis=0)
    lengths = np.repeat(b.prompt_lengths, rl.group_size)
    rb = engine.generate(state.params, prompts, lengths,
                         jax.random.PRNGKey(1), version=0)
    rewards = np.random.default_rng(0).uniform(size=16).astype(np.float32)
    tb = assemble_train_batch([rb], rewards)
    state2, m = trainer.step(state, tb)
    assert int(state2.version) == 1
    assert np.isfinite(m["loss"])
    assert m["prox_time_s"] >= 0
    if method == "recompute":
        assert m["prox_time_s"] > 0


def test_recompute_prox_is_score(toy, rl):
    params = Trainer(toy, rl).init_state(jax.random.PRNGKey(0)).params
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 4, 20)
    prox = recompute_prox_logp(params, toy, toks)
    logp, _, _ = score_tokens(params, toy, toks)
    np.testing.assert_allclose(prox, logp, rtol=1e-6)


def test_checkpoint_roundtrip(toy, rl):
    trainer = Trainer(toy, rl)
    state = trainer.init_state(jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt")
        save_checkpoint(path, {"params": state.params, "opt": state.opt},
                        {"version": 3})
        tree, meta = load_checkpoint(path)
        assert meta["version"] == 3
        restored = tree["params"]
        flat_a = jax.tree.leaves(state.params)
        flat_b = jax.tree.leaves(restored)
        assert len(flat_a) == len(flat_b)
        for a, b in zip(flat_a, flat_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_simulation_staleness(toy, task, rl):
    from repro.async_rl.orchestrator import simulate_async
    _, recs = simulate_async(toy, rl, task, "loglinear", num_steps=4,
                             n_prompts=2, max_new_tokens=3, staleness=2)
    assert [r.staleness_mean for r in recs] == [0.0, 1.0, 2.0, 2.0]


def test_async_threaded_orchestrator(toy, task, rl):
    from repro.async_rl.orchestrator import AsyncOrchestrator
    orch = AsyncOrchestrator(toy, rl, task, "loglinear", n_prompts=2,
                             max_new_tokens=3, queue_capacity=2)
    trainer = Trainer(toy, rl, "loglinear")
    state = trainer.init_state(jax.random.PRNGKey(0))
    state, recs = orch.run(state, num_steps=2)
    assert len(recs) == 2
    assert int(state.version) == 2
    assert all(np.isfinite(r.loss) for r in recs)


def test_rollout_queue_staleness_gate():
    from repro.async_rl.buffer import RolloutQueue
    from repro.rollout.engine import RolloutBatch
    q = RolloutQueue(capacity=4, max_staleness=2)

    def mk(version):
        return RolloutBatch(np.zeros((1, 4), np.int32), np.array([2]),
                            np.zeros((1, 2), np.float32),
                            np.ones((1, 2), np.float32), version=version)

    q.push(mk(0))
    q.push(mk(5))
    fresh = q.pop_fresh(current_version=6, n=1)
    assert fresh[0].version == 5  # version 0 was dropped (staleness 6 > 2)
    assert q.dropped == 1


def test_roofline_peaks_keyed_by_device_kind():
    """v5e's published peaks drive the roofline; a device with no table
    entry is an error, never silently costed as a v5e."""
    from repro.distributed.hlo_analysis import roofline_terms
    t = roofline_terms(197e12, 819e9 * 2, 0.0, "TPU v5 lite")
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(2.0)
    assert t["dominant"] == "memory_s"
    with pytest.raises(ValueError, match="no published peaks"):
        roofline_terms(1.0, 1.0, 1.0, "TPU v4")


def test_sharding_env_divisibility_fallback():
    """kv_heads=8 on model=16 must fall back to replication, not crash."""
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.distributed.sharding import ShardingEnv
    mesh = AbstractMesh((16, 16), ("data", "model"))
    env = ShardingEnv(mesh)
    # kv=8 not divisible by model=16 -> replicated
    assert env.spec((8, 128), ("kv_heads", "head_dim")) == P()
    # heads=96 divisible -> sharded
    assert env.spec((96, 128), ("heads", "head_dim")) == P("model")
    # FSDP weight: embed over data, ff over model
    assert env.spec((4096, 11008), ("embed", "ff")) == P("data", "model")
    # fsdp off -> embed replicated
    env2 = ShardingEnv(mesh, fsdp=False)
    assert env2.spec((4096, 11008), ("embed", "ff")) == P(None, "model")
    # batch spans (pod, data) on the multi-pod mesh
    mesh3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    env3 = ShardingEnv(mesh3)
    assert env3.spec((256, 4096), ("batch", "seq")) == P(("pod", "data"))
    # batch=1 (long_500k) -> replicated
    assert env3.spec((1, 4096), ("batch", "seq")) == P()


def test_constrain_noop_without_mesh():
    from repro.distributed.sharding import constrain
    x = jnp.ones((4, 4))
    y = constrain(x, "batch", None)
    np.testing.assert_array_equal(x, y)


def test_restore_sharded_roundtrip(toy, rl):
    """Checkpoint restore onto mesh shardings (single-device local mesh)."""
    import tempfile
    from repro.launch.mesh import make_local_mesh
    from repro.distributed.sharding import ShardingEnv
    from repro.models import model as M
    from repro.training.checkpoints import restore_sharded, save_checkpoint

    trainer = Trainer(toy, rl)
    state = trainer.init_state(jax.random.PRNGKey(0))
    mesh = make_local_mesh()
    env = ShardingEnv(mesh)
    shardings = M.param_shardings(toy, env)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt")
        save_checkpoint(path, state.params, {"v": 1})
        restored, meta = restore_sharded(path, shardings)
    assert meta["v"] == 1
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_eval_hook_in_simulation(toy, task, rl):
    from repro.async_rl.orchestrator import simulate_async
    calls = []

    def fake_eval(params):
        calls.append(1)
        return 0.25

    _, recs = simulate_async(toy, rl, task, "loglinear", 4, n_prompts=2,
                             max_new_tokens=3, staleness=1,
                             eval_every=2, eval_fn=fake_eval)
    assert [r.eval_reward for r in recs] == [None, 0.25, None, 0.25]
    assert len(calls) == 2
