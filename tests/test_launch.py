"""Launch-layer tests: input specs, shardings, lowering on a local mesh,
and the trip-count-aware HLO cost parser."""
import dataclasses
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import InputShape, RLConfig, SHAPES
from repro.configs.registry import get_config
from repro.distributed.hlo_cost import analyze
from repro.distributed.sharding import ShardingEnv, use_sharding
from repro.launch import steps
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.training import trainer as trainer_mod


def test_hlo_cost_plain_matmul():
    m, n, k = 32, 48, 64
    f = jax.jit(lambda a, b: a @ b)
    txt = f.lower(jax.ShapeDtypeStruct((m, k), jnp.float32),
                  jax.ShapeDtypeStruct((k, n), jnp.float32)
                  ).compile().as_text()
    c = analyze(txt)
    assert c.flops == 2 * m * n * k


def test_hlo_cost_scan_trip_counts():
    m, k, L = 32, 64, 7

    def g(x, ws):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

    txt = jax.jit(g).lower(
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((L, k, k), jnp.float32)).compile().as_text()
    c = analyze(txt)
    assert c.flops == L * 2 * m * k * k
    assert list(c.while_trips.values()) == [L]


def test_hlo_cost_nested_scan():
    m, k = 16, 32

    def h(x, ws):
        def outer(carry, wset):
            return jax.lax.scan(lambda c, w: (c @ w, None), carry,
                                wset)[0], None
        return jax.lax.scan(outer, x, ws)[0]

    txt = jax.jit(h).lower(
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((3, 5, k, k), jnp.float32)).compile().as_text()
    c = analyze(txt)
    assert c.flops == 15 * 2 * m * k * k


@pytest.mark.parametrize("arch", ["codeqwen1.5-7b", "mamba2-370m",
                                  "deepseek-v2-lite-16b", "zamba2-1.2b"])
def test_reduced_train_step_lowers_on_local_mesh(arch):
    """The dry-run's train program, ``Trainer.step``'s own compiled step
    (shardings included), compiles on the real local mesh for reduced
    configs — same code path as the 512-device dry-run."""
    cfg = dataclasses.replace(get_config(arch + "-reduced"), dtype="float32")
    env = ShardingEnv(make_local_mesh())
    compiled = steps.lower_train_step(cfg, RLConfig(), "a3po", env, 4,
                                      32).compile()
    assert compiled.cost_analysis() is not None
    # the step's named phases: the minibatch scan's Adam and the loss
    scopes = {part for name in re.findall(r'op_name="([^"]*)"',
                                          compiled.as_text())
              for part in name.split("/")}
    assert "optimizer" in scopes and "jvp(policy_loss)" in scopes


def test_train_specs_are_the_trainer_step_operands():
    """A train shape's specs are the operands ``Trainer.step`` passes its
    compiled step, in order, and ``make_step``'s train step on them is
    that step: it matches ``Trainer.step`` on the same batch."""
    impl = inspect.signature(trainer_mod._train_step_impl).parameters
    names = list(impl)[2:list(impl).index("prox_logp")]
    cfg = dataclasses.replace(get_config("toy-2m"), dtype="float32")
    shape = InputShape("tiny_train", 12, 8, "train")
    specs = steps.input_specs(cfg, shape)
    assert list(specs) == names
    rl = RLConfig(group_size=4, num_minibatches=2, learning_rate=1e-3)
    tr = trainer_mod.Trainer(cfg, rl, "a3po")
    state = tr.init_state(jax.random.PRNGKey(0))
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    batch = trainer_mod.TrainBatch(
        tokens=jax.random.randint(ks[0], (8, 12), 4, cfg.vocab_size),
        response_mask=jnp.ones((8, 11)),
        behav_logp=-jax.random.uniform(ks[1], (8, 11)),
        versions=jnp.zeros((8,), jnp.int32),
        rewards=jax.random.bernoulli(ks[2], 0.5, (8,)).astype(jnp.float32))
    ops = dict(version=state.version, tokens=batch.tokens,
               behav_logp=batch.behav_logp, mask=batch.response_mask,
               versions=batch.versions, rewards=batch.rewards)
    assert {k: (v.shape, v.dtype) for k, v in ops.items()} == {
        k: (v.shape, v.dtype) for k, v in specs.items()}
    step = steps.make_step(cfg, shape, rl, "a3po")
    p1, _, packed = step(state.params,
                         jax.tree.map(jnp.copy, state.opt), ops)
    s2, m = tr.step(state, batch)
    metrics = dict(zip(trainer_mod.metric_keys(cfg), np.asarray(packed)))
    assert metrics["loss"] == m["loss"]
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "musicgen-large"])
def test_reduced_decode_step_lowers(arch):
    cfg = dataclasses.replace(get_config(arch + "-reduced"), dtype="float32")
    shape = InputShape("tiny_decode", 64, 4, "decode")
    rl = RLConfig()
    mesh = make_local_mesh()
    env = ShardingEnv(mesh)
    specs = steps.input_specs(cfg, shape)
    step = steps.make_step(cfg, shape, rl)
    params_abs = M.abstract_params(cfg)
    param_sh = M.param_shardings(cfg, env)
    batch_sh = steps.batch_shardings(cfg, shape, env, specs)
    with mesh, use_sharding(env):
        compiled = jax.jit(step, in_shardings=(param_sh, batch_sh)).lower(
            params_abs, specs).compile()
    assert compiled is not None


def test_input_specs_no_allocation():
    """Specs are abstract — building them must not allocate device arrays."""
    cfg = get_config("command-r-plus-104b")
    specs = steps.input_specs(cfg, SHAPES["decode_32k"])
    leaves = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    assert all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves)
    total = sum(np.prod(leaf.shape) * leaf.dtype.itemsize
                for leaf in leaves)
    assert total > 2**40  # the full-scale cache would be >1TiB if real


def test_chunked_prefill_equivalence():
    """Batch-chunked prefill (nm=4) == unchunked (logits + cache)."""
    cfg = dataclasses.replace(get_config("toy-2m"), dtype="float32")
    shape = InputShape("t", 16, 8, "prefill")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 4,
                                          cfg.vocab_size)}
    l1, c1 = steps.make_prefill_step(cfg, shape, 1)(params, batch)
    l4, c4 = steps.make_prefill_step(cfg, shape, 4)(params, batch)
    np.testing.assert_allclose(l1, l4, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
