"""The named scopes of the compiled a3po train step.

The benchmark splits the step's device time into phases by the scope path
JAX writes into each compiled instruction's ``metadata={op_name=...}``
(``bench/phases.py``), so these names are a contract: a rename fails here.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import RLConfig
from repro.configs.registry import get_config
from repro.kernels.logprob import token_logprob_entropy
from repro.training import trainer as T


def op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.fixture(scope="module")
def step_paths():
    # a dense stack with checkpointed layers, the logprob head on its
    # reference path (off TPU)
    cfg = dataclasses.replace(get_config("toy-2m"), dtype="float32",
                              remat=True)
    rl = RLConfig(group_size=2, num_minibatches=2)
    trainer = T.Trainer(cfg, rl, "a3po")
    state = trainer.init_state(jax.random.PRNGKey(0))
    B, S = 4, 16
    f32, i32 = jnp.float32, jnp.int32
    text = T._train_step.lower(
        state.params, state.opt, state.version, jnp.ones((B, S), i32),
        jnp.zeros((B, S - 1), f32), jnp.ones((B, S - 1), f32),
        jnp.zeros((B,), i32), jnp.ones((B,), f32), cfg=cfg, rl=rl,
        algo=trainer.algo, num_minibatches=2,
        num_microbatches=1).compile().as_text()
    return op_names(text)


@pytest.mark.parametrize("pattern", [
    r"/jvp\(decoder\)/",
    r"/transpose\(jvp\(decoder\)\)/",
    r"decoder\)\)/.*/rematted_computation/",
    r"jvp\(logprob_head\)",
    r"policy_loss",
    r"/optimizer/",
])
def test_compiled_step_carries_the_phase_scope(step_paths, pattern):
    assert any(re.search(pattern, p) for p in step_paths), pattern


def test_logprob_kernel_backward_loop_carries_its_scope():
    # four vocabulary blocks of 2048: the backward stays a loop
    T_, d, V = 8, 128, 8192
    kh, kw, kt = jax.random.split(jax.random.PRNGKey(1), 3)
    h = jax.random.normal(kh, (T_, d), jnp.float32)
    w = jax.random.normal(kw, (d, V), jnp.float32) * d ** -0.5
    t = jax.random.randint(kt, (T_,), 0, V)

    def loss(h, w):
        logp, ent = token_logprob_entropy(h, w, t, interpret=True)
        return jnp.sum(logp) + jnp.sum(ent)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(h, w) \
        .compile().as_text()
    loops = re.findall(r' while\([^\n]*op_name="([^"]*)"', text)
    assert any("logprob_head_bwd" in p for p in loops), loops
