"""Per-architecture smoke tests: REDUCED variant of each assigned family
(2 layers, d_model<=512, <=4 experts) runs one forward + one Trainer step
on CPU with correct shapes and no NaNs (frontend stacks are not trained);
decode consistency vs the full pass.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RLConfig
from repro.configs.registry import ASSIGNED, get_config
from repro.launch import steps
from repro.models import model as M
from repro.training.trainer import Trainer, TrainBatch

ARCHS = sorted(ASSIGNED)


def _reduced(name):
    return dataclasses.replace(get_config(name + "-reduced"),
                               dtype="float32")


def _inputs(cfg, B=2, S=16, key=None):
    key = jax.random.PRNGKey(0) if key is None else key
    toks = jax.random.randint(key, (B, S), 4, cfg.vocab_size)
    embeds = None
    if cfg.frontend:
        embeds = jax.random.normal(
            key, (B, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    return toks, embeds


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_config_limits(arch):
    cfg = _reduced(arch)
    assert cfg.num_layers <= 6
    assert cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.num_experts <= 4


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nan(arch):
    cfg = _reduced(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks, embeds = _inputs(cfg)
    logits, aux = M.forward_logits(params, cfg, toks, embeds=embeds)
    B, S = toks.shape
    F = cfg.frontend_tokens if cfg.frontend else 0
    assert logits.shape == (B, S + F, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits)))
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_runs(arch):
    """One full RL train step (fwd + bwd + adam) through ``Trainer.step`` on
    the reduced config; a frontend stack is refused by name."""
    cfg = _reduced(arch)
    rl = RLConfig(group_size=2, num_minibatches=1, learning_rate=1e-4)
    if cfg.frontend:
        with pytest.raises(ValueError, match="frontend"):
            Trainer(cfg, rl, "a3po")
        return
    tr = Trainer(cfg, rl, "a3po")
    state = tr.init_state(jax.random.PRNGKey(0))
    # target version 4 against behavior versions 1 and 2: stale rows
    state = state._replace(version=jnp.int32(4))
    B, S = 2, 16
    toks, _ = _inputs(cfg, B, S)
    batch = TrainBatch(
        tokens=toks, response_mask=jnp.ones((B, S - 1)),
        behav_logp=-jnp.ones((B, S - 1)) * 2,
        versions=jnp.array([1, 2], jnp.int32),
        rewards=jnp.array([1.0, 0.0]))
    new, m = tr.step(state, batch)
    assert np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
    assert m["entropy"] >= 0
    # params actually changed
    diff = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()),
                     state.params, new.params))
    assert diff > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = _reduced(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 12
    toks, embeds = _inputs(cfg, B, S, jax.random.PRNGKey(1))
    logits_full, _ = M.forward_logits(params, cfg, toks, embeds=embeds)
    F = cfg.frontend_tokens if cfg.frontend else 0
    _, cache = M.prefill(params, cfg, toks[:, : S - 1], embeds=embeds,
                         max_len=F + S + 4)
    logits_dec, cache2 = M.decode_step(params, cfg, cache, toks[:, S - 1])
    ref = logits_full[:, -1]
    err = float(jnp.abs(ref - logits_dec).max()
                / (jnp.abs(ref).max() + 1e-9))
    assert err < 2e-3, f"{arch}: rel err {err}"
    assert int(cache2["lengths"][0]) == int(cache["lengths"][0]) + 1


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_cover_all_shapes(arch):
    from repro.configs.base import SHAPES
    cfg = get_config(arch)
    for shape in SHAPES.values():
        if shape.kind == "train" and cfg.frontend:
            # the Trainer trains token-only stacks
            with pytest.raises(ValueError, match="frontend"):
                steps.input_specs(cfg, shape)
            continue
        specs = steps.input_specs(cfg, shape)
        assert "tokens" in specs
        if shape.kind == "train":
            assert set(specs) == {"version", "tokens", "behav_logp", "mask",
                                  "versions", "rewards"}
        if shape.kind == "decode":
            assert "cache" in specs
            leaves = jax.tree.leaves(
                specs["cache"],
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            assert all(isinstance(leaf, jax.ShapeDtypeStruct)
                       for leaf in leaves)


def test_sliding_window_policy():
    """long_500k: SSM/hybrid/MLA keep full state; dense archs window."""
    from repro.configs.base import SHAPES
    long = SHAPES["long_500k"]
    assert steps.decode_window(get_config("mamba2-370m"), long) is None
    assert steps.decode_window(get_config("zamba2-1.2b"), long) is None
    assert steps.decode_window(get_config("deepseek-v2-lite-16b"),
                               long) is None
    assert steps.decode_window(get_config("codeqwen1.5-7b"), long) == 8192
    assert steps.decode_window(get_config("codeqwen1.5-7b"),
                               SHAPES["decode_32k"]) is None


def test_param_counts_match_analytic():
    """init param count == ModelConfig.num_params() for every arch."""
    from repro.models.params import count_params
    for arch in ARCHS:
        cfg = get_config(arch)
        spec_count = count_params(M.model_spec(cfg))
        analytic = cfg.num_params()
        assert spec_count == analytic, (arch, spec_count, analytic)
