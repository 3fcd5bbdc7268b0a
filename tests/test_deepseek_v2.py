"""DeepSeek-V2's block in the program against the plain reference
(``bench/reference_deepseek_v2.py``) at a tiny size with the structure of
the benchmark's configuration: one dense layer then two expert layers, 8
router outputs of which 4 are held, one shared expert, top-2, latent
attention of rank 16 with YaRN, float32 throughout.

Tolerances: both sides compute in float32 (the reference at HIGHEST), in
different orders (grouped matmuls and a sorted scatter against a masked
product over every held expert), so results agree to float32 round-off:
1e-5 relative, 1e-6 absolute on values of order 1e-2 to 1; a gradient,
summed over every position of the batch, to 1e-4 relative.
"""
import dataclasses
import json
import math
import pathlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R
from bench import reference_deepseek_v2 as RD
from bench import traffic
from bench.drivers import train_moe
from bench.drivers.train import _rl_dict
from repro.configs.base import RLConfig, YarnConfig
from repro.models import mla as mla_mod
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.models.layers import rope_freqs
from repro.models.params import count_params, init_from_specs
from repro.obs.metrics import get_registry
from repro.obs.tracing import SpanTracer, install_tracer
from repro.training.trainer import (
    RowFit,
    Trainer,
    TrainBatch,
    _score_tokens,
    fit_rows,
    input_needs,
    row_block,
)

ROOT = pathlib.Path(__file__).resolve().parents[1]
FILE = json.loads((ROOT / "bench" / "configs"
                   / "deepseek-v2-lite-l6-ep8.json").read_text())
TINY = dict(FILE, name="tiny-ds", hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=16, moe_intermediate_size=32, n_routed_experts=4,
            num_experts_per_tok=2, n_shared_experts=1, num_hidden_layers=3,
            vocab_size=256, torch_dtype="float32",
            deployment=dict(FILE["deployment"], experts_held=[2, 4],
                            router_outputs=8))
MIX = dict(traffic.load("rl_batch_gsm8k"), pad_to=48,
           prompt_len={"median": 8, "sigma": 0.4, "min": 4, "max": 16},
           response_len={"median": 16, "sigma": 0.5, "min": 4, "max": 30})
TOL = dict(rtol=1e-5, atol=1e-6)


def cfg_for(conf):
    return train_moe.model_config(conf)


def batch(seed=3, B=8):
    b = traffic.rl_batches(MIX, seed, 1, B, TINY["vocab_size"])[0]
    b["behav_logp"] = (-1.0 + 0.3 * b["behav_offset"]) * b["response_mask"]
    return b


def test_the_configuration_is_the_one_chip_share():
    cfg = cfg_for(FILE)
    assert cfg.num_layers == 6 and cfg.first_dense_layers == 1
    assert cfg.moe.num_experts == 64 and cfg.moe.held == (0, 8)
    assert cfg.moe.top_k == 6 and cfg.moe.d_ff_expert == 1408
    assert cfg.d_ff == 10944 and cfg.norm_eps == 1e-6
    assert not cfg.moe.norm_topk_prob and M.packable(cfg)
    # 13.76 M of latent attention a layer, 81.0 M in the dense layer, 8
    # experts of 8.65 M, 17.3 M shared, 0.13 M router; head and embedding
    # 26.2 M each
    assert cfg.num_params() == count_params(M.model_spec(cfg)) \
        == 635_466_752


def test_the_registry_entry_is_the_published_model():
    """``deepseek-v2-lite-16b`` holds the published config: dense layer 0
    of width 10944, all 64 experts, rms_norm_eps 1e-6, YaRN, top-k weights
    not renormalised; 15.7 B parameters, as published."""
    from repro.configs.registry import get_config

    cfg = get_config("deepseek-v2-lite-16b")
    assert (cfg.num_layers, cfg.first_dense_layers, cfg.d_ff) \
        == (27, 1, 10944)
    assert cfg.moe.held_range == (0, 64) and not cfg.moe.norm_topk_prob
    assert cfg.norm_eps == 1e-6 and cfg.rope_scaling.factor == 40.0
    assert cfg.num_params() == count_params(M.model_spec(cfg))
    assert round(cfg.num_params() / 1e9, 1) == 15.7


# ----------------------------------------------------------- yarn and scale
def test_yarn_frequencies_and_the_softmax_scale_follow_deepseek():
    rs = FILE["rope_scaling"]
    y = YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                   beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                   mscale_all_dim=0.707)
    dim, base = 64, 10000.0

    # DeepseekV2YarnRotaryEmbedding, transcribed
    def corr(rot):
        return (dim * math.log(4096 / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)),
                                                  dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(rope_freqs(dim, base, y)), want,
                               rtol=1e-6)
    np.testing.assert_allclose(RD.yarn_inv_freq(FILE), want, rtol=1e-6)
    # the fastest bands are kept, the slowest interpolated by 40
    assert want[0] == extra[0] and want[-1] == pytest.approx(extra[-1] / 40)
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert y.cos_sin_factor == 1.0
    scale = 192 ** -0.5 * mscale ** 2
    assert mla_mod.softmax_scale(cfg_for(FILE)) == pytest.approx(scale)
    assert RD.softmax_scale(FILE) == pytest.approx(scale)
    assert rs["mscale"] == rs["mscale_all_dim"] == 0.707


# ------------------------------------------------------ the expert layer
def layer_params(conf, key=0):
    """One expert layer's weights, every router output's expert made."""
    full = dict(conf, n_routed_experts=conf["deployment"]["router_outputs"],
                deployment=dict(conf["deployment"], experts_held=[
                    0, conf["deployment"]["router_outputs"]]))
    cfg = cfg_for(full)
    p = init_from_specs(moe_mod.moe_spec(cfg), jax.random.PRNGKey(key),
                        jnp.float32)
    return full, cfg, p


def ref_layer(conf, p, h):
    m = RD.Model(conf)
    return np.asarray(m.experts(p, h)[0])


@pytest.mark.parametrize("ranks", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(ranks):
    """Every way of dividing the 8 experts over ranks: the ranks' partial
    outputs, with the shared expert (which every rank computes) counted
    once, give the uncut layer, in the program and the reference alike."""
    full, cfg, p = layer_params(TINY)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64))
    whole = ref_layer(full, p, h)
    shared = np.asarray(RD.Model(full).swiglu(p["shared"], h))
    n = 8 // ranks
    parts, rows = 0.0, 0
    for r in range(ranks):
        held = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=(r * n, n)))
        pr = dict(p, **{k: p[k][r * n:(r + 1) * n]
                        for k in ("w_gate", "w_up", "w_down")})
        y, _, st = moe_mod.moe_apply_local(pr, h, held)
        parts = parts + np.asarray(y) - shared
        rows += int(st["moe_local_rows"])
        conf = dict(full, n_routed_experts=n, deployment=dict(
            full["deployment"], experts_held=[r * n, n]))
        np.testing.assert_allclose(np.asarray(y), ref_layer(conf, pr, h),
                                   **TOL)
    np.testing.assert_allclose(parts + shared, whole, **TOL)
    assert rows == 2 * 12 * 2    # every token's top-2, each counted once


def test_routing_drops_no_token_when_every_token_wants_one_expert():
    """A router that sends every token to expert 3: a capacity-bound
    dispatch would drop most of them; the drop-free layer gives the
    reference's result and counts all of them on that expert."""
    full, cfg, p = layer_params(TINY)
    p = dict(p, router=p["router"].at[:, 3].set(5.0))
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (2, 12, 64))) + 1
    y, _, st = moe_mod.moe_apply_local(p, h, cfg)
    np.testing.assert_allclose(np.asarray(y), ref_layer(full, p, h), **TOL)
    assert int(st["moe_expert_rows_max"]) == 24
    assert int(st["moe_local_rows"]) == 48


def use_kernel(monkeypatch):
    """The expert layer's grouped matmuls on the megablox kernel, as on one
    TPU chip, run in the Pallas interpreter."""
    monkeypatch.setattr(moe_mod, "_use_kernel", lambda: True)
    monkeypatch.setattr(moe_mod, "gmm", partial(moe_mod.gmm, interpret=True))


@pytest.mark.parametrize("k,n", [(64, 32), (48, 80)])
def test_grouped_matmul_kernel_matches_ragged_dot(monkeypatch, k, n):
    """The kernel against ``jax.lax.ragged_dot`` on the rows its groups
    cover, fewer than the buffer's: the product and both gradients, an
    empty group among them, ragged last tiles where k or n is not a
    multiple of the other."""
    M, sizes = 40, jnp.array([5, 0, 13, 9], jnp.int32)
    live = (jnp.arange(M) < jnp.sum(sizes))[:, None]
    x = jax.random.normal(jax.random.PRNGKey(0), (M, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, k, n)) / k ** 0.5
    ct = jax.random.normal(jax.random.PRNGKey(2), (M, n))

    def loss(dot, x, w):
        return jnp.sum(jnp.where(live, dot(x, w, sizes), 0) * ct)

    want = jax.value_and_grad(partial(loss, jax.lax.ragged_dot),
                              argnums=(0, 1))(x, w)
    use_kernel(monkeypatch)
    got = jax.value_and_grad(partial(loss, moe_mod.grouped_matmul),
                             argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got[1][0])[:27],
                               np.asarray(want[1][0])[:27], **TOL)
    np.testing.assert_allclose(np.asarray(got[1][1]), np.asarray(want[1][1]),
                               rtol=1e-5, atol=1e-5)


def test_expert_layer_through_the_kernel_matches_the_reference(monkeypatch):
    """The layer with its grouped matmuls on the megablox kernel (in the
    interpreter), as it runs on one TPU chip: the reference's output, and
    the gradients of the XLA path, whatever the kernel leaves in the
    buffer's rows past the routed ones."""
    full, cfg, p = layer_params(TINY)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64))

    def loss(p, h):
        y, aux, _ = moe_mod.moe_apply_local(p, h, cfg)
        return jnp.sum(y * jnp.cos(y)) + aux, y

    (_, y_xla), g_xla = jax.value_and_grad(loss, argnums=(0, 1),
                                           has_aux=True)(p, h)
    use_kernel(monkeypatch)
    (_, y), g = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, h)
    np.testing.assert_allclose(np.asarray(y), ref_layer(full, p, h), **TOL)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_xla)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_padding_is_routed_nowhere_and_left_out_of_the_aux_loss():
    full, cfg, p = layer_params(TINY)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64))
    valid = jnp.arange(12)[None, :] < jnp.array([[7], [12]])
    y, aux, st = moe_mod.moe_apply_local(p, h, cfg, valid)
    alone, _, _ = moe_mod.moe_apply_local(p, h[1:], cfg)
    assert int(st["moe_local_rows"]) == (7 + 12) * 2
    np.testing.assert_allclose(np.asarray(y[1]), np.asarray(alone[0]), **TOL)
    # the aux loss counts the 7 + 12 real tokens alone
    probs, _, top_i = moe_mod.route(p["router"], h.reshape(24, 64), cfg.moe)
    keep = np.asarray(valid).reshape(-1)
    want = moe_mod.load_balance_loss(probs[keep], top_i[keep], cfg.moe)
    assert float(aux) == pytest.approx(float(want) * 0.01, rel=1e-6)


# ------------------------------------------------------- packing and step
def test_packed_rows_equal_padded_rows():
    cfg = cfg_for(TINY)
    b = batch()
    tokens = jnp.asarray(b["tokens"])
    mask = jnp.asarray(b["response_mask"])
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    block = row_block(8)
    fit = RowFit(*(a[0] for a in fit_rows(mask[None], block)))
    rows = int(fit.blocks) * block
    assert rows < 8
    valid = jnp.arange(mask.shape[1])[None, :] < input_needs(mask)[:, None]
    pad = _score_tokens(params, cfg, tokens, valid=valid)
    pack = _score_tokens(params, cfg, tokens, fit, rows=rows)
    real = np.asarray(mask) > 0
    for a, b_ in zip(pad[:2], pack[:2]):
        np.testing.assert_allclose(np.asarray(b_)[real], np.asarray(a)[real],
                                   **TOL)
    np.testing.assert_allclose(float(pack[2]), float(pad[2]), **TOL)
    assert {k: int(v) for k, v in pack[3].items()} \
        == {k: int(v) for k, v in pad[3].items()}


def test_train_step_matches_the_reference_loss_and_gradients():
    """``Trainer.step`` on one batch, one minibatch: its loss, and its
    gradient as Adam's first moment holds it, against the reference's
    loss and full float32 gradient."""
    cfg = cfg_for(TINY)
    rl = RLConfig(group_size=4, num_minibatches=1)
    b = batch()
    tr = Trainer(cfg, rl, "a3po")
    key = jax.random.PRNGKey(7)
    tracer = SpanTracer()
    install_tracer(tracer)
    try:
        state, m = tr.step(tr.init_state(key), TrainBatch(
            tokens=jnp.asarray(b["tokens"]),
            response_mask=jnp.asarray(b["response_mask"]),
            behav_logp=jnp.asarray(b["behav_logp"]),
            versions=jnp.asarray(b["versions"]),
            rewards=jnp.asarray(b["rewards"])))
    finally:
        install_tracer(None)
    assert m["packed_rows"] < m["padded_rows"]
    # the counters: in the step's output, as gauges and on its span; the
    # benchmark's own forward over the same weights counts the same rows
    assert 0 < m["moe_expert_rows_max"] <= m["moe_local_rows"]
    reg = get_registry()
    assert reg.gauge("train_moe_local_rows").get() == m["moe_local_rows"]
    assert reg.gauge("train_moe_expert_rows_max").get() \
        == m["moe_expert_rows_max"]
    # two expert layers, each on a buffer of at least one 512-row rung
    assert m["moe_buffer_rows"] >= max(m["moe_local_rows"], 2 * 512)
    assert reg.gauge("train_moe_buffer_rows").get() == m["moe_buffer_rows"]
    ev, = [e for e in tracer.events()
           if e["ph"] == "X" and e["name"] == "train_update"]
    assert ev["args"]["moe_local_rows"] == m["moe_local_rows"]
    assert ev["args"]["moe_buffer_rows"] == m["moe_buffer_rows"]
    need = input_needs(jnp.asarray(b["response_mask"]))
    model = RD.Model(TINY)
    _, held = RD.Forward(model).hidden(
        RD.make_weights(TINY, key), b["tokens"][:, :-1],
        np.arange(b["tokens"].shape[1] - 1)[None, :]
        < np.asarray(need)[:, None])
    assert m["moe_local_rows"] == sum(held)

    ref = RD.Trainer(TINY, _rl_dict(rl), rows_per_block=2)
    mask = b["response_mask"]
    mb = dict(tokens=b["tokens"], mask=mask, behav=b["behav_logp"],
              adv=R.group_advantages(b["rewards"], 4)[:, None] * mask,
              alpha=np.broadcast_to(R.alpha_inverse(-b["versions"])[:, None],
                                    mask.shape))
    grads, loss = ref._grads(RD.make_weights(TINY, key), mb)
    assert m["loss"] == pytest.approx(loss, rel=1e-5)

    flat = R.flatten(grads)
    gnorm = math.sqrt(sum(float(jnp.sum(g * g)) for g in flat.values()))
    assert m["grad_norm"] == pytest.approx(gnorm, rel=1e-5)
    scale = min(1.0, rl.max_grad_norm / (gnorm + 1e-9))
    prog = R.flatten(state.opt["m"])
    assert set(prog) == set(flat)
    for k, g in flat.items():
        np.testing.assert_allclose(
            np.asarray(prog[k]) / ((1 - rl.adam_b1) * scale), np.asarray(g),
            rtol=1e-4, atol=1e-6 * float(jnp.max(jnp.abs(g))) + 1e-9,
            err_msg=k)


def test_reference_blocks_cut_after_their_last_real_token(monkeypatch):
    """The reference's blocks, longest rows first, cut after their last
    real token (rounded up to ``TRIM``), give the gradient and loss of
    blocks of every position: positions past a row's last real token
    carry no loss and no real position attends them."""
    b = batch(seed=5)
    mask = b["response_mask"]
    mb = dict(tokens=b["tokens"], mask=mask, behav=b["behav_logp"],
              adv=R.group_advantages(b["rewards"], 4)[:, None] * mask,
              alpha=np.broadcast_to(R.alpha_inverse(-b["versions"])[:, None],
                                    mask.shape))
    rl = _rl_dict(RLConfig(group_size=4, num_minibatches=1))
    w = RD.make_weights(TINY, jax.random.PRNGKey(11))
    need = RD.input_needs(mask)
    assert RD.block_len(need, mask.shape[1]) == mask.shape[1]  # TRIM > S
    whole, loss = RD.Trainer(TINY, rl, rows_per_block=2)._grads(w, mb)
    monkeypatch.setattr(RD, "TRIM", 8)
    assert RD.block_len(np.array([3, 9]), 47) == 16
    assert RD.block_len(np.array([0, 0]), 47) == 8
    assert RD.block_len(np.array([41, 2]), 47) == 47
    assert RD.block_len(np.sort(need)[:2], 47) < 47  # some blocks are cut
    cut, loss_cut = RD.Trainer(TINY, rl, rows_per_block=2)._grads(w, mb)
    assert loss_cut == pytest.approx(loss, rel=1e-5)
    for k, g in R.flatten(whole).items():
        np.testing.assert_allclose(
            np.asarray(R.flatten(cut)[k]), np.asarray(g), rtol=1e-4,
            atol=1e-6 * float(jnp.max(jnp.abs(g))) + 1e-9, err_msg=k)
