"""The expert layer's dispatch buffer sized by the rows routed here: each
rung of ``moe.buffer_rungs`` gives the worst-case buffer's layer.

A layer of 8 router outputs holding experts 2 and 3, top-2, over 2 x 1024
tokens has the rungs 1024 and 4096 (the balanced share, and every token's
top-2 on the held experts). A constant input feature and a router row on
it steer how many assignments land here, so each case picks its rung. The
oracle is the same math on the single worst-case buffer of ``T *
min(top_k, held)`` rows, written out here. Each case runs the layer alone
and as two layers of a checkpointed scan, as the decoder stacks it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.configs.registry import get_config
from repro.models import moe as moe_mod
from repro.models.layers import swiglu
from repro.models.params import init_from_specs

B, S, D = 2, 1024, 64
CFG = dataclasses.replace(
    get_config("qwen3-moe-30b-a3b-reduced"), dtype="float32", d_model=D,
    moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32,
                  num_shared_experts=1, held=(2, 2)))


def worst_case_layer(params, x, cfg, valid):
    """The layer with one buffer of ``T * min(top_k, held)`` rows."""
    m = cfg.moe
    T, k = B * S, m.top_k
    first, n = m.held_range
    rows = T * min(k, n)
    xf, ok = x.reshape(T, D), valid.reshape(T)
    probs, top_w, top_i = moe_mod.route(params["router"], xf, m)
    aux = moe_mod.load_balance_loss(probs, top_i, m, ok) * m.router_aux_weight
    local = top_i - first
    mine = (local >= 0) & (local < n) & ok[:, None]
    key = jnp.where(mine, local, n).reshape(T * k)
    order = jnp.argsort(key, stable=True)[:rows]
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    live = jnp.arange(rows) < jnp.sum(sizes)
    tok = order // k
    xs = jnp.where(live[:, None], xf[tok], 0)
    w = jnp.where(live, top_w.reshape(T * k)[order], 0.0)
    g = jax.lax.ragged_dot(xs, params["w_gate"], sizes)
    u = jax.lax.ragged_dot(xs, params["w_up"], sizes)
    ys = jax.lax.ragged_dot(jax.nn.silu(g) * u, params["w_down"], sizes)
    ys = jnp.where(live[:, None], ys, 0) * w[:, None]
    y = jnp.zeros((T, D)).at[tok].add(ys) + swiglu(params["shared"], xf)
    return y.reshape(B, S, D), aux, rows


def steered(bias):
    """Weights and inputs whose router adds ``bias`` to the held experts'
    logits."""
    p = init_from_specs(moe_mod.moe_spec(CFG), jax.random.PRNGKey(0),
                        jnp.float32)
    p = dict(p, router=p["router"].at[0, 2:4].set(bias))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D)).at[..., 0].set(1)
    return p, x


def test_the_ladder():
    assert moe_mod.buffer_rungs(B * S, CFG.moe) == (1024, 4096)
    # rounded up to the kernel's row tile; the worst case caps the share
    assert moe_mod.buffer_rungs(100, CFG.moe) == (512,)
    assert moe_mod.buffer_rungs(1500, CFG.moe) == (1024, 3072)


@pytest.mark.parametrize("T", [24, 2048, 10744])
def test_a_layer_holding_every_expert_has_one_rung(T):
    m = dataclasses.replace(CFG.moe, held=None)
    assert moe_mod.buffer_rungs(T, m) == (-(-T * 2 // 512) * 512,)
    full = dataclasses.replace(CFG, moe=m)
    p = init_from_specs(moe_mod.moe_spec(full), jax.random.PRNGKey(0),
                        jnp.float32)
    text = str(jax.make_jaxpr(moe_mod.moe_apply_local, static_argnums=2)(
        p, jnp.zeros((1, T, D)), full))
    assert " cond[" not in text


def scanned(layer):
    """Two layers of ``layer`` with residuals, the second's expert weights
    scaled, each checkpointed in a scan as the decoder runs its stack;
    returns (out, the layers' aux summed, the layers' buffer rows)."""
    def run(p, x):
        stacked = jax.tree.map(lambda a: jnp.stack([a, a]), p)
        for name in ("w_gate", "w_up", "w_down"):
            stacked[name] = stacked[name].at[1].multiply(1.25)

        def body(h, lp):
            y, aux, rows = layer(lp, h)
            return h + y, (aux, rows)

        h, (aux, rows) = jax.lax.scan(jax.checkpoint(body), x, stacked)
        return h, jnp.sum(aux), jax.tree.map(jnp.sum, rows)
    return run


@pytest.mark.parametrize("wrap", ["alone", "scanned"])
@pytest.mark.parametrize("bias,real,rung", [
    (-1.0, 512, 1024),    # a third of the balanced share, half the tokens
    (0.5, S, 4096),       # 1.5 times the balanced share: the worst case
    (10.0, S, 4096),      # every token's top-2 on the held experts
], ids=["first", "over_share", "worst"])
def test_each_rung_gives_the_worst_case_buffer(bias, real, rung, wrap):
    p, x = steered(bias)
    valid = jnp.broadcast_to(jnp.arange(S) < real, (B, S))
    n_layers = 1 if wrap == "alone" else 2

    def rungs(p, x):
        y, aux, st = moe_mod.moe_apply_local(p, x, CFG, valid)
        return y, aux, (st["moe_buffer_rows"], st["moe_local_rows"])

    def oracle(p, x):
        y, aux, rows = worst_case_layer(p, x, CFG, valid)
        return y, aux, (rows, rows)

    if wrap == "scanned":
        rungs, oracle = scanned(rungs), scanned(oracle)

    def loss(layer, p, x):
        y, aux, _ = layer(p, x)
        return jnp.sum(y * jnp.cos(y)) + aux, (y, aux)

    (_, (y, aux)), g = jax.value_and_grad(
        lambda p, x: loss(rungs, p, x), argnums=(0, 1), has_aux=True)(p, x)
    (_, (y0, aux0)), g0 = jax.value_and_grad(
        lambda p, x: loss(oracle, p, x), argnums=(0, 1), has_aux=True)(p, x)
    buffer_rows, routed = (int(v) for v in jax.tree.leaves(rungs(p, x)[2]))
    assert buffer_rows == n_layers * rung
    if wrap == "alone":
        assert (routed <= 1024) == (rung == 1024)
        if bias == 10.0:
            assert routed == rung
    if wrap == "alone":
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-6,
                                   atol=1e-7)
        assert float(aux) == float(aux0)
        for name in ("w_gate", "w_up", "w_down", "router"):
            np.testing.assert_allclose(np.asarray(g[0][name]),
                                       np.asarray(g0[0][name]), rtol=1e-5,
                                       atol=1e-6, err_msg=name)
        np.testing.assert_allclose(np.asarray(g[1]), np.asarray(g0[1]),
                                   rtol=1e-5, atol=1e-6)
        return
    # the scan compiles the two stacks' sums in other orders: each array
    # within a few float32 roundings of its largest entry
    pairs = [(y, y0), (aux, aux0), (g[1], g0[1])] + [
        (g[0][n], g0[0][n]) for n in ("w_gate", "w_up", "w_down", "router")]
    for a, b in pairs:
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 1e-5 * np.max(np.abs(b))
