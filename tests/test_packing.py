"""Sequence packing of the trainer's decoder rows: the on-device fit,
segment-aware attention, packed scoring against the padded layout, which
configs pack, and the counters that say how far packing engaged."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RLConfig
from repro.configs.registry import get_config
from repro.models import model as M
from repro.models.attention import chunked_causal_attention
from repro.obs.metrics import get_registry
from repro.obs.tracing import SpanTracer, install_tracer
from repro.training.trainer import (
    RowFit,
    TrainBatch,
    Trainer,
    _score_tokens,
    first_fit,
    fit_rows,
    input_needs,
    pack_index,
    row_block,
)

# (lengths, units, seq, multiple)
PLANS = [
    (np.array([6, 7, 12, 5, 4, 8, 6, 10]), 2, 12, 1),
    (np.array([6, 7, 12, 5, 4, 8, 6, 10]), 1, 12, 2),
    (np.array([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]), 1, 12, 1),
    (np.array([12, 2, 2, 2, 3, 3]), 2, 12, 1),
    (np.random.default_rng(0).integers(2, 200, 64), 4, 200, 1),
    (np.random.default_rng(1).integers(2, 200, 64), 4, 200, 4),
]


def needs_mask(lengths, units, seq):
    """A response mask [units, n, seq - 1] whose last kept position is each
    sequence's last input position (``length - 1`` of them)."""
    S = seq - 1
    need = np.clip(np.asarray(lengths) - 1, 0, S)
    mask = np.zeros((len(lengths), S), np.float32)
    mask[need > 0, need[need > 0] - 1] = 1.0
    return jnp.asarray(mask.reshape(units, -1, S)), need.reshape(units, -1)


def unit_index(fit, u, S, rows):
    one = RowFit(*(a[u] for a in fit))
    return [np.asarray(a) for a in pack_index(one, S, rows)]


@pytest.mark.parametrize("lengths,units,seq,multiple", PLANS)
def test_plan_places_every_real_position_once(lengths, units, seq,
                                              multiple):
    S = seq - 1
    mask, need = needs_mask(lengths, units, seq)
    n = need.shape[1]
    q = row_block(n, multiple)
    fit = fit_rows(mask, q)
    np.testing.assert_array_equal(fit.need, need)
    # some unit packs (one that needs all its blocks keeps its n rows)
    assert any(int(b) * q < n for b in fit.blocks)
    for u in range(units):
        R = int(fit.blocks[u]) * q
        assert R <= n and R % multiple == 0
        # the fewest whole blocks that hold the rows used
        assert R - q <= int(fit.row[u].max()) < R
        src, dest = unit_index(fit, u, S, R)
        g = src[src >= 0]
        want = np.concatenate([b * S + np.arange(need[u, b])
                               for b in range(n)])
        # every real input position of the unit exactly once
        np.testing.assert_array_equal(np.sort(g), want)
        seg = np.where(src >= 0, src // S, -1)
        for b in range(n):
            rows, cols = np.nonzero(seg == b)
            # no segment straddles a row, and it sits in one run
            assert len(set(rows.tolist())) <= 1
            assert len(cols) == need[u, b]
            if len(cols):
                np.testing.assert_array_equal(
                    cols, cols[0] + np.arange(len(cols)))
                # positions restart at 0 in each segment
                np.testing.assert_array_equal(src[rows, cols] % S,
                                              np.arange(len(cols)))
            # dest is the inverse of src
            idx = dest[b]
            np.testing.assert_array_equal(
                src.reshape(-1)[idx[idx < R * S]],
                b * S + np.arange(need[u, b]))
            assert (idx[need[u, b]:] == R * S).all()


def test_full_length_sequence_fills_one_row_alone():
    mask, _ = needs_mask([12, 3, 4, 5], 1, 12)
    fit = fit_rows(mask, row_block(4))
    src, _ = unit_index(fit, 0, 11, 3)
    seg = np.where(src >= 0, src // 11, -1)
    rows, cols = np.nonzero(seg == 0)
    assert (rows == rows[0]).all() and len(cols) == 11
    assert set(seg[rows[0]].tolist()) == {0}


@pytest.mark.parametrize("lengths,units,multiple", [
    (np.full(8, 12), 2, 1),               # every row full: nothing saved
    (np.array([7, 7, 7, 7]), 1, 1),       # no two share a row
    (np.array([6, 7, 12, 5, 4, 8, 6, 10]), 2, 4),  # rounding eats the gain
])
def test_unit_that_saves_no_row_keeps_its_padded_rows(lengths, units,
                                                       multiple):
    """A unit whose rows fill all its blocks, or that is one block, keeps
    its padded rows."""
    mask, need = needs_mask(lengths, units, 12)
    n = need.shape[1]
    q = row_block(n, multiple)
    if q < n:
        fit = fit_rows(mask, q)
        assert (np.asarray(fit.blocks) * q == n).all()


def test_input_needs_end_at_the_last_position_the_mask_keeps():
    """The input positions a sequence's loss reads end at the last one its
    mask keeps, whatever tokens follow it."""
    m = np.array([[0, 1, 1, 0, 0], [1, 0, 0, 1, 0], [0, 0, 0, 0, 0],
                  [1, 1, 1, 1, 1]], np.float32)
    np.testing.assert_array_equal(input_needs(jnp.asarray(m)), [3, 4, 0, 5])


@pytest.mark.parametrize("n,shards,want", [
    (16, 1, 4), (16, 4, 4), (16, 8, 8), (4, 1, 1), (4, 4, 4), (2, 1, 1),
    (1, 1, 1), (64, 1, 16),
])
def test_row_block_is_a_quarter_on_the_shard_count(n, shards, want):
    assert row_block(n, shards) == want


def test_first_fit_is_first_fit_decreasing():
    need = jnp.asarray([5, 6, 11, 4, 3, 7, 5, 9], jnp.int32)
    row, offset, used = first_fit(need, 11)
    # by hand: 11 | 9 | 7 4 | 6 5 | 5 3
    assert int(used) == 5
    np.testing.assert_array_equal(row, [3, 3, 0, 2, 4, 2, 4, 1])
    np.testing.assert_array_equal(offset, [6, 0, 0, 7, 5, 0, 0, 0])


# ---------------------------------------------------------------- attention
@pytest.mark.parametrize("S", [24, 1024])        # one chunk, two chunks
@pytest.mark.parametrize("window", [None, 3])
def test_segment_attention_equals_each_sequence_alone(S, window):
    H, KV, hd = 4, 2, 8
    lens = [S // 3, S // 2, S // 8]                  # leaves padding
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, S, H, hd), jnp.float32)
    k = jax.random.normal(kk, (1, S, KV, hd), jnp.float32)
    v = jax.random.normal(kv, (1, S, KV, hd), jnp.float32)
    seg = np.full((1, S), -1, np.int32)
    pos = np.zeros((1, S), np.int32)
    o = 0
    for i, L in enumerate(lens):
        seg[0, o:o + L], pos[0, o:o + L] = i, np.arange(L)
        o += L
    out = chunked_causal_attention(
        q, k, v, q_positions=jnp.asarray(pos), kv_positions=jnp.asarray(pos),
        window=window, segment_ids=jnp.asarray(seg))
    o = 0
    for L in lens:
        p = jnp.arange(L)[None]
        alone = chunked_causal_attention(
            q[:, o:o + L], k[:, o:o + L], v[:, o:o + L], q_positions=p,
            kv_positions=p, window=window)
        np.testing.assert_allclose(np.asarray(out[:, o:o + L]),
                                   np.asarray(alone), rtol=1e-5, atol=1e-6)
        o += L
    assert np.isfinite(np.asarray(out)).all()


# ------------------------------------------------------------------- scoring
@pytest.fixture(scope="module")
def toy():
    return dataclasses.replace(get_config("toy-2m"), dtype="float32")


def ragged_tokens(lengths, T, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(4, 60, (len(lengths), T)).astype(np.int32)
    toks[np.arange(T)[None, :] >= np.asarray(lengths)[:, None]] = 0
    return toks


def test_packed_scoring_matches_padded_at_every_real_position(toy):
    lengths = np.array([6, 7, 12, 5, 4, 8, 6, 10])
    T = 12
    tokens = jnp.asarray(ragged_tokens(lengths, T))
    params = M.init_params(toy, jax.random.PRNGKey(0))
    mask, _ = needs_mask(lengths, 1, T)
    fit = RowFit(*(a[0] for a in fit_rows(mask, row_block(8))))
    assert int(fit.blocks) * row_block(8) == 6
    padded, ent_pad, _, _ = _score_tokens(params, toy, tokens)
    packed, ent_pack, _, _ = _score_tokens(params, toy, tokens, fit, rows=6)
    real = np.arange(T - 1)[None, :] < (lengths - 1)[:, None]
    np.testing.assert_allclose(np.asarray(packed)[real],
                               np.asarray(padded)[real], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ent_pack)[real],
                               np.asarray(ent_pad)[real], rtol=1e-5,
                               atol=1e-5)
    assert np.isfinite(np.asarray(packed)).all()


@pytest.mark.parametrize("arch", sorted(
    ["toy-2m", "mamba2-370m", "zamba2-1.2b", "qwen3-moe-30b-a3b",
     "deepseek-v2-lite-16b", "llava-next-mistral-7b"]))
def test_only_a_plain_attention_stack_packs(arch):
    # attention stacks pack, plain or latent, dense or with drop-free
    # routed experts; SSM state and frontend prefixes do not
    packs = ("toy-2m", "qwen3-moe-30b-a3b", "deepseek-v2-lite-16b")
    cfg = get_config(arch)
    assert M.packable(cfg) == (arch in packs)
    if arch not in packs:
        with pytest.raises(ValueError, match="cannot share a row"):
            M.forward_hidden({}, cfg, jnp.zeros((1, 4), jnp.int32),
                             segment_ids=jnp.zeros((1, 4), jnp.int32))


def ragged_batch(lengths, T, seed=0):
    toks = ragged_tokens(lengths, T, seed)
    B = len(lengths)
    t = np.arange(T - 1)[None, :]
    mask = ((t >= 3) & (t <= np.asarray(lengths)[:, None] - 2)
            ).astype(np.float32)
    return TrainBatch(tokens=jnp.asarray(toks),
                      response_mask=jnp.asarray(mask),
                      behav_logp=jnp.asarray(-0.5 * mask),
                      versions=jnp.zeros((B,), jnp.int32),
                      rewards=jnp.arange(B, dtype=jnp.float32))


@pytest.mark.parametrize("arch", ["mamba2-370m-reduced",
                                  "zamba2-1.2b-reduced"])
def test_unpackable_stack_keeps_the_padded_rows(arch):
    cfg = dataclasses.replace(get_config(arch), dtype="float32")
    tr = Trainer(cfg, RLConfig(group_size=4, num_minibatches=2))
    state = tr.init_state(jax.random.PRNGKey(0))
    batch = ragged_batch([6, 7, 12, 5, 4, 8, 6, 10], 12)
    _, m = tr.step(state, batch)
    assert m["packed_rows"] == m["padded_rows"] == 8.0
    assert m["host_syncs"] == 1.0
    assert np.isfinite(m["loss"])


def test_step_reports_its_rows_in_output_registry_and_span(toy):
    tr = Trainer(toy, RLConfig(group_size=4, num_minibatches=2), "a3po")
    state = tr.init_state(jax.random.PRNGKey(0))
    tracer = SpanTracer()
    install_tracer(tracer)
    try:
        _, m = tr.step(state, ragged_batch([6, 7, 12, 5, 4, 8, 6, 10], 12))
    finally:
        install_tracer(None)
    # 2 minibatches of 4 sequences, 3 packed rows each
    assert (m["packed_rows"], m["padded_rows"]) == (6.0, 8.0)
    reg = get_registry()
    assert reg.gauge("train_packed_rows").get() == 6.0
    assert reg.gauge("train_padded_rows").get() == 8.0
    ev = [e for e in tracer.events()
          if e["ph"] == "X" and e["name"] == "train_update"]
    assert ev[-1]["args"]["rows"] == 3


# ------------------------------------------------------------ data mesh
_MESH_SCRIPT = """
import dataclasses, json, os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs.base import RLConfig
from repro.configs.registry import get_config
from repro.distributed.sharding import ShardingEnv, use_sharding
from repro.training import trainer as T

cfg = dataclasses.replace(get_config("toy-2m"), dtype="float32")
rl = RLConfig(group_size=4, num_minibatches=2)
B, S = 32, 24
rng = np.random.default_rng(0)
lengths = rng.integers(5, 13, B)
toks = rng.integers(4, 60, (B, S)).astype(np.int32)
toks[np.arange(S)[None] >= lengths[:, None]] = 0
t = np.arange(S - 1)[None]
mask = ((t >= 2) & (t <= lengths[:, None] - 2)).astype(np.float32)
batch = T.TrainBatch(
    tokens=jnp.asarray(toks), response_mask=jnp.asarray(mask),
    behav_logp=jnp.asarray(-0.5 * mask), versions=jnp.zeros((B,), jnp.int32),
    rewards=jnp.asarray(rng.uniform(size=B), jnp.float32))
mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1), ("data", "model"))


def run():
    with mesh, use_sharding(ShardingEnv(mesh)):
        tr = T.Trainer(cfg, rl, "a3po")
        state, m = tr.step(tr.init_state(jax.random.PRNGKey(0)), batch)
        return m, jax.device_get(state.params)


packed, p_packed = run()
T._block = lambda cfg, n: None          # the padded rows, for comparison
jax.clear_caches()
padded, p_padded = run()
err = max(float(np.max(np.abs(a - b)))
          for a, b in zip(jax.tree.leaves(p_packed), jax.tree.leaves(p_padded)))
print(json.dumps({"devices": jax.device_count(), "packed": packed,
                  "padded": padded, "param_err": err}))
"""


def test_packed_step_on_a_data_mesh_matches_padded_rows():
    """On four data shards (the FSDP layout) packed rows split over the
    shards, and the step's metrics and weights equal the padded rows'.
    A subprocess: the device count is fixed before JAX starts."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    packed, padded = out["packed"], out["padded"]
    assert padded["packed_rows"] == padded["padded_rows"] == 32
    # 2 minibatches of 16, each on 4, 8 or 12 rows: a multiple of the 4
    # shards, fewer than its 16
    assert packed["packed_rows"] < 32 and packed["packed_rows"] % 4 == 0
    for k in ("loss", "grad_norm", "entropy", "kl", "iw_mean", "tokens"):
        np.testing.assert_allclose(packed[k], padded[k], rtol=2e-4,
                                   atol=1e-6, err_msg=k)
    assert out["param_err"] < 1e-5
