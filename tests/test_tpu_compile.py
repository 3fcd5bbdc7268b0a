"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one kernel at Qwen2.5-1.5B
widths with the TPU compiler, which refuses the block shapes, layouts and
VMEM use the chip would refuse (interpret mode accepts them all). The
topology is described inside a module fixture, never at import, so under
several pytest workers only the worker given this file loads the TPU
library; where no topology can be described the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config

# one async-loop minibatch of the chip smoke (16 sequences x 79 scored
# positions): not a multiple of the 256-row block, so the partial last
# token block is compiled too
TOKENS = 16 * 79
# the rollout engine's pool geometry (AsyncOrchestrator's control plane)
N_BLOCKS, BLOCK_SIZE, MAX_BLOCKS, SLOTS, CHUNK = 512, 8, 16, 32, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def widths():
    cfg = get_config("qwen2.5-1.5b")
    return dict(d=cfg.d_model, V=cfg.vocab_size, H=cfg.num_heads,
                KV=cfg.num_kv_heads, hd=cfg.resolved_head_dim)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


def _logprob_args(sds, w):
    return (sds((TOKENS, w["d"]), jnp.bfloat16),
            sds((w["d"], w["V"]), jnp.bfloat16),
            sds((TOKENS,), jnp.int32))


def _pool_args(sds, w, n_queries):
    q = sds((n_queries, w["H"], w["hd"]), jnp.bfloat16)
    pool = sds((N_BLOCKS, w["KV"], BLOCK_SIZE, w["hd"]), jnp.bfloat16)
    tables = sds((SLOTS, MAX_BLOCKS), jnp.int32)
    return q, pool, tables


@pytest.fixture(scope="module")
def sds(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def test_logprob_forward_compiles(sds, widths):
    from repro.kernels.logprob.kernel import logprob_stats_pallas
    _compile(logprob_stats_pallas, *_logprob_args(sds, widths))


def test_logprob_forward_backward_compiles(sds, widths):
    """jax.grad through the kernel's custom_vjp (the train step's path)."""
    from repro.kernels.logprob.ops import token_logprob_entropy_kernel

    def objective(h, w, t):
        logp, ent = token_logprob_entropy_kernel(h, w, t, False)
        return jnp.sum(logp) + 0.01 * jnp.sum(ent)

    _compile(jax.grad(objective, argnums=(0, 1)),
             *_logprob_args(sds, widths))


def test_a3po_loss_compiles(sds):
    from repro.kernels.a3po_loss.kernel import a3po_loss_pallas
    x = sds((TOKENS,), jnp.float32)
    _compile(lambda *a: a3po_loss_pallas(*a, interpret=False), x, x, x, x, x)


def test_paged_decode_compiles(sds, widths):
    from repro.kernels.decode_attn.paged_kernel import (
        paged_decode_attention_pallas,
    )
    q, pool, tables = _pool_args(sds, widths, SLOTS)
    _compile(paged_decode_attention_pallas, q, pool, pool, tables,
             sds((SLOTS,), jnp.int32))


def test_paged_prefill_compiles(sds, widths):
    from repro.kernels.prefill_attn.kernel import (
        paged_prefill_attention_pallas,
    )
    q, pool, tables = _pool_args(sds, widths, CHUNK)
    rows = sds((CHUNK,), jnp.int32)
    _compile(paged_prefill_attention_pallas, q, pool, pool, tables, rows,
             rows, sds((SLOTS,), jnp.int32))


def test_drop_free_expert_layer_compiles(sds, monkeypatch):
    """DeepSeek-V2-Lite's expert layer over the 8 experts held on one chip
    of expert parallelism 8 (router of 64, top-6, width 1408), forward and
    backward over one packed minibatch of the train cell (8 rows of 1343):
    the grouped matmuls compile to the megablox kernel, as on one chip
    (the default backend here is the CPU, so the test picks the kernel)."""
    import dataclasses

    from repro.configs.registry import get_config
    from repro.models import moe as moe_mod

    monkeypatch.setattr(moe_mod, "_use_kernel", lambda: True)
    full = get_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(full, moe=dataclasses.replace(full.moe,
                                                            held=(0, 8)))
    p = jax.tree.map(lambda s: sds(s.shape, jnp.bfloat16),
                     moe_mod.moe_spec(cfg),
                     is_leaf=lambda x: hasattr(x, "logical"))
    x = sds((8, 1343, cfg.d_model), jnp.bfloat16)

    def loss(p, x):
        y, aux, _ = moe_mod.moe_apply_local(p, x, cfg)
        return jnp.sum(y.astype(jnp.float32)) + aux

    _compile(jax.grad(loss, argnums=(0, 1)), p, x)
