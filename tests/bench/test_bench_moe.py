"""The DeepSeek-V2 train cell on the CPU at a tiny size: a whole run through
``bench/drivers/train_moe.py`` is correct, and is not with the control or
a planted fault in the program's place; the expert layer's phases found in
the compiled step; the FLOP and byte arithmetic of ``bench/flops_moe.py``
at the cell's widths; its readers on hand-built traces."""
import dataclasses
import json
import math
import time
import types

import numpy as np
import pytest

from bench import compare, flops, flops_moe, harness, phases, phases_moe
from bench import trace_reduce as tr
from bench import traffic

CELL = "train_a3po.deepseek-v2-lite-l6-ep8"
STEP = "jit(_train_step_impl)/while/body/closed_call/"
DS = harness.config("deepseek-v2-lite-l6-ep8")
TINY = dict(DS, name="tiny-ds", hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=16, moe_intermediate_size=32, n_routed_experts=4,
            num_experts_per_tok=2, n_shared_experts=1, num_hidden_layers=3,
            vocab_size=256, torch_dtype="float32",
            deployment=dict(DS["deployment"], experts_held=[2, 4],
                            router_outputs=8))
LENS = {"prompt_len": {"median": 8, "sigma": 0.4, "min": 4, "max": 16},
        "response_len": {"median": 16, "sigma": 0.5, "min": 4, "max": 40}}
OVERRIDES = dict(config=TINY, mix=dict(LENS, pad_to=64),
                 cell={"batch": 8, "reference_rows": 2})


@pytest.fixture(scope="module", autouse=True)
def _restore_compile_cache_config():
    """The harness turns JAX's persistent compile cache on in the process;
    put the settings back for the tests that share this worker."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def run(**extra):
    return harness.run(CELL, 2 ** 33 + 11, 0.5, False,
                       t_start=time.perf_counter(),
                       overrides=dict(OVERRIDES, **extra),
                       require_chip=False)


@pytest.fixture(scope="module")
def sound():
    return run(variants=["fp8", "half_batch"])


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"setup_s", "train_tokens_per_s"}


@pytest.mark.parametrize("variant", ["fp8", "half_batch"])
def test_control_and_fault_fail_the_cell_limits(sound, variant):
    ok, checks = compare.judge(sound["variant_numbers"][variant],
                               harness.cell(CELL)["limits"])
    assert not ok, checks


def test_half_the_batch_left_out_by_the_program_is_not_correct(monkeypatch):
    from repro.training import trainer as T

    step = T.Trainer.step

    def half(self, state, batch):
        mask = np.array(batch.response_mask)
        B = mask.shape[0]
        nmb = self.rl.num_minibatches
        rows = np.arange(B).reshape(nmb, B // nmb)[:, B // nmb // 2:]
        mask[rows.reshape(-1)] = 0.0
        return step(self, state, dataclasses.replace(
            batch, response_mask=mask))

    monkeypatch.setattr(T.Trainer, "step", half)
    assert not run()["correct"]


# --------------------------------------------------------------- phases
@pytest.mark.parametrize("path,phase", [
    (STEP + "jvp(decoder)/while/body/closed_call/moe_experts/jit(gmm)/"
     "pallas_call", "moe_experts.fwd"),
    (STEP + "transpose(jvp(decoder))/while/body/closed_call/checkpoint/"
     "rematted_computation/moe_route/sort", "moe_route.recompute"),
    (STEP + "transpose(jvp(decoder))/while/body/closed_call/"
     "transpose(jvp(moe_combine))/scatter-add", "moe_combine.bwd"),
    ("moe_route/reduce_sum", "moe_route.bwd"),
    (STEP + "jvp(decoder)/while/body/closed_call/moe_shared/dot_general",
     "moe_shared.fwd"),
])
def test_expert_scopes_and_their_direction(path, phase):
    scope, d = phase.split(".")
    assert phases._has(path, scope)
    assert phases_moe.direction(path) == d
    # the whole step's classifier still puts them in the decoder, or in
    # other where a transpose lost the caller's scopes
    assert phases.classify(path) in phases.DECODER + ("other",)


def op(name, a, b):
    return (name, float(a), float(b), "")


def ctx_for(trace, lo, hi, steps=1, rows=6.0e4, tokens=2.7e4):
    c = types.SimpleNamespace(
        workload=CELL, seed=5, chips=1, cfg=DS, cell=harness.cell(CELL),
        mix=traffic.load("rl_batch_gsm8k"),
        peaks=harness.peaks("TPU v5 lite"), data={}, logged=[])
    c.log = c.logged.append
    c.data.update(trace=trace, lo=lo, hi=hi, busy_s=(hi - lo) / 1e9,
                  traced_steps=steps, moe_local_rows=rows,
                  moe_tokens=tokens, minibatches=4, train_flops=1.0)
    return c


HLO = """\
HloModule jit__train_step_impl

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, \
metadata={op_name="@1@"}
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, \
metadata={op_name="@2@"}
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, \
metadata={op_name="@3@"}
  %fusion.4 = f32[4]{0} fusion(%p), kind=kLoop, \
metadata={op_name="@4@"}
  ROOT %copy.4 = f32[4]{0} copy(%fusion.1)
}
"""
FWD = STEP + "jvp(decoder)/while/body/closed_call/"
BWD = STEP + "transpose(jvp(decoder))/while/body/closed_call/"


def hlo(*paths):
    text = HLO
    for i, p in enumerate(paths):
        text = text.replace(f"@{i + 1}@", p)
    return text


def test_expert_readers_on_a_hand_built_trace(monkeypatch):
    text = hlo(FWD + "moe_experts/ragged_dot", BWD + "moe_experts/x",
                  FWD + "moe_route/sort", BWD + "checkpoint/"
                  "rematted_computation/moe_experts/ragged_dot")
    monkeypatch.setattr(phases_moe, "step_text", lambda ctx: text)
    chip = [op("fusion.1", 0, 1e8), op("fusion.2", 1e8, 3e8),
            op("fusion.3", 3e8, 3.5e8), op("fusion.4", 3.5e8, 4.5e8)]
    c = ctx_for(tr.Trace(devices=[chip], host=[]), 0, 1e9)
    cost = flops_moe.experts_cost(DS, 6.0e4, 1 * 4 * 5)
    want = flops.roofline_share(cost, 0.3, c.peaks)["share_pct"]
    assert harness.reader("moe_experts_roofline")(c) == pytest.approx(want)
    cost = flops_moe.dispatch_cost(DS, 2.7e4 * 5, 6.0e4)
    want = flops.roofline_share(cost, 0.05, c.peaks)["share_pct"]
    assert harness.reader("moe_dispatch_roofline")(c) == pytest.approx(want)
    line, = [m for m in c.logged if m.startswith("phases of the expert")]
    assert "moe_experts.fwd 0.1000" in line and \
        "moe_experts.recompute 0.1000" in line


def test_expert_readers_read_nothing_without_the_scopes(monkeypatch):
    plain = hlo(*(FWD + "dot_general",) * 4)
    monkeypatch.setattr(phases_moe, "step_text", lambda ctx: plain)
    chip = [op("fusion.1", 0, 1e8)]
    for name in ("moe_experts_roofline", "moe_dispatch_roofline"):
        c = ctx_for(tr.Trace(devices=[chip], host=[]), 0, 1e9)
        assert harness.reader(name)(c) is None
    # and no trace
    c = ctx_for(None, 0, 1)
    assert harness.reader("moe_experts_roofline")(c) is None


@pytest.fixture
def compile_log():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    log = harness.CompileLog()
    harness.enable_cache(log)
    yield log
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_step_text_carries_the_expert_scopes(compile_log):
    import jax.numpy as jnp

    from bench.drivers import common, train_moe
    from repro.configs.base import RLConfig
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.training import trainer as T

    mix = dict(traffic.load("rl_batch_gsm8k"), pad_to=48, **LENS)
    c = types.SimpleNamespace(cfg=TINY, mix=mix, chips=1, seed=5,
                              cell=dict(harness.cell(CELL), batch=8),
                              compile_log=compile_log, logged=[])
    c.log = c.logged.append
    b = traffic.rl_batches(mix, c.seed, 1, 8, TINY["vocab_size"])[0]
    mesh = common.local_mesh(1)
    with mesh, use_sharding(ShardingEnv(mesh)):
        trainer = T.Trainer(train_moe.model_config(TINY),
                            RLConfig(group_size=mix["group_size"]), "a3po")
        state = trainer.init_state(common.seed_key(c.seed))
        state, _ = trainer.step(state, T.TrainBatch(
            tokens=jnp.asarray(b["tokens"]),
            response_mask=jnp.asarray(b["response_mask"]),
            behav_logp=jnp.asarray(-b["response_mask"]),
            versions=jnp.asarray(b["versions"]),
            rewards=jnp.asarray(b["rewards"])))
    text = phases_moe.step_text(c)
    assert text is not None, c.logged
    found = {(s, phases_moe.direction(p))
             for p in phases.scope_map(text).values()
             for s in phases_moe.MOE if phases._has(p, s)}
    assert found == {(s, d) for s in phases_moe.MOE
                     for d in phases_moe.DIRECTIONS}
    # every expert scope sits inside the decoder's
    assert all(phases._has(p, "decoder")
               for p in phases.scope_map(text).values()
               if phases._has(p, "moe_experts"))


# ---------------------------------------------------------- the arithmetic
def test_flops_at_the_cell_widths():
    d, H, V = 2048, 16, 12800
    mla = d * H * 192 + d * (512 + 64) + 512 * H * (128 + 128) + H * 128 * d
    assert flops_moe.mla_params(DS) == mla == 13_762_560
    assert flops_moe.expert_params(DS) == 3 * d * 1408 == 8_650_752
    token = (6 * mla + 3 * d * 10944 + 5 * (d * 64 + 3 * d * 2 * 1408)
             + d * V)
    assert flops_moe.token_params(DS) == token == 263_192_576
    S, rows = 1000, 3750.0                 # 0.75 x 1000 tokens x 5 layers
    attn = 2.0 * S * S * H * (192 + 128) * 6
    assert flops_moe.train_flops(DS, [S], rows) == pytest.approx(
        6 * token * S + 3 * attn / 2 + 6 * 3 * d * 1408 * rows)
    # about 1.85 GFLOP a real token at this traffic's median length
    per_token = flops_moe.train_flops(DS, [S], rows) / S
    assert 1.7e9 < per_token < 2.0e9

    cost = flops_moe.experts_cost(DS, 5120, 1)
    assert cost["flops"] == pytest.approx(3 * 2 * 3 * d * 1408 * 5120)
    fwd_bytes = 2 * (3 * 8 * d * 1408 + 3 * 5120 * (d + 1408))
    assert cost["bytes"] == pytest.approx(3 * fwd_bytes)
    # 640 rows an expert: compute-bound on the v5e (240 FLOP/B)
    assert 350 < cost["flops"] / cost["bytes"] < 370
    cost = flops_moe.dispatch_cost(DS, 1000, 750)
    assert cost["bytes"] == pytest.approx(2 * 2 * (2 * 1000 * d
                                                   + 4 * 750 * d))
    assert cost["flops"] == pytest.approx(6 * 1000 * d * 64)
    share = flops.roofline_share(cost, 1.0, harness.peaks("TPU v5 lite"))
    assert share["bound"] == "memory"
    assert math.isfinite(share["share_pct"])


def test_cell_file_and_its_configuration():
    cell = harness.cell(CELL)
    assert cell["driver"] == "train_moe" and cell["batch"] == 64
    assert (cell["pool_batches"], cell["check_steps"],
            cell["trace_steps"]) == (3, 3, 1)
    man = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = harness.workload_entry(man, CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "rl_batch_gsm8k"
    names = {m["name"] for m in harness.cell_metrics(man, entry, True)}
    assert names == {"train_mfu", "moe_experts_roofline",
                     "moe_dispatch_roofline"}
    assert DS["published"] == {"num_hidden_layers": 27,
                               "n_routed_experts": 64, "vocab_size": 102400}
