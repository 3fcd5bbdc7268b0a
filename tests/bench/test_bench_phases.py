"""The train step's phases: the classifier on the scope paths the program
writes, the scope map from compiled text, the phase readers on hand-built
traces, the arithmetic of their work at the cell's widths, and the compiled
text of the window's step on the CPU at a tiny size."""
import types

import pytest

from bench import flops, harness, phases, trace_reduce as tr, traffic

STEP = "jit(_train_step_impl)/while/body/closed_call/"
CELL = "train_a3po.qwen2.5-1.5b-l12"


@pytest.mark.parametrize("path,phase", [
    (STEP + "jvp(decoder)/while/body/closed_call/dot_general",
     "decoder.fwd"),
    (STEP + "decoder/while/body/closed_call/dot_general", "decoder.fwd"),
    (STEP + "transpose(jvp(decoder))/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general", "decoder.recompute"),
    (STEP + "transpose(jvp(decoder))/while/body/closed_call/mul",
     "decoder.bwd"),
    (STEP + "jvp(logprob_head)/jit(take_along_axis)", "logprob_head.fwd"),
    ("jvp(logprob_head)/reduce_sum", "logprob_head.fwd"),
    (STEP + "transpose(jvp(logprob_head))/td,dv->tv", "logprob_head.bwd"),
    (STEP + "transpose(jvp(logprob_head_bwd))/jit(token_logprob_entropy_"
     "bwd)/while/body/closed_call/dot_general", "logprob_head.bwd"),
    (STEP + "jvp(policy_loss)/jit(a3po_loss_pallas)", "policy_loss"),
    (STEP + "transpose(jvp(policy_loss))/mul", "policy_loss"),
    (STEP + "optimizer/mul", "optimizer"),
    ("optimizer/reduce_sum", "optimizer"),
    # the embedding's gradient and the step's own bookkeeping
    (STEP + "transpose(jvp(jit(_take)))", "other"),
    ("jit(_train_step_impl)/reduce_sum", "other"),
    (STEP + "jvp(my_decoder)/mul", "other"),
    (None, "other"),
])
def test_classify_scope_paths(path, phase):
    assert phases.classify(path) == phase


HLO = """\
HloModule jit__train_step_impl

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul.2 = f32[4]{0} multiply(%param_0, %param_0), \
metadata={op_name="inner" stack_frame_id=3}
}

%body.7 (p.1: (f32[4])) -> (f32[4]) {
  %p.1 = (f32[4]{0}) parameter(0)
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%p.1)
  ROOT %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, \
metadata={op_name="%sfwd" stack_frame_id=1}
  %convolution_add_fusion.8 = f32[4]{0} fusion(%fusion.1), kind=kOutput, \
metadata={op_name="%sbwd" stack_frame_id=2}
  %while.3 = (f32[4]) while(%t), condition=%cond.6, body=%body.7, \
metadata={op_name="%sloop"}
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, \
metadata={op_name="%sopt"}
  ROOT %copy.4 = f32[4]{0} copy(%fusion.9)
}
"""


def hlo(fwd=STEP + "jvp(decoder)/dot_general",
        bwd=STEP + "transpose(jvp(decoder))/dot_general",
        loop=STEP + "transpose(jvp(decoder))/while",
        opt=STEP + "optimizer/mul"):
    return HLO.replace('"%sfwd"', f'"{fwd}"').replace(
        '"%sbwd"', f'"{bwd}"').replace('"%sloop"', f'"{loop}"').replace(
        '"%sopt"', f'"{opt}"')


def test_scope_map_reads_instruction_names_and_op_names():
    m = phases.scope_map(hlo())
    assert m["fusion.1"] == STEP + "jvp(decoder)/dot_general"
    assert m["convolution_add_fusion.8"].endswith("transpose(jvp(decoder))"
                                                  "/dot_general")
    assert m["mul.2"] == "inner"
    assert m["while.3"].endswith("/while")
    # made by the compiler without a path: the loop that runs its body
    assert m["copy-start.1"] == m["copy-done.1"] == m["while.3"]
    # nothing runs the entry computation
    assert "copy.4" not in m and "p" not in m


def op(name, a, b):
    return (name, float(a), float(b), "")


def test_phase_seconds_leave_out_loops_and_average_chips():
    m = phases.scope_map(hlo())
    chip = [op("while.3", 0, 1e9), op("fusion.1", 0, 2e8),
            op("convolution_add_fusion.8", 2e8, 5e8),
            op("copy-done.1", 5e8, 6e8),
            op("fusion.9", 6e8, 7e8), op("copy.4", 7e8, 8e8)]
    t = tr.Trace(devices=[chip, chip[:2]], host=[])
    sec = phases.phase_seconds(t, m, 0, 1e9)
    assert set(sec) == set(phases.PHASES)
    assert sec["decoder.fwd"] == pytest.approx(0.2)
    assert sec["decoder.bwd"] == pytest.approx(0.2)      # 0.4 on one chip
    assert sec["optimizer"] == pytest.approx(0.05)
    assert sec["other"] == pytest.approx(0.05)
    assert sec["decoder.recompute"] == 0.0


# ----------------------------------------------------------- the arithmetic
@pytest.fixture(scope="module")
def cfg():
    return harness.config("qwen2.5-1.5b-l12")


def test_adam_bytes_at_the_cell_widths(cfg):
    d, L, ff, V = 1536, 12, 8960, 151936
    layer = (2 * d + d * 12 * 128 * 2 + d * 2 * 128 * 2
             + 12 * 128 + 2 * 2 * 128 + 3 * d * ff)
    n = V * d + d + L * layer
    assert n == 794_949_120
    # bf16 gradient twice, bf16 parameter in and out, f32 m and v in and out
    assert phases.adam_bytes(cfg) == n * (2 + 2 + 2 + 2 + 8 + 8)


def test_decoder_flops_at_the_cell_widths(cfg):
    layer = 1536 * 128 * (2 * 12 + 2 * 2) + 3 * 1536 * 8960
    assert layer == 46_792_704
    for lengths in ([429], [33, 1343]):
        want = sum(6.0 * 12 * layer * S + 6.0 * S * S * 12 * 128 * 12
                   for S in lengths)
        assert phases.decoder_flops(cfg, lengths) == pytest.approx(want)
        head = 6.0 * 1536 * 151936 * sum(lengths)
        assert phases.decoder_flops(cfg, lengths) + head == pytest.approx(
            flops.train_flops(cfg, lengths))


# ------------------------------------------------------------- the readers
def ctx_for(cfg, trace, lo, hi, steps):
    """A traced run's context after the driver: the trace, its window and
    the FLOPs of ``steps`` steps of the cell's batch."""
    c = types.SimpleNamespace(
        workload=CELL, seed=2 ** 33 + 3, chips=1, cfg=cfg,
        cell=harness.cell(CELL), mix=traffic.load("rl_batch_gsm8k"),
        peaks=harness.peaks("TPU v5 lite"), data={}, logged=[])
    c.log = c.logged.append
    lengths = traffic.rl_batches(c.mix, c.seed, 1, c.cell["batch"],
                                 cfg["vocab_size"])[0]["lengths"]
    c.data.update(trace=trace, lo=lo, hi=hi, busy_s=(hi - lo) / 1e9,
                  train_flops=steps * flops.train_flops(cfg, lengths))
    return c, lengths


reader = harness.reader


def test_decoder_mfu_and_optimizer_roofline_on_a_hand_built_trace(
        cfg, monkeypatch):
    monkeypatch.setattr(phases, "step_text", lambda ctx: hlo())
    chip = [op("while.3", 0, 4e9), op("fusion.1", 0, 1e9),
            op("convolution_add_fusion.8", 1e9, 3e9),
            op("fusion.9", 3e9, 3.1e9), op("copy.4", 3.1e9, 3.2e9)]
    c, lengths = ctx_for(cfg, tr.Trace(devices=[chip], host=[]), 0, 4e9,
                         steps=2)
    peaks = c.peaks
    want = 100 * 2 * phases.decoder_flops(cfg, lengths) / (
        3.0 * peaks["flops_bf16"])
    assert reader("decoder_mfu")(c) == pytest.approx(want)
    # 2 steps of 4 minibatches: 8 updates in 0.1 s
    want = 100 * 8 * phases.adam_bytes(cfg) / peaks["hbm_bytes_per_s"] / 0.1
    assert reader("optimizer_roofline")(c) == pytest.approx(want)
    line, = [m for m in c.logged if m.startswith("phases, device s")]
    assert "decoder.fwd 0.5000" in line and "optimizer 0.0500" in line


def test_phase_readers_read_nothing_without_scopes(cfg, monkeypatch):
    # a program without the scopes: the same instructions, other op names
    plain = hlo(fwd="jit(_train_step_impl)/while/body/dot_general",
                bwd="jit(_train_step_impl)/while/body/transpose",
                opt="jit(_train_step_impl)/while/body/mul")
    monkeypatch.setattr(phases, "step_text", lambda ctx: plain)
    chip = [op("fusion.1", 0, 1e9), op("fusion.9", 1e9, 2e9)]
    for name in ("decoder_mfu", "optimizer_roofline"):
        c, _ = ctx_for(cfg, tr.Trace(devices=[chip], host=[]), 0, 2e9, 1)
        assert reader(name)(c) is None
    # and no trace, or no compiled step the window ran
    c, _ = ctx_for(cfg, None, 0, 1, 1)
    assert reader("decoder_mfu")(c) is None
    monkeypatch.setattr(phases, "step_text", lambda ctx: None)
    c, _ = ctx_for(cfg, tr.Trace(devices=[chip], host=[]), 0, 2e9, 1)
    assert reader("optimizer_roofline")(c) is None


# ---------------------------------------------- the window's compiled step
TINY = {"name": "tiny", "model_type": "qwen2", "hidden_act": "silu",
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0, "tie_word_embeddings": True,
        "torch_dtype": "float32"}


@pytest.fixture
def compile_log():
    """The harness's compile log; JAX's cache settings put back after."""
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


def test_step_text_is_the_program_the_window_ran(compile_log):
    import jax.numpy as jnp

    from bench.drivers import common
    from repro.configs.base import RLConfig
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.training import trainer as T

    mix = dict(traffic.load("rl_batch_gsm8k"), pad_to=48,
               prompt_len={"median": 8, "sigma": 0.4, "min": 4, "max": 16},
               response_len={"median": 16, "sigma": 0.5, "min": 4,
                             "max": 30})
    c = types.SimpleNamespace(cfg=TINY, mix=mix, chips=1, seed=5,
                              cell=dict(harness.cell(CELL), batch=8),
                              compile_log=compile_log, logged=[])
    c.log = c.logged.append
    b = traffic.rl_batches(mix, c.seed, 1, 8, TINY["vocab_size"])[0]
    mesh = common.local_mesh(1)
    with mesh, use_sharding(ShardingEnv(mesh)):
        trainer = T.Trainer(common.model_config(TINY),
                            RLConfig(group_size=mix["group_size"]), "a3po")
        state = trainer.init_state(common.seed_key(c.seed))
        batch = T.TrainBatch(
            tokens=jnp.asarray(b["tokens"]),
            response_mask=jnp.asarray(b["response_mask"]),
            behav_logp=jnp.asarray(-b["response_mask"]),
            versions=jnp.asarray(b["versions"]),
            rewards=jnp.asarray(b["rewards"]))
        for _ in range(2):
            state, _ = trainer.step(state, batch)
    text = phases.step_text(c)
    assert text is not None, c.logged
    found = {phases.classify(p) for p in phases.scope_map(text).values()}
    assert found == set(phases.PHASES)
    # another batch is another program: no map
    c.cell["batch"] = 4
    assert phases.step_text(c) is None
    assert "no scope map" in c.logged[-1]
