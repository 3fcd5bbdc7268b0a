"""A whole run of each cell at a size a test run holds, on the CPU: the
harness's look for a chip is skipped, the rest of the run is driven, and
``correct`` comes out true for the program as it is and false with the
timed path broken underneath, and for the control (the reference one
precision lower, in the program's place) against the cell's limits."""
import dataclasses
import time

import numpy as np
import pytest

from bench import compare, harness

TINY = {"name": "tiny", "model_type": "qwen2", "hidden_act": "silu",
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0, "tie_word_embeddings": True,
        "torch_dtype": "float32"}
LENS = {"prompt_len": {"median": 8, "sigma": 0.4, "min": 4, "max": 16},
        "response_len": {"median": 16, "sigma": 0.5, "min": 4, "max": 40}}
TRAIN = "train_a3po.qwen2.5-1.5b-l12"
ROLL = "rollout_grpo.qwen2.5-1.5b"
OVERRIDES = {
    TRAIN: dict(config=TINY, mix=dict(LENS, pad_to=64),
                cell={"batch": 8, "reference_rows": 2}),
    # the rollout cell is not in BENCHMARK.json until the chip has given
    # the readings its limits come from; its driver runs here with limits
    # for this size: float32 sound runs read well under them, the control
    # (the reference at fp8) above them
    ROLL: dict(manifest=dict(harness.manifest(), workloads=[
                   {"name": ROLL, "config": "qwen2.5-1.5b",
                    "traffic": "grpo_rollout_gsm8k", "chips": 1}]),
               config=TINY,
               mix=dict(LENS, outstanding_groups=6, block_groups=6),
               cell={"max_seqs": 16, "n_blocks": 128,
                     "max_blocks_per_seq": 8, "warmup_of": 16,
                     "warmup_finished": 8, "check_finished": 4,
                     "check_inflight": 4,
                     "limits": {"logit_rel_l2": 0.01, "logp_gap": 0.01}}),
}


def limits(workload):
    return (OVERRIDES[workload]["cell"].get("limits")
            or harness.cell(workload)["limits"])


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


def run(workload, **extra):
    ov = dict(OVERRIDES[workload], **extra)
    return harness.run(workload, 2 ** 33 + 11, 0.5, False,
                       t_start=time.perf_counter(), overrides=ov,
                       require_chip=False)


@pytest.fixture(scope="module")
def sound():
    return {w: run(w, variants=["fp8"]) for w in (TRAIN, ROLL)}


@pytest.mark.parametrize("workload", [TRAIN, ROLL])
def test_sound_run_is_correct(sound, workload):
    out = sound[workload]
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("workload", [TRAIN, ROLL])
def test_control_fails_the_cell_limits(sound, workload):
    ok, checks = compare.judge(sound[workload]["variant_numbers"]["fp8"],
                               limits(workload))
    assert not ok, checks


def test_step_returning_its_state_unchanged_is_not_correct(monkeypatch):
    from repro.training import trainer as T

    step = T.Trainer.step

    def unchanged(self, state, batch):
        import jax
        import jax.numpy as jnp

        kept = jax.tree.map(jnp.copy, state)   # the step donates the state
        _, m = step(self, state, batch)
        return kept, m

    monkeypatch.setattr(T.Trainer, "step", unchanged)
    out = run(TRAIN)
    assert not out["correct"]
    assert out["checks"]["dparam_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    from repro.training import trainer as T

    step = T.Trainer.step

    def half(self, state, batch):
        # the second half of each minibatch's rows carries no loss: the
        # masked mean is taken over the rest
        mask = np.array(batch.response_mask)
        B = mask.shape[0]
        nmb = self.rl.num_minibatches
        rows = np.arange(B).reshape(nmb, B // nmb)[:, B // nmb // 2:]
        mask[rows.reshape(-1)] = 0.0
        return step(self, state, dataclasses.replace(
            batch, response_mask=mask))

    monkeypatch.setattr(T.Trainer, "step", half)
    assert not run(TRAIN)["correct"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.rollout import continuous as C

    horizon = C._paged_decode_horizon

    def altered(*a, **k):
        packed, *rest = horizon(*a, **k)
        # the first emitted token of every slot reaches the host changed
        tokens = packed[0, 0]
        packed = packed.at[0, 0].set(
            (tokens + 1) % a[1].vocab_size * (packed[2, 0] > 0)
            + tokens * (packed[2, 0] <= 0))
        return (packed, *rest)

    monkeypatch.setattr(C, "_paged_decode_horizon", altered)
    out = run(ROLL)
    assert not out["correct"]
    assert out["checks"]["logp_gap"]["value"] > \
        out["checks"]["logp_gap"]["limit"]
