"""Pins of the FLOP and byte arithmetic the benchmark's shares rest on."""
import json
import pathlib

import pytest

from bench import flops

CONFIGS = pathlib.Path(__file__).resolve().parents[2] / "bench" / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,total", [
    ("qwen2.5-1.5b-l12", 794_886_144),      # 794.9M
    ("qwen2.5-1.5b", 1_543_569_408),        # 1.5436e9
])
def test_matmul_params_include_the_head(name, total):
    c = cfg(name)
    assert flops.head_params(c) == 233_373_696     # 233.4M
    assert flops.layer_matmul_params(c) == 46_792_704
    assert flops.matmul_params(c) == total


def test_train_flops_are_six_per_weight_plus_causal_attention():
    c = cfg("qwen2.5-1.5b-l12")
    n = flops.matmul_params(c)
    S = 1000
    attn = 4.0 * S * S * 12 * 128 * 12          # fwd QK^T + PV, all pairs
    assert flops.train_flops(c, [S]) == pytest.approx(6 * n * S
                                                      + 3 * attn / 2)
    assert flops.train_flops(c, [S, 10]) == pytest.approx(
        flops.train_flops(c, [S]) + flops.train_flops(c, [10]))


def test_decode_flops_and_paged_bytes():
    c = cfg("qwen2.5-1.5b")
    n = flops.matmul_params(c)
    assert flops.decode_flops(c, [100]) == pytest.approx(
        2 * n + 4 * 100 * 12 * 128 * 28)
    cost = flops.paged_decode_cost(c, [100, 300])
    kv = 2 * 400 * 2 * 128 * 2 * 28              # K and V of 400 tokens
    q_o = 2 * (2 * 12 * 128 * 2 * 28)            # query in, output out
    assert cost["bytes"] == pytest.approx(kv + q_o)


def test_logprob_cost_and_roofline_bound():
    cost = flops.logprob_cost(1536, 151936, 21488)
    assert cost["flops"] == pytest.approx(6 * 21488 * 1536 * 151936)
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    share = flops.roofline_share(cost, 0.3, peaks)
    assert share["bound"] == "compute"
    assert share["share_pct"] == pytest.approx(
        100 * cost["flops"] / 197e12 / 0.3)
    mem = flops.roofline_share({"flops": 1.0, "bytes": 819e9}, 2.0, peaks)
    assert mem["bound"] == "memory" and mem["share_pct"] == pytest.approx(50)
