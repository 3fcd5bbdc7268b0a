"""Operations and bytes of the DeepSeek-V2 configuration from its shapes,
beside ``bench.flops`` (whose arithmetic reads Qwen2 shapes). Counted from
the published widths, whatever implements them; recomputation is never
counted. Routed experts are counted by the assignments the benchmark's own
forward pass gives the experts held here (``local_rows``), not by the
buffer a dispatch sizes for the worst case.

A configuration is the dict of ``bench/configs/<name>.json``.
"""
from __future__ import annotations

from typing import Dict, Iterable

from bench import reference_deepseek_v2 as RD


def mla_params(cfg: Dict) -> int:
    """Weights of one latent-attention block that enter a product: the
    query, the latent down-projection (with the rotary key), the key and
    value up-projections and the output."""
    s = RD.dims(cfg)
    d, H = s["d"], s["H"]
    return (d * H * (s["nope"] + s["rope"]) + d * (s["r"] + s["rope"])
            + s["r"] * H * (s["nope"] + s["v"]) + H * s["v"] * d)


def expert_params(cfg: Dict) -> int:
    """One routed expert's three SwiGLU matrices."""
    s = RD.dims(cfg)
    return 3 * s["d"] * s["ff"]


def token_params(cfg: Dict) -> int:
    """Weights every real token meets in a product, routed experts left
    out: attention in every layer, the dense layers' FFN, each expert
    layer's router and shared experts, and the untied head (the input
    lookup is a gather)."""
    s = RD.dims(cfg)
    n_moe = s["L"] - s["k"]
    return (s["L"] * mla_params(cfg) + s["k"] * 3 * s["d"] * s["ff_dense"]
            + n_moe * (s["d"] * s["E"] + 3 * s["d"] * s["shared"] * s["ff"])
            + s["d"] * s["V"])


def attention_flops_fwd(cfg: Dict, q_len: int, kv_len: int) -> float:
    """Scores over ``qk_nope + qk_rope`` and the weighted sum over ``v``
    for ``q_len`` queries against ``kv_len`` keys each, every layer,
    forward only."""
    s = RD.dims(cfg)
    return (2.0 * q_len * kv_len * s["H"] * (s["nope"] + s["rope"] + s["v"])
            * s["L"])


def train_flops(cfg: Dict, lengths: Iterable[int], local_rows: float
                ) -> float:
    """Model FLOPs of one forward and backward pass over sequences of the
    given real lengths whose tokens give ``local_rows`` assignments to the
    experts held here (over every expert layer): 6 per weight a token
    meets, 6 per routed expert weight per assignment, and causal attention
    at each sequence's own length (S^2 / 2 pairs, 3 passes)."""
    n = token_params(cfg)
    total = 6.0 * expert_params(cfg) * float(local_rows)
    for S in lengths:
        S = int(S)
        total += 6.0 * n * S + 3.0 * attention_flops_fwd(cfg, S, S) / 2.0
    return total


def experts_cost(cfg: Dict, local_rows: float, passes: int
                 ) -> Dict[str, float]:
    """The grouped matmuls of ``passes`` calls of the expert layer (layers
    x minibatches x steps) over ``local_rows`` assignments in all, forward
    and backward. FLOPs: ``2 * 3 * d * ff`` a row forward, twice that
    backward. Bytes, bf16: each of the three products reads its input
    rows and its weights and writes its output rows once forward; the
    backward's two products per matrix (input gradient, weight gradient)
    move twice that."""
    s = RD.dims(cfg)
    d, ff = s["d"], s["ff"]
    fwd_flops = 2.0 * 3 * d * ff * local_rows
    fwd_bytes = 2.0 * (3.0 * s["n"] * d * ff * passes
                       + 3.0 * local_rows * (d + ff))
    return {"flops": 3.0 * fwd_flops, "bytes": 3.0 * fwd_bytes}


def dispatch_cost(cfg: Dict, tokens: float, local_rows: float
                  ) -> Dict[str, float]:
    """Routing, the gather into expert order and the weighted combine over
    ``tokens`` real tokens (summed over every expert-layer call) and
    ``local_rows`` assignments, forward and backward. Bytes, bf16: the
    router reads each token; the gather reads and writes each assigned
    row; the combine reads each assigned row and writes each token; the
    backward moves the same rows again. FLOPs: the router's product, 3
    passes."""
    s = RD.dims(cfg)
    d = s["d"]
    fwd_bytes = 2.0 * (2.0 * tokens * d + 4.0 * local_rows * d)
    return {"flops": 3.0 * 2.0 * tokens * d * s["E"],
            "bytes": 2.0 * fwd_bytes}
