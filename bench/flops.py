"""Operations and bytes from shapes: the arithmetic behind every share of a
peak the benchmark reports. Counted from the model's published sizes, the
same whatever implements them; recomputation (rematerialisation, a kernel
that recomputes its logits in the backward pass) is never counted.

A configuration is the dict of ``bench/configs/<name>.json``.
"""
from __future__ import annotations

from typing import Dict, Iterable


def dims(cfg: Dict) -> Dict[str, int]:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return dict(d=d, L=cfg["num_hidden_layers"], H=H,
                KV=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // H,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"])


def layer_matmul_params(cfg: Dict) -> int:
    """Weights of one decoder layer that enter a matrix product: the q, k,
    v and o projections and the three SwiGLU matrices (biases and norms
    do not)."""
    s = dims(cfg)
    attn = s["d"] * s["hd"] * (2 * s["H"] + 2 * s["KV"])
    return attn + 3 * s["d"] * s["ff"]


def head_params(cfg: Dict) -> int:
    s = dims(cfg)
    return s["d"] * s["V"]


def matmul_params(cfg: Dict) -> int:
    """Every weight a token meets in a matrix product, the output head
    included (counted once when it is tied to the embedding: the input
    lookup is a gather, not a product)."""
    return dims(cfg)["L"] * layer_matmul_params(cfg) + head_params(cfg)


def attention_flops_fwd(cfg: Dict, q_len: int, kv_len: int) -> float:
    """QK^T and PV for ``q_len`` queries against ``kv_len`` keys each, all
    layers, forward only: 4 * q * kv * H * hd * L."""
    s = dims(cfg)
    return 4.0 * q_len * kv_len * s["H"] * s["hd"] * s["L"]


def train_flops(cfg: Dict, lengths: Iterable[int]) -> float:
    """Model FLOPs of one forward and backward pass over sequences of the
    given real lengths: 6 per matmul weight per position, plus causal
    attention at each sequence's own length (S^2 / 2 pairs, 3 passes)."""
    n = matmul_params(cfg)
    total = 0.0
    for S in lengths:
        S = int(S)
        total += 6.0 * n * S + 3.0 * attention_flops_fwd(cfg, S, S) / 2.0
    return total


def decode_flops(cfg: Dict, contexts: Iterable[int]) -> float:
    """Model FLOPs of generating one token at each of the given context
    lengths (tokens attended, the new one included): 2 per matmul weight
    plus attention over the context."""
    n = matmul_params(cfg)
    return sum(2.0 * n + attention_flops_fwd(cfg, 1, int(c))
               for c in contexts)


# ------------------------------------------------------------------ kernels
def logprob_pass_cost(d: int, V: int, T: int) -> Dict[str, Dict[str, float]]:
    """The fused token-logprob kernel over ``T`` rows, by pass. Forward:
    logits ``2TdV``; backward: ``dh`` and ``dW`` at ``2TdV`` each (its
    recomputed logits not counted). Bytes: ``h`` and ``W`` in bf16 read
    once per pass, ``dh`` and ``dW`` written in bf16, and three f32 per-row
    outputs (logp, entropy, logZ)."""
    hbytes, wbytes = 2.0 * T * d, 2.0 * d * V
    return {"fwd": {"flops": 2.0 * T * d * V,
                    "bytes": hbytes + wbytes + 4.0 * T + 3 * 4.0 * T},
            "bwd": {"flops": 4.0 * T * d * V,
                    "bytes": 2 * (hbytes + wbytes) + 4 * 4.0 * T}}


def logprob_cost(d: int, V: int, T: int) -> Dict[str, float]:
    """Forward and backward of the fused token-logprob kernel together."""
    p = logprob_pass_cost(d, V, T)
    return {k: p["fwd"][k] + p["bwd"][k] for k in ("flops", "bytes")}


def paged_decode_cost(cfg: Dict, contexts: Iterable[int],
                      kv_bytes: int = 2) -> Dict[str, float]:
    """Paged decode attention for one token per context, every layer:
    read each context's K and V once (``2 * ctx * KV * hd`` elements per
    layer), the query and write the output. FLOPs ``4 * ctx * H * hd``
    per layer."""
    s = dims(cfg)
    flops = bytes_ = 0.0
    for c in contexts:
        c = int(c)
        flops += 4.0 * c * s["H"] * s["hd"] * s["L"]
        bytes_ += (2.0 * c * s["KV"] * s["hd"] * kv_bytes
                   + 2.0 * s["H"] * s["hd"] * kv_bytes) * s["L"]
    return {"flops": flops, "bytes": bytes_}


def roofline_share(cost: Dict[str, float], seconds: float,
                   peaks: Dict[str, float]) -> Dict[str, float]:
    """Least time the chip could take (the larger of FLOPs over peak FLOP/s
    and bytes over peak bandwidth) over the time measured, in percent,
    with the bound that sets it."""
    t_flops = cost["flops"] / peaks["flops_bf16"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return {"share_pct": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": bound}
