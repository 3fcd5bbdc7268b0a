"""The one traffic generator: reads a mix's parameters from its data file.

Lengths follow a clipped lognormal given by its median and sigma, as
``repro.loadgen.traces`` draws prompt lengths (``exp(log(median) + sigma *
z)``). Every seed gets the same multiset of lengths in another order: each
block of draws takes the stratified quantiles ``(i + 0.5) / n`` of the
distribution, and only the permutation, the token ids, the rewards and the
offsets come from the seed. So the work a run does is fixed by the mix, and
runs on different seeds differ only as much as two runs of one seed.

Token ids are drawn from ``[first_token_id, vocab)``: ids below it are the
tokenizer's PAD, BOS, EOS and SEP, and a prompt that ended in EOS would stop
its rollout at once.
"""
from __future__ import annotations

import json
import math
import pathlib
import statistics
from typing import Dict, Iterator, List, Tuple

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


def load(name: str) -> Dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per (seed, stream); any non-negative seed."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), *stream.encode()]))


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of the clipped lognormal ``spec``
    (``median``, ``sigma``, ``min``, ``max``), as whole lengths."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def shuffled_lengths(spec: Dict, n: int,
                     rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(quantile_lengths(spec, n))


def tokens(rng: np.random.Generator, n: int, mix: Dict,
           vocab: int) -> np.ndarray:
    return rng.integers(mix["first_token_id"], vocab, n).astype(np.int32)


# ------------------------------------------------------------- GRPO rollout
def rollout_groups(mix: Dict, seed: int, vocab: int
                   ) -> Iterator[Tuple[np.ndarray, List[int]]]:
    """Endless stream of GRPO groups: (prompt ids, one response budget per
    member). Each block of ``block_groups`` groups holds the same lengths,
    shuffled by the seed."""
    rng = rng_for(seed, "rollout")
    g, n = mix["group_size"], mix["block_groups"]
    while True:
        prompts = shuffled_lengths(mix["prompt_len"], n, rng)
        budgets = shuffled_lengths(mix["response_len"], n * g, rng)
        for i in range(n):
            yield (tokens(rng, int(prompts[i]), mix, vocab),
                   [int(b) for b in budgets[i * g:(i + 1) * g]])


# ----------------------------------------------------------- trainer batches
def rl_batches(mix: Dict, seed: int, n_batches: int, batch: int,
               vocab: int) -> List[Dict[str, np.ndarray]]:
    """Rollout-shaped a3po batches of ``batch`` sequences (whole groups),
    padded to ``pad_to`` positions as ``assemble_train_batch`` pads to the
    longest possible sequence.

    Each row is a prompt (shared by its group) followed by the member's own
    response, then PAD. ``response_mask`` marks the positions that predict
    response tokens. ``behav_offset`` is the seeded offset of the behavior
    policy from the current one: the driver adds it to the policy's own
    log-probs. Versions are drawn in ``[0, max_staleness]`` per sequence,
    rewards are 0/1 per sequence.
    """
    rng = rng_for(seed, "rl_batch")
    g, T = mix["group_size"], mix["pad_to"]
    if batch % g:
        raise ValueError(f"batch {batch} is not whole groups of {g}")
    out = []
    for _ in range(n_batches):
        n_groups = batch // g
        p_lens = shuffled_lengths(mix["prompt_len"], n_groups, rng)
        r_lens = shuffled_lengths(mix["response_len"], batch, rng)
        toks = np.zeros((batch, T), np.int32)      # PAD = 0
        mask = np.zeros((batch, T - 1), np.float32)
        for gi in range(n_groups):
            prompt = tokens(rng, int(p_lens[gi]), mix, vocab)
            P = len(prompt)
            for j in range(g):
                b = gi * g + j
                R = int(r_lens[b])
                if P + R > T:
                    raise ValueError(f"sequence {P}+{R} exceeds {T}")
                toks[b, :P] = prompt
                toks[b, P:P + R] = tokens(rng, R, mix, vocab)
                mask[b, P - 1:P - 1 + R] = 1.0
        out.append(dict(
            tokens=toks, response_mask=mask,
            behav_offset=(mix["behav_offset_sigma"]
                          * rng.standard_normal(mask.shape)
                          ).astype(np.float32) * mask,
            versions=rng.integers(0, mix["max_staleness"] + 1,
                                  batch).astype(np.int32),
            rewards=rng.integers(0, 2, batch).astype(np.float32),
            lengths=(p_lens.repeat(g) + r_lens).astype(np.int64)))
    return out
