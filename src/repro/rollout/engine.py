"""Batched autoregressive rollout engine (the in-framework SGLang/vLLM).

``generate`` is a single jit'd program: prefill the (right-padded, ragged)
prompts, then a ``lax.scan`` over decode steps with sampling. It returns
the sequences, per-token behavior log-probs, and the response mask — plus
the policy version tag the async runtime stamps on every batch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RLConfig
from repro.data import tokenizer as tok
from repro.models import model as M
from repro.models.layers import logits_from_hidden
from repro.obs.tracing import span
from repro.rollout.sampler import fused_sample_step


@dataclasses.dataclass
class RolloutBatch:
    """One generation batch (host-side, numpy)."""

    tokens: np.ndarray         # [B, P + N] prompts + generations (PAD after EOS)
    prompt_lengths: np.ndarray  # [B]
    gen_logp: np.ndarray       # [B, N] behavior logp of generated tokens
    gen_mask: np.ndarray       # [B, N] 1.0 up to & including EOS
    version: int = 0           # behavior policy version (stamped by caller)
    rewards: Optional[np.ndarray] = None  # [B] attached after verification
    # [B, N] per-token weight versions when generation crossed a publish
    # (interruptible serving); None => every token was sampled at `version`
    gen_versions: Optional[np.ndarray] = None

    @property
    def batch_size(self) -> int:
        return self.tokens.shape[0]

    def min_version(self) -> int:
        """Oldest behavior version in the batch (staleness gate input)."""
        if self.gen_versions is None:
            return self.version
        stamped = self.gen_versions[self.gen_mask > 0]
        return int(stamped.min()) if stamped.size else self.version


@functools.partial(jax.jit, static_argnames=("cfg", "max_new", "temperature",
                                             "top_p", "greedy"))
def _generate_jit(params, cfg: ModelConfig, prompts, prompt_lengths, key,
                  max_new: int, temperature: float, top_p: float,
                  greedy: bool = False):
    B, P = prompts.shape
    hidden, cache = M.prefill(params, cfg, prompts, lengths=prompt_lengths,
                              max_len=P + max_new)
    last_h = jnp.take_along_axis(
        hidden, (prompt_lengths - 1)[:, None, None].astype(jnp.int32),
        axis=1)[:, 0]
    logits = logits_from_hidden(params["embedding"], last_h, cfg)

    def step(carry, key_t):
        logits, cache, done = carry
        token, logp, mask, done = fused_sample_step(
            logits, key_t, done, temperature=temperature, top_p=top_p,
            greedy=greedy)
        logits, cache = M.decode_step(params, cfg, cache, token)
        return (logits, cache, done), (token, logp, mask)

    keys = jax.random.split(key, max_new)
    done0 = jnp.zeros((B,), bool)
    (_, _, _), (tokens, logps, masks) = jax.lax.scan(
        step, (logits, cache, done0), keys)
    return tokens.T, logps.T, masks.T  # [B, N]


class RolloutEngine:
    """Holds generation settings; weights are passed per call (the async
    runtime swaps them under us, exactly like an inference engine receiving
    weight updates)."""

    def __init__(self, cfg: ModelConfig, rl: Optional[RLConfig] = None,
                 max_new_tokens: int = 16):
        self.cfg = cfg
        self.rl = rl or RLConfig()
        self.max_new_tokens = max_new_tokens

    def generate(self, params, prompts: np.ndarray,
                 prompt_lengths: np.ndarray, key, *, version: int = 0,
                 greedy: bool = False) -> RolloutBatch:
        with span("rollout_generate", batch=int(prompts.shape[0]),
                  max_new=self.max_new_tokens, version=version):
            toks, logps, masks = _generate_jit(
                params, self.cfg, jnp.asarray(prompts),
                jnp.asarray(prompt_lengths), key, self.max_new_tokens,
                self.rl.temperature, self.rl.top_p, greedy)
            toks = np.asarray(toks)
        B, P = prompts.shape
        full = np.concatenate([prompts, np.full_like(toks, tok.PAD)], axis=1)
        # place generated tokens right after each ragged prompt
        for b in range(B):
            L = int(prompt_lengths[b])
            full[b, L: L + toks.shape[1]] = toks[b]
        return RolloutBatch(
            tokens=full,
            prompt_lengths=np.asarray(prompt_lengths),
            gen_logp=np.asarray(logps),
            gen_mask=np.asarray(masks),
            version=version,
        )

    def completions(self, batch: RolloutBatch) -> list:
        """Decode generated token ids (up to EOS) per sequence."""
        out = []
        N = batch.gen_logp.shape[1]
        for b in range(batch.batch_size):
            L = int(batch.prompt_lengths[b])
            out.append(batch.tokens[b, L: L + N])
        return out
