"""Plain reference of DeepSeek-V2-Lite's one-chip share, its a3po training
step and its weights.

Written from the configuration file alone (``bench/configs/<name>.json``);
it imports nothing of the program. What it shares with the Qwen2
reference it takes from ``bench.reference`` by import: the weight
recipe's keying and nesting, the float8 control, the a3po token loss,
group advantages, the staleness schedule, the trimming of a block's
positions and the training loop with its Adam (``Trainer`` below changes
only how one minibatch's gradient is taken).

The model follows the published description (DeepSeek-V2, arXiv
2405.04434, and the published ``config.json``):

* Multi-head latent attention without a query LoRA: the keys and values
  come from a rank-``kv_lora_rank`` latent, RMS-normed, plus one rotary
  key of ``qk_rope_head_dim`` shared by every head; softmax scale
  ``(qk_nope + qk_rope) ** -0.5 * mscale(factor, mscale_all_dim) ** 2``.
* YaRN rotary frequencies as ``DeepseekV2YarnRotaryEmbedding`` computes
  them (``yarn_find_correction_range`` over ``beta_fast`` / ``beta_slow``
  against ``original_max_position_embeddings``, a linear ramp between the
  interpolated and the kept bands), cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
* ``first_k_dense_replace`` dense SwiGLU layers of ``intermediate_size``,
  then expert layers: softmax over the router's outputs, greedy top-k,
  weights renormalised only with ``norm_topk_prob`` and scaled by
  ``routed_scaling_factor``; the shared experts on every token.
* Final RMSNorm and an untied head; the input embedding is not rescaled.

The share of the deployment the configuration states (``deployment``):
the router keeps its published outputs, this chip holds the experts
``experts_held`` = [first, first + n_routed_experts), each applied to the
tokens routed to it (its gate is zero on the others), and what the other
experts add is left out. The vocabulary is the slice the file gives.

Departures, each the system's and followed here: the rotary columns use
the rotate-half layout (DeepSeek stores them interleaved; a fixed
permutation of the columns, the same model with random weights); the
load-balance loss is the system's Switch-style ``E * sum_e f_e * P_e``
over the router's outputs and the real tokens (the positions through the
last one each sequence's loss reads), weight 0.01 per expert layer.

Arithmetic, the float8 control (``quant="fp8"``) and the planted fault
(``fault="half_batch"``) are those of ``bench.reference``. Rows are
blocked as there, longest first, and each block is cut after its last
real token rounded up to a multiple of ``TRIM``: few shapes, so few
compiles, and the programs are shared by every trainer of one
configuration in a process. The load-balance loss couples a minibatch's rows
(its assignment shares are counted over all of them), so each
minibatch's gradient is taken in two passes: the forward of every block
of rows, which counts the assignments, then each block's backward, with
those shares fixed (they carry no gradient).

Weight recipe: ``bench.reference``'s, over this model's leaves (the
program's names, the dense layers stacked under ``dense_blocks``).
"""
from __future__ import annotations

import json
import math
import zlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R

HIGHEST = R.HIGHEST
AUX_WEIGHT = 0.01
# a block's positions are cut to a multiple of this: 6 lengths at 1343
TRIM = 256


# ------------------------------------------------------------------ weights
def dims(cfg: Dict) -> Dict[str, int]:
    first, n = cfg["deployment"]["experts_held"]
    if n != cfg["n_routed_experts"]:
        raise ValueError("experts_held must count n_routed_experts experts")
    return dict(
        d=cfg["hidden_size"], L=cfg["num_hidden_layers"],
        k=cfg["first_k_dense_replace"], H=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], r=cfg["kv_lora_rank"],
        ff_dense=cfg["intermediate_size"], ff=cfg["moe_intermediate_size"],
        E=cfg["deployment"]["router_outputs"], first=first, n=n,
        top_k=cfg["num_experts_per_tok"], shared=cfg["n_shared_experts"],
        V=cfg["vocab_size"])


def leaf_specs(cfg: Dict) -> Dict[str, tuple]:
    """path -> (shape, init, std) of every weight."""
    s = dims(cfg)
    d, H = s["d"], s["H"]

    def normal(*shape):
        return (shape, "normal", float(np.prod(shape[:-1])) ** -0.5)

    def block(prefix, n):
        return {
            f"{prefix}/ln1/scale": ((n, d), "ones", None),
            f"{prefix}/ln2/scale": ((n, d), "ones", None),
            f"{prefix}/attn/wq": normal(n, d, H, s["nope"] + s["rope"]),
            f"{prefix}/attn/w_dkv": normal(n, d, s["r"] + s["rope"]),
            f"{prefix}/attn/kv_norm/scale": ((n, s["r"]), "ones", None),
            f"{prefix}/attn/w_uk": normal(n, s["r"], H, s["nope"]),
            f"{prefix}/attn/w_uv": normal(n, s["r"], H, s["v"]),
            f"{prefix}/attn/wo": normal(n, H, s["v"], d),
        }

    k, Lm, ff, sh = s["k"], s["L"] - s["k"], s["ff"], s["shared"] * s["ff"]
    specs = {
        "embedding/embed": ((s["V"], d), "normal", d ** -0.5),
        "embedding/out_head": normal(d, s["V"]),
        "final_norm/scale": ((d,), "ones", None),
        **block("blocks", Lm),
        "blocks/ffn/router": normal(Lm, d, s["E"]),
        "blocks/ffn/w_gate": normal(Lm, s["n"], d, ff),
        "blocks/ffn/w_up": normal(Lm, s["n"], d, ff),
        "blocks/ffn/w_down": normal(Lm, s["n"], ff, d),
        "blocks/ffn/shared/w_gate": normal(Lm, d, sh),
        "blocks/ffn/shared/w_up": normal(Lm, d, sh),
        "blocks/ffn/shared/w_down": normal(Lm, sh, d),
    }
    if k:
        specs.update(block("dense_blocks", k))
        specs.update({
            "dense_blocks/ffn/w_gate": normal(k, d, s["ff_dense"]),
            "dense_blocks/ffn/w_up": normal(k, d, s["ff_dense"]),
            "dense_blocks/ffn/w_down": normal(k, s["ff_dense"], d)})
    return specs


def make_weights(cfg: Dict, key: jax.Array) -> Dict:
    """The random weights of ``bench.reference``'s recipe over
    ``leaf_specs``, as a nested dict."""
    specs = leaf_specs(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])

    @jax.jit
    def make(key):
        flat = {}
        for path, (shape, init, std) in specs.items():
            if init == "ones":
                flat[path] = jnp.ones(shape, dtype)
            else:
                k = jax.random.fold_in(key, zlib.crc32(path.encode())
                                       % (2 ** 31))
                flat[path] = (jax.random.normal(k, shape, jnp.float32)
                              * std).astype(dtype)
        return R._nest(flat)

    return make(key)


# --------------------------------------------------------------------- yarn
def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: Dict) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies over the rotary dims."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = freq_extra / rs["factor"]

    def correction_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32)


def softmax_scale(cfg: Dict) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


# -------------------------------------------------------------------- model
class Model(R.Model):
    """Forward pieces; products, norms and the float8 control are
    ``bench.reference.Model``'s."""

    def __init__(self, cfg: Dict, *, precision=HIGHEST,
                 quant: Optional[str] = None):
        super().__init__(cfg, precision=precision, quant=quant)
        self.s = dims(cfg)
        self.inv_freq = yarn_inv_freq(cfg)
        rs = cfg["rope_scaling"]
        self.cs = (yarn_get_mscale(rs["factor"], rs["mscale"])
                   / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]))
        self.scale = softmax_scale(cfg)

    def rope(self, x, pos):
        """Rotate-half rotary embedding of x [B, S, h, dim] at YaRN's
        frequencies."""
        half = x.shape[-1] // 2
        ang = pos.astype(jnp.float32)[..., None] * self.inv_freq
        cos = jnp.cos(ang)[..., None, :] * self.cs
        sin = jnp.sin(ang)[..., None, :] * self.cs
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1)

    def attention(self, a, h, pos):
        """Latent attention of h [B, S, d] (causal)."""
        s = self.s
        q = self.mm("bsd,dhk->bshk", h, a["wq"])
        q_nope, q_pe = q[..., :s["nope"]], self.rope(q[..., s["nope"]:], pos)
        dkv = self.mm("bsd,dr->bsr", h, a["w_dkv"])
        c = self.norm(dkv[..., :s["r"]], a["kv_norm"]["scale"])
        k_pe = self.rope(dkv[..., None, s["r"]:], pos)[:, :, 0]
        k_nope = self.mm("bsr,rhk->bshk", c, a["w_uk"])
        v = self.mm("bsr,rhk->bshk", c, a["w_uv"])
        sc = (self.mm("bqhk,bthk->bhqt", q_nope, k_nope)
              + self.mm("bqhp,btp->bhqt", q_pe, k_pe)) * self.scale
        causal = pos[:, None] >= pos[None, :]
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        o = self.mm("bhqt,bthk->bqhk", jax.nn.softmax(sc, axis=-1), v)
        return self.mm("bshk,hkd->bsd", o, a["wo"])

    def swiglu(self, f, h):
        u = jax.nn.silu(self.mm("bsd,df->bsf", h, f["w_gate"])) \
            * self.mm("bsd,df->bsf", h, f["w_up"])
        return self.mm("bsf,fd->bsd", u, f["w_down"])

    def experts(self, f, h):
        """The expert FFN of h [B, S, d]: (output, router probabilities
        [B, S, E], each position's assignments to the router's outputs
        [B, S, E])."""
        s = self.s
        probs = jax.nn.softmax(self.mm("bsd,de->bse", h, f["router"]),
                               axis=-1)
        top_w, top_i = jax.lax.top_k(probs, s["top_k"])
        if self.cfg["norm_topk_prob"]:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        top_w = top_w * self.cfg["routed_scaling_factor"]
        # each held expert's weight on each token: zero where not routed
        gate = jnp.einsum("bsj,bsje->bse", top_w, jax.nn.one_hot(
            top_i - s["first"], s["n"], dtype=jnp.float32))
        a = jax.nn.silu(self.mm("bsd,edf->bsef", h, f["w_gate"])) \
            * self.mm("bsd,edf->bsef", h, f["w_up"])
        y = self.mm("bsef,efd->bsd", a * gate[..., None], f["w_down"])
        y = y + self.swiglu(f["shared"], h)
        onehot = jnp.sum(jax.nn.one_hot(top_i, s["E"], dtype=jnp.float32),
                         axis=-2)
        return y, probs, onehot

    def layer(self, p, x, dense: bool, valid, fvec):
        """One decoder layer on x [B, S, d] at positions 0..S-1. Returns
        (x, the layer's load-balance term over the ``valid`` [B, S]
        positions with the assignment shares ``fvec`` [E] held fixed,
        the assignments of those positions to each router output [E])."""
        pos = jnp.arange(x.shape[1])
        x = x + self.attention(p["attn"], self.norm(x, p["ln1"]["scale"]),
                               pos)
        h = self.norm(x, p["ln2"]["scale"])
        if dense:
            return (x + self.swiglu(p["ffn"], h), jnp.zeros((), jnp.float32),
                    jnp.zeros((self.s["E"],), jnp.float32))
        y, probs, onehot = self.experts(p["ffn"], h)
        m = valid.astype(jnp.float32)[..., None]
        aux = jnp.sum(probs * m * fvec)
        return x + y, aux, jnp.sum(onehot * m, axis=(0, 1))

    def embed(self, E, tokens):
        return E[tokens].astype(jnp.float32)

    def logits(self, x_last, final_scale, W):
        """Final norm and the untied head: x [..., d] -> logits [..., V]."""
        return self.mm("...d,dv->...v", self.norm(x_last, final_scale), W)


def _stack_of(model: Model, l: int):
    k = model.s["k"]
    return ("dense_blocks", l) if l < k else ("blocks", l - k)


class Forward:
    """Jitted layer-by-layer forward over stacked weights (bf16 traffic:
    ``precision=DEFAULT``)."""

    def __init__(self, model: Model):
        self.m = model
        self._layer = {dense: jax.jit(
            lambda blocks, l, x, valid, dense=dense: model.layer(
                R._layer_slice(blocks, l), x, dense, valid,
                jnp.zeros((model.s["E"],), jnp.float32)))
            for dense in (True, False)}
        self._embed = jax.jit(model.embed)

    def hidden(self, w, tokens, valid):
        """tokens [B, S] -> (final residual stream [B, S, d], the
        assignments of the ``valid`` positions to the held experts, per
        expert layer [L - k])."""
        x = self._embed(w["embedding"]["embed"], jnp.asarray(tokens))
        valid = jnp.asarray(valid)
        held: List[float] = []
        s = self.m.s
        for l in range(s["L"]):
            stack, i = _stack_of(self.m, l)
            x, _, counts = self._layer[stack == "dense_blocks"](
                w[stack], i, x, valid)
            if stack == "blocks":
                held.append(float(jnp.sum(
                    counts[s["first"]:s["first"] + s["n"]])))
        return x, held


def input_needs(mask: np.ndarray) -> np.ndarray:
    """Input positions each row's loss reads: all up to the last one its
    mask keeps (none where it keeps none)."""
    S = mask.shape[-1]
    return np.max(np.where(mask > 0, np.arange(1, S + 1), 0), axis=-1)


def block_len(need: np.ndarray, S: int) -> int:
    """Positions a block of rows computes: through the last one any of its
    rows' loss reads (``need``), rounded up to a multiple of ``TRIM``, at
    most ``S``. The positions past a row's last real token carry no loss
    and are attended by no real position (causal)."""
    return min(S, -(-max(int(need.max()), 1) // TRIM) * TRIM)


# jitted programs by (configuration, RL settings, quant): one set a process
_PROGRAMS: Dict[tuple, Dict] = {}
_JITTED = ("_fwd", "_bwd", "_head_bwd", "_embed", "_embed_bwd", "_zeros",
           "_adam")


# ----------------------------------------------------------------- training
class Trainer(R.Trainer):
    """``bench.reference.Trainer`` with this model's minibatch gradient
    (``_grads``); its loop, advantages, alpha and Adam are unchanged."""

    def __init__(self, cfg: Dict, rl: Dict, *, rows_per_block: int = 4,
                 quant: Optional[str] = None, fault: Optional[str] = None):
        super().__init__(cfg, rl, rows_per_block=rows_per_block,
                         quant=quant, fault=fault)
        self.m = m = Model(cfg, quant=quant)
        E = m.s["E"]

        def fwd(blocks, l, x, valid, dense):
            return m.layer(R._layer_slice(blocks, l), x, dense, valid,
                           jnp.zeros((E,), jnp.float32))

        def bwd(blocks, acc, l, x, g, valid, fvec, dense):
            p = R._layer_slice(blocks, l)
            (_, aux), vjp = jax.vjp(
                lambda p, x: m.layer(p, x, dense, valid, fvec)[:2], p, x)
            gp, gx = vjp((g, jnp.ones((), jnp.float32)))
            acc = jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(
                    a, jax.lax.dynamic_index_in_dim(a, l, keepdims=False)
                    + u.astype(jnp.float32), l, 0), acc, gp)
            return acc, gx, aux

        def head(x, final_scale, W, targets, behav, alpha, adv, mask,
                 inv_denom):
            B, S, d = x.shape
            logp = R.token_logp(m, x.reshape(B * S, d), final_scale, W,
                                targets.reshape(-1)).reshape(B, S)
            tok = R.a3po_token_loss(logp, behav, alpha, adv, mask,
                                    rl["clip_eps"], rl["behav_weight_cap"])
            return jnp.sum(tok) * inv_denom

        def head_bwd(x, final_scale, W, acc_fs, acc_W, *rest):
            loss, vjp = jax.vjp(
                lambda x, fs, W: head(x, fs, W, *rest), x, final_scale, W)
            gx, gfs, gW = vjp(jnp.ones((), jnp.float32))
            return loss, gx, acc_fs + gfs, acc_W + gW.astype(jnp.float32)

        def embed_bwd(acc_E, tokens, g):
            return acc_E.at[tokens.reshape(-1)].add(g.reshape(-1, m.d))

        self._fwd = jax.jit(fwd, static_argnames=("dense",))
        self._bwd = jax.jit(bwd, static_argnames=("dense",),
                            donate_argnums=(1,))
        self._head_bwd = jax.jit(head_bwd, donate_argnums=(3, 4))
        self._embed = jax.jit(m.embed)
        self._embed_bwd = jax.jit(embed_bwd, donate_argnums=(0,))
        key = (json.dumps(cfg, sort_keys=True), json.dumps(rl, sort_keys=True),
               quant)
        self.__dict__.update(_PROGRAMS.setdefault(
            key, {n: getattr(self, n) for n in _JITTED}))

    def _grads(self, w, mb: Dict[str, np.ndarray]):
        """Full f32 gradient of one minibatch's loss (the a3po loss plus
        the load-balance loss of every expert layer), and the loss."""
        m, s = self.m, self.m.s
        rows = np.arange(mb["tokens"].shape[0])
        if self.fault == "half_batch":
            rows = rows[: len(rows) // 2]
        mask = mb["mask"][rows]
        inv_denom = np.float32(1.0 / max(float(mask.sum()), 1.0))
        need = input_needs(mask)
        order = np.argsort(-need, kind="stable")
        rows, need = rows[order], need[order]
        layers = [_stack_of(m, l) for l in range(s["L"])]
        acc = self._zeros(w)

        # pass 1: every block's forward, counting the assignments
        blocks, counts = [], np.zeros((s["L"], s["E"]), np.float64)
        for r0 in range(0, len(rows), self.rows):
            sel, nd = rows[r0:r0 + self.rows], need[r0:r0 + self.rows]
            n = block_len(nd, mask.shape[1])
            toks = jnp.asarray(mb["tokens"][sel][:, :n + 1])
            valid = jnp.asarray(np.arange(n)[None, :] < nd[:, None])
            xs = [self._embed(w["embedding"]["embed"], toks[:, :-1])]
            for l, (stack, i) in enumerate(layers):
                x, _, c = self._fwd(w[stack], i, xs[-1], valid,
                                    dense=stack == "dense_blocks")
                xs.append(x)
                counts[l] += np.asarray(c, np.float64)
            blocks.append((sel, n, toks, valid, xs))
        # d(aux)/d(prob of e at a real token) = weight * E * f_e / T, with
        # f_e the share of the top-k assignments that go to e
        T = max(float(need.sum()), 1.0)
        fvec = (AUX_WEIGHT * s["E"] * counts / (T * s["top_k"]) / T
                ).astype(np.float32)

        # pass 2: each block's backward
        loss = 0.0
        for sel, n, toks, valid, xs in blocks:
            lb, g, acc["final_norm"]["scale"], acc["embedding"]["out_head"] \
                = self._head_bwd(
                    xs[-1], w["final_norm"]["scale"],
                    w["embedding"]["out_head"], acc["final_norm"]["scale"],
                    acc["embedding"]["out_head"], toks[:, 1:],
                    jnp.asarray(mb["behav"][sel][:, :n]),
                    jnp.asarray(mb["alpha"][sel][:, :n]),
                    jnp.asarray(mb["adv"][sel][:, :n]),
                    jnp.asarray(mb["mask"][sel][:, :n]), inv_denom)
            loss += float(lb)
            xs.pop()
            for l in reversed(range(s["L"])):
                stack, i = layers[l]
                acc[stack], g, aux = self._bwd(
                    w[stack], acc[stack], i, xs.pop(), g, valid,
                    jnp.asarray(fvec[l]), dense=stack == "dense_blocks")
                loss += float(aux)
            acc["embedding"]["embed"] = self._embed_bwd(
                acc["embedding"]["embed"], toks[:, :-1], g)
        return acc, loss
