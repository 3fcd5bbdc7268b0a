"""Plain reference of the model, its a3po training step and its weights.

Written from the configuration alone; it imports nothing of the program
and takes nothing the program made. Weights are made here from the seed by
the recipe the system states for its random weights (below), so both sides
start from the same values without sharing an array.

The model is Qwen2 as published (pre-norm decoder, GQA with q/k/v biases,
rotate-half RoPE, SwiGLU, RMSNorm, tied embeddings), with one departure the
system under test makes and the reference therefore follows: the tied input
embedding is scaled by ``sqrt(hidden_size)``, because the random embedding
is drawn at std ``hidden_size ** -0.5`` for O(1) logits.

Arithmetic is float32 with matrix products at ``HIGHEST`` precision, run
one layer at a time (and, for training, a few rows at a time with the
backward pass taken layer by layer) so that it fits beside nothing else on
one chip. ``quant="fp8"`` makes every matrix product take its operands
(and, in the backward pass, its cotangent) through float8_e4m3 with a
per-tensor scale: the control one precision step below bfloat16.

Weight recipe: every leaf is keyed by ``fold_in(key, crc32(path) % 2**31)``
with ``path`` its slash-joined name; matrices are ``normal * std`` with
``std = prod(shape[:-1]) ** -0.5`` over the stored (layer-stacked) shape,
the embedding ``hidden_size ** -0.5``; norms are ones, biases zeros; all in
the configuration's dtype.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


# ------------------------------------------------------------------ weights
def leaf_specs(cfg: Dict) -> Dict[str, tuple]:
    """path -> (shape, init, std) of every weight."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    ff, V = cfg["intermediate_size"], cfg["vocab_size"]

    def normal(*shape):
        return (shape, "normal", float(np.prod(shape[:-1])) ** -0.5)

    return {
        "embedding/embed": ((V, d), "normal", d ** -0.5),
        "final_norm/scale": ((d,), "ones", None),
        "blocks/ln1/scale": ((L, d), "ones", None),
        "blocks/ln2/scale": ((L, d), "ones", None),
        "blocks/attn/wq": normal(L, d, H, hd),
        "blocks/attn/wk": normal(L, d, KV, hd),
        "blocks/attn/wv": normal(L, d, KV, hd),
        "blocks/attn/wo": normal(L, H, hd, d),
        "blocks/attn/bq": ((L, H, hd), "zeros", None),
        "blocks/attn/bk": ((L, KV, hd), "zeros", None),
        "blocks/attn/bv": ((L, KV, hd), "zeros", None),
        "blocks/ffn/w_gate": normal(L, d, ff),
        "blocks/ffn/w_up": normal(L, d, ff),
        "blocks/ffn/w_down": normal(L, ff, d),
    }


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        sub = out
        parts = path.split("/")
        for p in parts[:-1]:
            sub = sub.setdefault(p, {})
        sub[parts[-1]] = v
    return out


def flatten(tree: Dict, prefix: str = "") -> Dict[str, jax.Array]:
    out = {}
    for k in sorted(tree):
        v = tree[k]
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def make_weights(cfg: Dict, key: jax.Array) -> Dict:
    """The random weights of the recipe above, as a nested dict."""
    specs = leaf_specs(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])

    @jax.jit
    def make(key):
        flat = {}
        for path, (shape, init, std) in specs.items():
            if init == "ones":
                flat[path] = jnp.ones(shape, dtype)
            elif init == "zeros":
                flat[path] = jnp.zeros(shape, dtype)
            else:
                k = jax.random.fold_in(key, zlib.crc32(path.encode())
                                       % (2 ** 31))
                flat[path] = (jax.random.normal(k, shape, jnp.float32)
                              * std).astype(dtype)
        return _nest(flat)

    return make(key)


# ------------------------------------------------------------ lower precision
def _q8(x):
    """float8_e4m3 round trip with a per-tensor scale."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(eq, a, b):
    return jnp.einsum(eq, _q8(a), _q8(b), precision=HIGHEST)


def _fp8_fwd(eq, a, b):
    qa, qb = _q8(a), _q8(b)
    return jnp.einsum(eq, qa, qb, precision=HIGHEST), (qa, qb)


def _fp8_bwd(eq, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(_q8(g))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


# -------------------------------------------------------------------- model
class Model:
    """Forward pieces of the reference; ``precision`` of its products is
    ``HIGHEST`` for the reference, ``DEFAULT`` for traffic generation."""

    def __init__(self, cfg: Dict, *, precision=HIGHEST,
                 quant: Optional[str] = None):
        self.cfg = cfg
        self.d = cfg["hidden_size"]
        self.H = cfg["num_attention_heads"]
        self.KV = cfg["num_key_value_heads"]
        self.hd = cfg.get("head_dim") or self.d // self.H
        self.L = cfg["num_hidden_layers"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]
        self.precision = precision
        self.quant = quant
        if quant not in (None, "fp8"):
            raise ValueError(f"unknown quant {quant!r}")

    def mm(self, eq, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.quant == "fp8":
            return _fp8_einsum(eq, a, b)
        return jnp.einsum(eq, a, b, precision=self.precision)

    def norm(self, x, scale):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * scale.astype(jnp.float32)

    def rope(self, x, pos):
        half = self.hd // 2
        freqs = 1.0 / (self.theta ** (jnp.arange(half, dtype=jnp.float32)
                                      / half))
        ang = pos.astype(jnp.float32)[..., None] * freqs
        cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1)

    def embed(self, E, tokens):
        return E[tokens].astype(jnp.float32) * math.sqrt(self.d)

    def layer(self, p, x):
        """One decoder layer on x [B, S, d] at positions 0..S-1."""
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        B, S, _ = x.shape
        pos = jnp.arange(S)
        h = self.norm(x, p["ln1"]["scale"])
        a = p["attn"]
        q = self.mm("bsd,dhk->bshk", h, a["wq"]) + f32(a["bq"])
        k = self.mm("bsd,dhk->bshk", h, a["wk"]) + f32(a["bk"])
        v = self.mm("bsd,dhk->bshk", h, a["wv"]) + f32(a["bv"])
        q, k = self.rope(q, pos), self.rope(k, pos)
        G = self.H // self.KV
        k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
        s = self.mm("bqhk,bthk->bhqt", q, k) * self.hd ** -0.5
        causal = pos[:, None] >= pos[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = self.mm("bhqt,bthk->bqhk", jax.nn.softmax(s, axis=-1), v)
        x = x + self.mm("bshk,hkd->bsd", o, a["wo"])
        h = self.norm(x, p["ln2"]["scale"])
        f = p["ffn"]
        u = jax.nn.silu(self.mm("bsd,df->bsf", h, f["w_gate"])) \
            * self.mm("bsd,df->bsf", h, f["w_up"])
        return x + self.mm("bsf,fd->bsd", u, f["w_down"])

    def logits(self, x_last, final_scale, E):
        """Final norm and tied head: x [..., d] -> logits [..., V]."""
        return self.mm("...d,vd->...v", self.norm(x_last, final_scale), E)


def _layer_slice(blocks, l):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, l, keepdims=False), blocks)


class Forward:
    """Jitted layer-by-layer forward of ``Model`` over stacked weights."""

    def __init__(self, model: Model):
        self.m = model
        self._layer = jax.jit(lambda blocks, l, x: model.layer(
            _layer_slice(blocks, l), x))
        self._embed = jax.jit(model.embed)
        self._logits = jax.jit(model.logits)

    def logits(self, x, final_scale, E, chunk: int = 256) -> np.ndarray:
        """Logits [N, V] of the residual stream x [N, d], ``chunk`` rows
        at a time."""
        return np.concatenate([np.asarray(self._logits(
            x[i:i + chunk], final_scale, E))
            for i in range(0, x.shape[0], chunk)])

    def hidden(self, w, tokens) -> jax.Array:
        """tokens [B, S] -> final residual stream [B, S, d] (pre-norm)."""
        x = self._embed(w["embedding"]["embed"], jnp.asarray(tokens))
        for l in range(self.m.L):
            x = self._layer(w["blocks"], l, x)
        return x


@functools.partial(jax.jit, static_argnames=("model", "chunk"))
def token_logp(model: Model, x, final_scale, E, targets, chunk: int = 512):
    """Log-prob of ``targets`` [N] from the residual stream x [N, d], the
    vocabulary in f32, ``chunk`` rows at a time (rematerialised)."""
    N = x.shape[0]
    pad = (-N) % chunk
    xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, chunk, x.shape[1])
    ts = jnp.pad(targets, (0, pad)).reshape(-1, chunk)

    @jax.checkpoint
    def one(args):
        xc, tc = args
        lg = model.logits(xc, final_scale, E)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0] - lse

    return jax.lax.map(one, (xs, ts)).reshape(-1)[:N]


# ----------------------------------------------------------------- training
@jax.jit
def leaf_sqnorms(tree):
    """Squared f32 norm of every leaf."""
    return jax.tree.map(lambda a: jnp.sum(jnp.square(a.astype(jnp.float32))),
                        tree)


@jax.jit
def sqdist(a, b):
    """Squared f32 distance of two arrays."""
    return jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))


def group_advantages(rewards: np.ndarray, group: int) -> np.ndarray:
    g = rewards.reshape(-1, group).astype(np.float64)
    adv = (g - g.mean(1, keepdims=True)) / (g.std(1, keepdims=True) + 1e-6)
    return adv.reshape(-1).astype(np.float32)


def alpha_inverse(d: np.ndarray) -> np.ndarray:
    """The paper's staleness schedule: 0 at d = 0, else 1/d."""
    d = np.maximum(d.astype(np.float32), 0.0)
    return np.where(d < 1.0, 0.0, 1.0 / np.maximum(d, 1.0)).astype(
        np.float32)


def a3po_token_loss(logp, behav, alpha, adv, mask, clip_eps, iw_cap):
    """Negated, masked A-3PO objective per token (paper Eq. 3-4)."""
    prox = jax.lax.stop_gradient(alpha * behav + (1.0 - alpha) * logp)
    iw = jax.lax.stop_gradient(jnp.minimum(jnp.exp(prox - behav), iw_cap))
    ratio = jnp.exp(logp - prox)
    obj = jnp.minimum(ratio * adv,
                      jnp.clip(ratio, 1.0 - clip_eps, 1.0 + clip_eps) * adv)
    return -iw * obj * mask


TRIM = 128


def _trimmed(mask: np.ndarray) -> int:
    """Prediction positions a block of rows needs: through its last masked
    one, rounded up to a multiple of ``TRIM`` (at most all of them)."""
    cols = np.nonzero(mask.any(axis=0))[0]
    last = int(cols[-1]) + 1 if len(cols) else 1
    return min(mask.shape[1], -(-last // TRIM) * TRIM)


class Trainer:
    """The a3po training step of the reference: group-normalised
    advantages, ``num_minibatches`` sequential minibatch updates with the
    log-linear proximal anchor, Adam with global-norm clipping, f32
    moments and params stored in the configuration's dtype.

    ``fault="half_batch"`` leaves out the second half of every minibatch's
    rows and takes the mean over the rest: a planted fault, read on the
    chip and in the tests against the sound reference.
    """

    def __init__(self, cfg: Dict, rl: Dict, *, rows_per_block: int = 4,
                 quant: Optional[str] = None, fault: Optional[str] = None):
        self.m = Model(cfg, quant=quant)
        self.cfg, self.rl = cfg, rl
        self.rows = rows_per_block
        self.fault = fault
        if fault not in (None, "half_batch"):
            raise ValueError(f"unknown fault {fault!r}")
        m = self.m

        def layer_fwd(blocks, l, x):
            return m.layer(_layer_slice(blocks, l), x)

        def layer_bwd(blocks, acc, l, x, g):
            p = _layer_slice(blocks, l)
            _, vjp = jax.vjp(lambda p, x: m.layer(p, x), p, x)
            gp, gx = vjp(g)
            acc = jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(
                    a, jax.lax.dynamic_index_in_dim(a, l, keepdims=False)
                    + u.astype(jnp.float32), l, 0), acc, gp)
            return acc, gx

        def head(x, final_scale, E, targets, behav, alpha, adv, mask,
                 inv_denom):
            B, S, d = x.shape
            logp = token_logp(m, x.reshape(B * S, d), final_scale, E,
                              targets.reshape(-1)).reshape(B, S)
            tok = a3po_token_loss(logp, behav, alpha, adv, mask,
                                  rl["clip_eps"], rl["behav_weight_cap"])
            return jnp.sum(tok) * inv_denom

        def head_bwd(x, final_scale, E, acc_fs, acc_E, *rest):
            loss, vjp = jax.vjp(
                lambda x, fs, E: head(x, fs, E, *rest), x, final_scale, E)
            gx, gfs, gE = vjp(jnp.ones((), jnp.float32))
            return loss, gx, acc_fs + gfs, acc_E + gE.astype(jnp.float32)

        def embed_bwd(acc_E, tokens, g):
            scale = math.sqrt(m.d)
            return acc_E.at[tokens.reshape(-1)].add(
                g.reshape(-1, m.d) * scale)

        self._embed = jax.jit(m.embed)
        self._layer_fwd = jax.jit(layer_fwd)
        self._layer_bwd = jax.jit(layer_bwd, donate_argnums=(1,))
        self._head_bwd = jax.jit(head_bwd, donate_argnums=(3, 4))
        self._embed_bwd = jax.jit(embed_bwd, donate_argnums=(0,))
        self._zeros = jax.jit(lambda w: jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), w))

        b1, b2, eps, lr = (rl["adam_b1"], rl["adam_b2"], rl["adam_eps"],
                           rl["learning_rate"])

        def adam(p, g, mo, v, scale, t):
            g = g * scale
            mo = b1 * mo + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
            step = lr * (mo / c1) / (jnp.sqrt(v / c2) + eps)
            return (p.astype(jnp.float32) - step).astype(p.dtype), mo, v

        self._adam = jax.jit(adam, donate_argnums=(0, 2, 3))

    # ---------------------------------------------------------------- grads
    def _grads(self, w, mb: Dict[str, np.ndarray]):
        """Full f32 gradient of one minibatch's loss, and the loss."""
        m = self.m
        rows = np.arange(mb["tokens"].shape[0])
        if self.fault == "half_batch":
            rows = rows[: len(rows) // 2]
        mask = mb["mask"][rows]
        inv_denom = np.float32(1.0 / max(float(mask.sum()), 1.0))
        acc = self._zeros(w)
        loss = 0.0
        for r0 in range(0, len(rows), self.rows):
            sel = rows[r0:r0 + self.rows]
            # positions past a block's last real token carry no loss and
            # are attended by no real position (causal): drop them, up to
            # a multiple of TRIM so that few shapes compile
            n = _trimmed(mb["mask"][sel])
            toks = jnp.asarray(mb["tokens"][sel][:, :n + 1])
            inp = toks[:, :-1]
            xs = [self._embed(w["embedding"]["embed"], inp)]
            for l in range(m.L):
                xs.append(self._layer_fwd(w["blocks"], l, xs[-1]))
            lb, g, acc["final_norm"]["scale"], acc["embedding"]["embed"] = \
                self._head_bwd(
                    xs[-1], w["final_norm"]["scale"],
                    w["embedding"]["embed"], acc["final_norm"]["scale"],
                    acc["embedding"]["embed"], toks[:, 1:],
                    jnp.asarray(mb["behav"][sel][:, :n]),
                    jnp.asarray(mb["alpha"][sel][:, :n]),
                    jnp.asarray(mb["adv"][sel][:, :n]),
                    jnp.asarray(mb["mask"][sel][:, :n]), inv_denom)
            loss += float(lb)
            xs.pop()
            for l in reversed(range(m.L)):
                acc["blocks"], g = self._layer_bwd(w["blocks"], acc["blocks"],
                                                   l, xs.pop(), g)
            acc["embedding"]["embed"] = self._embed_bwd(
                acc["embedding"]["embed"], inp, g)
        return acc, loss

    # ----------------------------------------------------------------- steps
    def run(self, w, batches: Sequence[Dict[str, np.ndarray]]) -> Dict:
        """Train ``len(batches)`` steps from weights ``w`` (consumed).

        Returns per-step losses (mean over minibatches, as the system
        reports them), the per-leaf norm of Adam's first moment after the
        first step, of the first step's first gradient, and the final
        weights."""
        rl = self.rl
        flat_w = flatten(w)
        mo = {k: jnp.zeros(v.shape, jnp.float32) for k, v in flat_w.items()}
        v2 = {k: jnp.zeros(v.shape, jnp.float32) for k, v in flat_w.items()}
        t = 0
        losses: List[float] = []
        m1 = g1 = None
        for step, batch in enumerate(batches):
            B = batch["tokens"].shape[0]
            nmb = rl["num_minibatches"]
            size = B // nmb
            adv = group_advantages(batch["rewards"], rl["group_size"])
            alpha = alpha_inverse(step - batch["versions"])
            mask = batch["response_mask"]
            full = dict(tokens=batch["tokens"], mask=mask,
                        behav=batch["behav_logp"],
                        adv=adv[:, None] * mask,
                        alpha=np.broadcast_to(alpha[:, None], mask.shape))
            step_losses = []
            for i in range(nmb):
                sl = slice(i * size, (i + 1) * size)
                mb = {k: np.ascontiguousarray(v[sl]) for k, v in full.items()}
                grads, loss = self._grads(_nest(flat_w), mb)
                step_losses.append(loss)
                flat_g = flatten(grads)
                sq = leaf_sqnorms(flat_g)
                if g1 is None:
                    g1 = {k: math.sqrt(float(s)) for k, s in sq.items()}
                gnorm = math.sqrt(sum(float(s) for s in sq.values()))
                scale = np.float32(min(1.0, rl["max_grad_norm"]
                                       / (gnorm + 1e-9)))
                t += 1
                for k in flat_w:
                    flat_w[k], mo[k], v2[k] = self._adam(
                        flat_w[k], flat_g.pop(k), mo[k], v2[k], scale,
                        np.float32(t))
                del grads
            losses.append(float(np.mean(step_losses)))
            if step == 0:
                m1 = {k: math.sqrt(float(s))
                      for k, s in leaf_sqnorms(mo).items()}
        return dict(losses=losses, m1=m1, g1=g1, weights=_nest(flat_w))
