"""Plain reference of the served CLIP-style dual encoder and its two task
heads, in straightforward ``jax.numpy``.  It imports nothing of the
program under test; weights come from ``bench/parts/clip.py`` in the
benchmark's own layout (per tower: ``patch_proj`` or ``embed``, ``pos``,
stacked ``ln1_s ln1_b wq wk wv wo ln2_s ln2_b w_gate w_up w_down``, the
final norm and ``proj``; then ``logit_scale`` and ``cls_w``).

The served block, as the configuration file states: pre-LayerNorm
multi-head attention without biases, a gated MLP of four times the
width with tanh-approximated GELU, image tokens mean-pooled (no class
token), the text read at its last position, both embeddings
L2-normalised.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from reference.lowp import einsum


def _ln(x, s, b, eps):
    """In float32, whatever the stream's type; the result in its type."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * s + b).astype(x.dtype)


def _tower(h, w, *, heads, causal, eps, mm):
    S = h.shape[1]
    mask = jnp.tril(jnp.ones((S, S), bool)) if causal else jnp.ones((S, S), bool)

    def body(h, lw):
        x = _ln(h, lw["ln1_s"], lw["ln1_b"], eps)
        q = mm("bsd,dhk->bshk", x, lw["wq"])
        k = mm("bsd,dhk->bshk", x, lw["wk"])
        v = mm("bsd,dhk->bshk", x, lw["wv"])
        s = mm("bshk,bthk->bhst", q, k) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(mask[None, None], s.astype(jnp.float32),
                                     -jnp.inf), axis=-1)
        h = h + mm("bshk,hkd->bsd", mm("bhst,bthk->bshk", p, v), lw["wo"])
        x = _ln(h, lw["ln2_s"], lw["ln2_b"], eps)
        g = jax.nn.gelu(mm("bsd,df->bsf", x, lw["w_gate"]), approximate=True)
        u = mm("bsd,df->bsf", x, lw["w_up"])
        return h + mm("bsf,fd->bsd", g * u, lw["w_down"]), None

    keys = ("ln1_s", "ln1_b", "wq", "wk", "wv", "wo", "ln2_s", "ln2_b",
            "w_gate", "w_up", "w_down")
    h, _ = jax.lax.scan(body, h, {k: w[k] for k in keys})
    return h


def _normed(z):
    return z / jnp.linalg.norm(z, axis=-1, keepdims=True)


def image_embed(w, patches, *, heads, eps, mm):
    h = mm("bnd,de->bne", patches, w["patch_proj"]) + w["pos"][None]
    h = _tower(h, w, heads=heads, causal=False, eps=eps, mm=mm)
    h = _ln(h.mean(axis=1), w["ln_s"], w["ln_b"], eps)
    return _normed(mm("bd,de->be", h, w["proj"]))


def text_embed(w, ids, *, heads, eps, mm):
    h = w["embed"][ids] + w["pos"][None, :ids.shape[1]]
    h = _tower(h, w, heads=heads, causal=True, eps=eps, mm=mm)
    h = _ln(h, w["ln_s"], w["ln_b"], eps)
    return _normed(mm("bd,de->be", h[:, -1], w["proj"]))


@partial(jax.jit, static_argnames=("vheads", "theads", "eps", "low"))
def score(weights, patches, ids, *, vheads, theads, eps, low: bool = False):
    """Both heads' answers for a batch of images (and, for retrieval,
    their captions): classify logits (B, classes) and retrieval logits
    (B, captions per image), in float32 at ``Precision.HIGHEST``; with
    ``low``, every matmul's operands first rounded to fp8 (the
    control)."""
    mm = partial(einsum, precision=jax.lax.Precision.HIGHEST, low=low)
    zi = image_embed(weights["vision"], patches, heads=vheads, eps=eps, mm=mm)
    cls = mm("be,ec->bc", zi, weights["cls_w"])
    if ids is None:
        return cls, None
    B, C, S = ids.shape
    zt = text_embed(weights["text"], ids.reshape(B * C, S), heads=theads,
                    eps=eps, mm=mm).reshape(B, C, -1)
    return cls, jnp.exp(weights["logit_scale"]) * mm("be,bce->bc", zi, zt)
