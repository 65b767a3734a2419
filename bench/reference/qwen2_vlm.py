"""Plain reference of the served VLM: vision stub, image projection and a
Qwen2-style decoder (pre-RMSNorm, GQA with half-rotation RoPE, SwiGLU,
tied output embedding), in straightforward ``jax.numpy`` and float32 at
``Precision.HIGHEST``.

It imports nothing of the program under test.  Weights come from
``bench/parts/vlm.py`` in the benchmark's own layout:

    enc_w (d, d)          vision stub: tanh(patches @ enc_w)
    img_proj (d, d)       image embeddings into the decoder
    embed (V, d)          token embedding, tied to the output head
    ln1, ln2 (L, d)       RMSNorm scales
    wq (L, d, H, hd)  wk, wv (L, d, K, hd)  wo (L, H, hd, d)
    w_gate, w_up (L, d, F)  w_down (L, F, d)
    final_norm (d,)

Departures from the published Qwen2 block, shared with the served
program and stated in the configuration file: no q/k/v bias, and the
RMSNorm epsilon the configuration gives.

``score`` runs one teacher-forced forward over an image, a prompt and the
served tokens, padded to a fixed length so one compiled program serves
every request, and returns the reference logits at the positions that
predicted each served token.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from reference.lowp import einsum


def _rmsnorm(x, scale, eps):
    """In float32, whatever the stream's type; the result in its type."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _rope(x, theta):
    """x: (T, heads, hd); rotate the two halves of hd by position."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _layer(h, w, *, n_kv_groups, theta, eps, mm):
    T = h.shape[0]
    x = _rmsnorm(h, w["ln1"], eps)
    q = _rope(mm("td,dhk->thk", x, w["wq"]), theta)
    k = _rope(mm("td,dhk->thk", x, w["wk"]), theta)
    v = mm("td,dhk->thk", x, w["wv"])
    k = jnp.repeat(k, n_kv_groups, axis=1)
    v = jnp.repeat(v, n_kv_groups, axis=1)
    s = mm("shk,thk->hst", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s.astype(jnp.float32),
                                 -jnp.inf), axis=-1)
    h = h + mm("thk,hkd->td", mm("hst,thk->shk", p, v), w["wo"])
    x = _rmsnorm(h, w["ln2"], eps)
    g = mm("td,df->tf", x, w["w_gate"])
    u = mm("td,df->tf", x, w["w_up"])
    return h + mm("tf,fd->td", jax.nn.silu(g) * u, w["w_down"])


def logits_at(weights, image, tokens, positions, *, sizes,
              low: bool = False):
    """Reference logits (n, V) in float32 at ``positions`` of the
    sequence [image prefix; tokens]; ``tokens`` is padded to a fixed
    length, and causal attention keeps the padding from reaching any
    earlier position.  Every matmul runs at ``Precision.HIGHEST``; with
    ``low`` every matmul's operands are first rounded to fp8 (the
    control)."""
    mm = partial(einsum, precision=jax.lax.Precision.HIGHEST, low=low)
    W = weights
    img = jnp.tanh(mm("nd,de->ne", image, W["enc_w"]))
    img = mm("nd,de->ne", img, W["img_proj"])
    h = jnp.concatenate([img, W["embed"][tokens]], axis=0)
    per_layer = {k: W[k] for k in ("ln1", "wq", "wk", "wv", "wo", "ln2",
                                   "w_gate", "w_up", "w_down")}
    groups = sizes["num_attention_heads"] // sizes["num_key_value_heads"]

    def body(h, w):
        return _layer(h, w, n_kv_groups=groups, theta=sizes["rope_theta"],
                      eps=sizes["rms_norm_eps"], mm=mm), None

    h, _ = jax.lax.scan(body, h, per_layer)
    h = _rmsnorm(h, W["final_norm"], sizes["rms_norm_eps"])[positions]
    return mm("nd,vd->nv", h, W["embed"]).astype(jnp.float32)


@partial(jax.jit, static_argnames=("sizes_items", "control"))
def score(weights, image, tokens, positions, served, valid, *, sizes_items,
          control: bool = False):
    """Teacher-forced reference over one request.

    Returns per position: the gap by which the served token's float32
    reference logit lies below the reference's best, and, with
    ``control``, the same gap for the token that an fp8 copy of the
    reference puts first at that position (the lower-precision control).
    Positions where ``valid`` is False read 0.
    """
    sizes = dict(sizes_items)
    ref = logits_at(weights, image, tokens, positions, sizes=sizes)
    best = ref.max(axis=-1)
    served_gap = best - jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
    served_gap = jnp.where(valid, served_gap, 0.0)
    if not control:
        return served_gap, jnp.zeros_like(served_gap)
    low = logits_at(weights, image, tokens, positions, sizes=sizes,
                    low=True)
    pick = jnp.argmax(low, axis=-1)
    ctrl_gap = best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
    return served_gap, jnp.where(valid, ctrl_gap, 0.0)
