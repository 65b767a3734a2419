"""Plain reference of the served Kimi-VL decoder: vision stub, image
projection and the DeepSeek-V3 block as published for
Kimi-VL-A3B-Instruct, in straightforward ``jax.numpy`` and float32 at
``Precision.HIGHEST``.

It imports nothing of the program under test.  Per layer:

* Multi-head latent attention, expanded: ``q = x W_q`` (no q low rank),
  split into a 128-wide part without position and a 64-wide rotary
  part; ``c_kv = RMSNorm(x W_dkv)`` (512); one rotary key shared by the
  heads, ``k_rope = RoPE(x W_kr)`` (64); per head ``k_nope = c_kv W_uk``
  and ``v = c_kv W_uv``; softmax scale 1/sqrt(128 + 64).  Attention is
  taken in blocks of queries, so a long sequence fits.
* Layer 0 (``first_k_dense_replace``): a SwiGLU of the dense width.
* Every later layer: sigmoid scores over all router outputs; the top-k
  by score plus ``e_score_correction_bias`` (selection only); the gates
  are those scores normalised over the k and times
  ``routed_scaling_factor``.  Only the held experts' part is computed
  (the chip's share of an expert-parallel layer), each expert a SwiGLU
  of the expert width, plus the shared experts as one SwiGLU.

Weights come from ``bench/parts/mla_moe_vlm.py`` in the benchmark's own
layout, the layers stacked in two groups as the model lays them out:

    enc_w, img_proj (d, d)     vision stub tanh(x @ enc_w), projection
    embed (V, d)  head (d, V)  final_norm (d,)
    dense, moe: each layer's attention, stacked over the group's layers
        ln1, ln2 (d,)  wq (d, H, nope+rope)  w_dkv (d, R)  kv_norm (R,)
        w_kr (d, r)  w_uk (R, H, nope)  w_uv (R, H, v)  wo (H, v, d)
    dense (the first ``first_k_dense_replace`` layers), besides:
        w_gate, w_up (d, Fd)  w_down (Fd, d)
    moe (every later layer), besides:
        router (d, N)  router_bias (N,)
        w_gate, w_up (E, d, f)  w_down (E, f, d)      the held experts
        shared_gate, shared_up (d, Fs)  shared_down (Fs, d)

Departures from the checkpoint, shared with the served program and
stated in the configuration file: RoPE rotates the two halves of the
rotary part (the checkpoint's interleaved pairs are a fixed permutation
of random rope columns), and the vision tower is the stub.

``score`` runs one teacher-forced forward over an image, a prompt and
the served tokens, padded to a fixed length so one compiled program
serves every request, and returns per-position gaps as the VLM
reference does, beside each position's routing margin: how far, in
score plus bias, the nearest held expert lies from the top-k boundary
in the MoE layer where it lies nearest.  A program that rounds its
activations (bfloat16 operands on the MXU) shifts router scores a
little, so at a small margin it may route a held expert differently
and move that token's result far; away from a near tie it routes as
the reference does.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from reference.lowp import fp8

Q_BLOCK = 256                  # queries per attention block
HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _matmul(rounding):
    """einsum at ``HIGHEST`` over operands rounded as ``rounding``
    says: None (float32), "fp8" (the control) or "bf16" (what one
    bfloat16 pass of the MXU multiplies)."""
    rnd = {None: None, "fp8": fp8, "bf16": _bf16}[rounding]

    def mm(spec, a, b):
        if rnd is not None:
            a, b = rnd(a), rnd(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    return mm


def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _rope(x, theta):
    """x: (T, ..., hd); rotate the two halves of hd by position."""
    T, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (T,) + (1,) * (x.ndim - 2) + (hd // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(x, g, u, d, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, g))
              * mm("td,df->tf", x, u), d)


def _attention(h, w, *, s, mm):
    T = h.shape[0]
    nope = s["qk_nope_head_dim"]
    x = _rmsnorm(h, w["ln1"], s["rms_norm_eps"])
    q = mm("td,dhk->thk", x, w["wq"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                               s["rope_theta"])], -1)
    ckv = _rmsnorm(mm("td,dr->tr", x, w["w_dkv"]), w["kv_norm"],
                   s["rms_norm_eps"])
    kr = _rope(mm("td,dr->tr", x, w["w_kr"]), s["rope_theta"])
    k_nope = mm("tr,rhk->thk", ckv, w["w_uk"])
    H = k_nope.shape[1]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kr[:, None, :], (T, H, kr.shape[-1]))], -1)
    v = mm("tr,rhk->thk", ckv, w["w_uv"])
    scale = 1.0 / math.sqrt(q.shape[-1])

    nb = -(-T // Q_BLOCK)
    qp = jnp.pad(q, ((0, nb * Q_BLOCK - T), (0, 0), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = mm("shk,thk->hst", qb, k) * scale
        mask = jnp.arange(T)[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return mm("hst,thk->shk", p, v)

    out = jax.lax.map(block, jnp.arange(nb))
    out = out.reshape(nb * Q_BLOCK, H, v.shape[-1])[:T]
    return h + mm("thk,hkd->td", out, w["wo"])


def _held_margin(v, idx, k, held):
    """Per token, the smallest gap in ``v`` (scores plus bias, (T, N))
    between a held expert and the top-k boundary: a chosen held expert
    above the best unchosen one, or the weakest chosen one above an
    unchosen held expert.  ``idx`` (T, k+1) is the top k+1 by ``v``,
    ``held`` (N,) marks the held experts."""
    top = jnp.take_along_axis(v, idx, axis=-1)
    chosen = jnp.any(jnp.arange(v.shape[-1]) == idx[:, :k, None], axis=1)
    drop = jnp.min(jnp.where(chosen & held, v, jnp.inf), -1) - top[:, k]
    enter = top[:, k - 1] - jnp.max(jnp.where(~chosen & held, v, -jnp.inf),
                                    -1)
    return jnp.minimum(drop, enter)


def _moe(x, w, *, s, first, mm, rmm):
    """The held experts' part of the routed layer, plus the shared
    experts, and each token's routing margin (``_held_margin``).
    ``first`` is the first held expert's id; ``rmm`` is the router's
    matmul, ``mm`` every other's."""
    k = s["num_experts_per_tok"]
    scores = jax.nn.sigmoid(rmm("td,dn->tn", x, w["router"]))
    v = scores + w["router_bias"]
    _, idx = jax.lax.top_k(v, k + 1)
    g = jnp.take_along_axis(scores, idx[:, :k], axis=-1)
    g = g / jnp.sum(g, -1, keepdims=True) * s["routed_scaling_factor"]
    E = w["w_gate"].shape[0]
    held = jnp.arange(first, first + E)
    is_held = (jnp.arange(v.shape[-1]) >= first) & (
        jnp.arange(v.shape[-1]) < first + E)
    margin = _held_margin(v, idx, k, is_held)
    gate = jnp.sum(jnp.where(idx[:, :k, None] == held, g[:, :, None], 0.0),
                   axis=1)                                      # (T, E)
    h = (jax.nn.silu(mm("td,edf->etf", x, w["w_gate"]))
         * mm("td,edf->etf", x, w["w_up"]))
    y = mm("etf,efd->etd", h, w["w_down"])                       # (E, T, d)
    out = jnp.sum(gate.T[:, :, None] * y, axis=0)
    return out + _swiglu(x, w["shared_gate"], w["shared_up"],
                         w["shared_down"], mm), margin


def logits_at(weights, image, tokens, positions, *, sizes):
    """Reference logits (n, V) in float32 at ``positions`` of the
    sequence [image prefix; tokens]; causal attention keeps the padding
    after the served tokens from reaching any earlier position."""
    return _forward(weights, image, tokens, positions, sizes=sizes)[0]


def _forward(weights, image, tokens, positions, *, sizes, rounding=None):
    """(logits (n, V), routing margin (n,)) at ``positions``, the
    matmuls' operands rounded as ``rounding`` says (``_matmul``): fp8
    rounds every one, bf16 all but the router's, which the program
    computes in float32 at ``HIGHEST``.  The margin is the smallest over
    the MoE layers."""
    s = sizes
    mm = _matmul(rounding)
    rmm = mm if rounding == "fp8" else _matmul(None)
    W = weights
    img = jnp.tanh(mm("nd,de->ne", image, W["enc_w"]))
    img = mm("nd,de->ne", img, W["img_proj"])
    h = jnp.concatenate([img, W["embed"][tokens]], axis=0)
    eps = s["rms_norm_eps"]

    def dense_layer(h, w):
        h = _attention(h, w, s=s, mm=mm)
        x = _rmsnorm(h, w["ln2"], eps)
        return h + _swiglu(x, w["w_gate"], w["w_up"], w["w_down"], mm), None

    def moe_layer(h, w):
        h = _attention(h, w, s=s, mm=mm)
        x = _rmsnorm(h, w["ln2"], eps)
        y, margin = _moe(x, w, s=s, first=s["first_held_expert"], mm=mm,
                         rmm=rmm)
        return h + y, margin

    h, _ = jax.lax.scan(dense_layer, h, W["dense"])
    h, margins = jax.lax.scan(moe_layer, h, W["moe"])
    h = _rmsnorm(h, W["final_norm"], eps)[positions]
    logits = mm("nd,dv->nv", h, W["head"]).astype(jnp.float32)
    return logits, jnp.min(margins, axis=0)[positions]


@partial(jax.jit, static_argnames=("sizes_items", "control"))
def score(weights, image, tokens, positions, served, valid, *, sizes_items,
          control: bool = False):
    """Teacher-forced reference over one request, per position: ``gap``,
    by which the served token's reference logit lies below the
    reference's best, and ``margin``, the routing margin there; with
    ``control`` also the same gap for the token that a copy of the
    reference with fp8 operands puts first (``control``) and for the
    token a copy with bfloat16 operands, the program's own rounding,
    puts first (``bf16``).  Invalid positions read 0."""
    sizes = dict(sizes_items)
    ref, margin = _forward(weights, image, tokens, positions, sizes=sizes)
    best = ref.max(axis=-1)

    def gap(tok):
        below = best - jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]
        return jnp.where(valid, below, 0.0)

    out = {"gap": gap(served), "margin": jnp.where(valid, margin, 0.0)}
    if control:
        for name, rounding in (("control", "fp8"), ("bf16", "bf16")):
            low, _ = _forward(weights, image, tokens, positions, sizes=sizes,
                              rounding=rounding)
            out[name] = gap(jnp.argmax(low, axis=-1))
    return out
