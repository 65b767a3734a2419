"""Encoder-decoder model (whisper-tiny family).

The conv audio frontend is a STUB per the assignment: ``input_specs``
provides precomputed frame embeddings (B, encoder_seq, d_model).  The
encoder is a bidirectional transformer; the decoder adds cross-attention
over the encoder output.  Positions are sinusoidal (parameter-free; the
real model's learned decoder table is documented as a stand-in choice in
DESIGN.md).

S2M3 view: the encoder is a modality-wise *encoder module*; the decoder
is the *task head module*.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.common.sharding import merge_rules
from repro.layers import attention as attn_lib
from repro.layers.embedding import embed_apply, embed_specs, head_apply
from repro.layers.initializers import WSpec, stack_specs
from repro.layers.mlp import mlp_apply, mlp_specs
from repro.layers.norms import apply_norm, norm_specs
from repro.layers.stack import scan_stack

F32 = jnp.float32


def _is_ws(x):
    return isinstance(x, WSpec)


def sinusoid(positions, d_model):
    """positions: (B, S) -> (B, S, d) float32 sinusoidal embedding."""
    half = d_model // 2
    freq = jnp.exp(-jnp.arange(half, dtype=F32) * (math.log(10000.0) / max(half - 1, 1)))
    ang = positions[..., None].astype(F32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _enc_block_specs(cfg):
    d = cfg.d_model
    return {
        "ln_attn": norm_specs(d, cfg.norm),
        "attn": attn_lib.attention_specs(d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),
        "ln_mlp": norm_specs(d, cfg.norm),
        "mlp": mlp_specs(d, cfg.d_ff),
    }


def _dec_block_specs(cfg):
    d = cfg.d_model
    return {
        "ln_self": norm_specs(d, cfg.norm),
        "self_attn": attn_lib.attention_specs(d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),
        "ln_cross": norm_specs(d, cfg.norm),
        "cross_attn": attn_lib.attention_specs(d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),
        "ln_mlp": norm_specs(d, cfg.norm),
        "mlp": mlp_specs(d, cfg.d_ff),
    }


def _enc_block(p, h, ctx, cfg):
    x = apply_norm(p["ln_attn"], h, cfg.norm, cfg.norm_eps)
    y, _ = attn_lib.attention_apply(
        p["attn"], x, positions=ctx["positions"], cfg=cfg, causal=False,
    )
    h = h + y
    x = apply_norm(p["ln_mlp"], h, cfg.norm, cfg.norm_eps)
    return h + mlp_apply(p["mlp"], x, cfg.act_fn)


def _dec_block(p, h, cache, ctx, cfg, enc_out, enc_positions):
    """cache: {self: {k,v}, cross: {k,v}} or None (train)."""
    h = ctx.get("constrain", lambda x: x)(h)
    mode = ctx["mode"]
    positions = ctx["positions"]
    B = h.shape[0]

    # --- self attention ---
    x = apply_norm(p["ln_self"], h, cfg.norm, cfg.norm_eps)
    if mode == "train":
        y, _ = attn_lib.attention_apply(p["self_attn"], x, positions=positions, cfg=cfg)
        new_self = None
    elif mode == "prefill":
        S = x.shape[1]
        y, (k, v) = attn_lib.attention_apply(p["self_attn"], x, positions=positions, cfg=cfg)
        new_self = {
            "k": cache["self"]["k"].at[:, :S].set(k.astype(cache["self"]["k"].dtype)),
            "v": cache["self"]["v"].at[:, :S].set(v.astype(cache["self"]["v"].dtype)),
        }
    else:
        lengths = ctx["lengths"]
        q, k_new, v_new = attn_lib.project_qkv(p["self_attn"], x, positions, cfg)
        k_c = attn_lib.cache_insert(cache["self"]["k"], k_new, lengths)
        v_c = attn_lib.cache_insert(cache["self"]["v"], v_new, lengths)
        T = k_c.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        kv_valid = kv_pos < (lengths + 1)[:, None]
        out = attn_lib.gqa_scores(
            q, k_c.astype(x.dtype), v_c.astype(x.dtype),
            q_positions=positions, kv_positions=kv_pos, causal=True,
            kv_valid=kv_valid,
        )
        y = attn_lib.output_proj(p["self_attn"], out, x.dtype)
        new_self = {"k": k_c, "v": v_c}
    h = h + y

    # --- cross attention ---
    x = apply_norm(p["ln_cross"], h, cfg.norm, cfg.norm_eps)
    if mode == "train":
        ck, cv = attn_lib.cross_kv_project(p["cross_attn"], enc_out, cfg)
        new_cross = None
    elif mode == "prefill":
        ck, cv = attn_lib.cross_kv_project(p["cross_attn"], enc_out, cfg)
        new_cross = {"k": ck.astype(cache["cross"]["k"].dtype),
                     "v": cv.astype(cache["cross"]["v"].dtype)}
    else:
        ck = cache["cross"]["k"].astype(x.dtype)
        cv = cache["cross"]["v"].astype(x.dtype)
        new_cross = {"k": cache["cross"]["k"], "v": cache["cross"]["v"]}
    y, _ = attn_lib.attention_apply(
        p["cross_attn"], x, positions=positions, cfg=cfg,
        cross_kv=(ck, cv), cross_positions=enc_positions,
    )
    h = h + y

    x = apply_norm(p["ln_mlp"], h, cfg.norm, cfg.norm_eps)
    h = h + mlp_apply(p["mlp"], x, cfg.act_fn)
    new_cache = None if mode == "train" else {"self": new_self, "cross": new_cross}
    return h, new_cache


def _encode(cfg, params, frames, compute_dtype, remat):
    B, S = frames.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h = frames.astype(compute_dtype)
    h = jnp.einsum("bsd,de->bse", h, params["audio_proj"]["w"].astype(compute_dtype))
    h = h + sinusoid(positions, cfg.d_model).astype(compute_dtype)
    ctx = {"positions": positions}

    def fn(lp, c, x_l):
        return _enc_block(lp, c, ctx, cfg), jnp.zeros((0,))

    h, _ = scan_stack(fn, params["encoder"], h, remat=remat)
    h = apply_norm(params["enc_norm"], h, cfg.norm, cfg.norm_eps)
    return h, positions


def build_encdec(cfg, mesh=None, rules=None, *, compute_dtype=jnp.bfloat16,
                 remat: str = "full"):
    from repro.models.api import ModelBundle, _constrainer, cross_entropy

    rules = merge_rules(rules if isinstance(rules, dict) else None)
    n_dec = cfg.n_layers

    specs: dict[str, Any] = {
        "audio_proj": {"w": WSpec((cfg.d_model, cfg.d_model), (None, "embed"))},
        "encoder": stack_specs(_enc_block_specs(cfg), cfg.n_encoder_layers),
        "enc_norm": norm_specs(cfg.d_model, cfg.norm),
        "embed": embed_specs(cfg.vocab_size, cfg.d_model),
        "decoder": stack_specs(_dec_block_specs(cfg), n_dec),
        "final_norm": norm_specs(cfg.d_model, cfg.norm),
    }
    # whisper ties decoder embedding and output head
    tied = True

    def _dec_embed(params, tokens, positions):
        h = embed_apply(params["embed"], tokens, dtype=compute_dtype)
        return h + sinusoid(positions, cfg.d_model).astype(compute_dtype)

    def _head(params, h):
        return head_apply(None, h, tied_table=params["embed"]["table"])

    def _run_decoder(params, h, ctx, cache, enc_out, enc_positions):
        def fn(lp, c, x_l, has_cache=cache is not None):
            hh, cc = _dec_block(lp, c[0], x_l if has_cache else None, ctx, cfg,
                                enc_out, enc_positions)
            return (hh, c[1]), (cc if has_cache else jnp.zeros((0,)))

        carry, ys = scan_stack(fn, params["decoder"], (h, jnp.zeros((), F32)),
                               xs=cache, remat=ctx["remat"])
        return carry[0], (ys if cache is not None else None)

    def loss_fn(params, batch):
        enc_out, enc_pos = _encode(cfg, params, batch["audio_frames"],
                                   compute_dtype, remat)
        B, S = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        ctx = {"mode": "train", "positions": positions, "lengths": None,
               "remat": remat, "constrain": _constrainer(mesh, rules)}
        h = _dec_embed(params, batch["tokens"], positions)
        h, _ = _run_decoder(params, h, ctx, None, enc_out, enc_pos)
        h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        logits = _head(params, h)
        loss = cross_entropy(logits, batch["targets"], batch["mask"])
        return loss, {"loss": loss, "ce": loss}

    def prefill(params, batch, cache):
        enc_out, enc_pos = _encode(cfg, params, batch["audio_frames"],
                                   compute_dtype, remat)
        B, S = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        lengths = batch.get("lengths")
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        ctx = {"mode": "prefill", "positions": positions, "lengths": lengths,
               "remat": "none", "constrain": _constrainer(mesh, rules)}
        h = _dec_embed(params, batch["tokens"], positions)
        h, new_cache = _run_decoder(params, h, ctx, cache, enc_out, enc_pos)
        h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        last = jnp.clip(lengths - 1, 0, S - 1)
        logits = _head(params, h[jnp.arange(B), last][:, None])[:, 0]
        # no routed experts: the bundle's empty per-expert counts
        return logits, new_cache, jnp.zeros((0, 0), jnp.int32)

    def decode_step(params, tokens, cache, lengths):
        B = tokens.shape[0]
        positions = lengths[:, None].astype(jnp.int32)
        ctx = {"mode": "decode", "positions": positions, "lengths": lengths,
               "remat": "none", "constrain": _constrainer(mesh, rules)}
        h = _dec_embed(params, tokens, positions)
        enc_pos = jnp.broadcast_to(
            jnp.arange(cfg.encoder_seq, dtype=jnp.int32), (B, cfg.encoder_seq))
        h, new_cache = _run_decoder(params, h, ctx, cache, None, enc_pos)
        h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        logits = _head(params, h)[:, 0]
        return logits, new_cache

    def cache_specs(B, T, dtype=jnp.bfloat16):
        K, D = cfg.n_kv_heads, cfg.head_dim
        kv = lambda t: {
            "k": WSpec((B, t, K, D), ("cache_batch", "cache_seq", "cache_heads", None),
                       init="zeros", dtype=dtype),
            "v": WSpec((B, t, K, D), ("cache_batch", "cache_seq", "cache_heads", None),
                       init="zeros", dtype=dtype),
        }
        per_layer = {"self": kv(T), "cross": kv(cfg.encoder_seq)}
        return jax.tree.map(
            lambda ws: dataclasses.replace(ws, shape=(n_dec, *ws.shape),
                                           axes=("layers", *ws.axes)),
            per_layer, is_leaf=_is_ws)

    def batch_specs(shape):
        B, S = shape.global_batch, shape.seq_len
        frames = WSpec((B, cfg.encoder_seq, cfg.d_model), ("batch", None, None),
                       dtype=compute_dtype)
        if shape.kind == "train":
            return {
                "tokens": WSpec((B, S), ("batch", "seq"), dtype=jnp.int32),
                "targets": WSpec((B, S), ("batch", "seq"), dtype=jnp.int32),
                "mask": WSpec((B, S), ("batch", "seq"), dtype=F32),
                "audio_frames": frames,
            }
        if shape.kind == "prefill":
            return {
                "tokens": WSpec((B, S), ("batch", "seq"), dtype=jnp.int32),
                "lengths": WSpec((B,), ("batch",), dtype=jnp.int32),
                "audio_frames": frames,
            }
        return {
            "tokens": WSpec((B, 1), ("batch", None), dtype=jnp.int32),
            "lengths": WSpec((B,), ("batch",), dtype=jnp.int32),
        }

    return ModelBundle(
        cfg=cfg, specs=specs, loss_fn=loss_fn, prefill=prefill,
        decode_step=decode_step, cache_specs=cache_specs,
        batch_specs=batch_specs, mesh=mesh, rules=rules,
    )
