"""Decoder-only LM covering all assigned text families via *stages*.

A model is: embedding -> [stage_0 ... stage_k] -> final norm -> head.
Each stage is a scan over homogeneous blocks; heterogeneous architectures
(gemma2 local/global pairs, deepseek dense->MoE, zamba2 mamba+shared-attn
superblocks, xLSTM 7:1 groups) become short sequences of stages, keeping
the HLO O(1) in depth.

Modes: "train" (no cache), "prefill" (fills caches), "decode" (one token,
reads+updates caches).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.layers import attention as attn
from repro.layers import mamba2 as m2
from repro.layers import mla as mla_lib
from repro.layers import moe as moe_lib
from repro.layers import xlstm as xl
from repro.layers.embedding import embed_apply, embed_specs, head_apply, head_specs
from repro.layers.initializers import WSpec, stack_specs
from repro.layers.mlp import mlp_apply, mlp_specs
from repro.layers.norms import apply_norm, norm_specs
from repro.layers.stack import scan_stack


# ---------------------------------------------------------------------------
# block spec builders
# ---------------------------------------------------------------------------

def _attn_block_specs(cfg, use_moe: bool, post_norm: bool):
    d = cfg.d_model
    specs = {
        "ln_attn": norm_specs(d, cfg.norm),
        "attn": attn.attention_specs(d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),
        "ln_mlp": norm_specs(d, cfg.norm),
    }
    if use_moe:
        specs["moe"] = moe_lib.moe_specs(cfg)
    else:
        specs["mlp"] = mlp_specs(d, cfg.d_ff)
    if post_norm:
        specs["ln_attn_post"] = norm_specs(d, cfg.norm)
        specs["ln_mlp_post"] = norm_specs(d, cfg.norm)
    return specs


def _mla_block_specs(cfg, use_moe: bool):
    d = cfg.d_model
    specs = {
        "ln_attn": norm_specs(d, cfg.norm),
        "attn": mla_lib.mla_specs(cfg),
        "ln_mlp": norm_specs(d, cfg.norm),
    }
    if use_moe:
        specs["moe"] = moe_lib.moe_specs(cfg)
    else:
        specs["mlp"] = mlp_specs(d, cfg.dense_d_ff or cfg.d_ff)
    return specs


def _mamba_block_specs(cfg):
    return {"ln": norm_specs(cfg.d_model, cfg.norm), "mamba": m2.mamba2_specs(cfg)}


def _shared_attention_specs(cfg):
    d = cfg.d_model
    return {
        "ln_attn": norm_specs(d, cfg.norm),
        "attn": attn.attention_specs(d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),
        "ln_mlp": norm_specs(d, cfg.norm),
        "mlp": mlp_specs(d, cfg.shared_attn_d_ff or cfg.d_ff),
    }


# ---------------------------------------------------------------------------
# block bodies
# ---------------------------------------------------------------------------

def _apply_attn_sub(p, h, cache, ctx, cfg, *, local: bool, post_norm: bool):
    """Norm + attention + residual (+post-norm). Returns (h, new_cache)."""
    x = apply_norm(p["ln_attn"], h, cfg.norm, cfg.norm_eps)
    if ctx["mode"] == "train":
        y, _ = attn.attention_apply(
            p["attn"], x, positions=ctx["positions"], cfg=cfg, local=local,
        )
        new_cache = cache
    elif ctx["mode"] == "prefill":
        S = x.shape[1]
        y, (k, v) = attn.attention_apply(
            p["attn"], x, positions=ctx["positions"], cfg=cfg, local=local,
        )
        new_cache = {
            "k": cache["k"].at[:, :S].set(k.astype(cache["k"].dtype)),
            "v": cache["v"].at[:, :S].set(v.astype(cache["v"].dtype)),
        }
    else:  # decode: single token at per-batch position `lengths`
        B = x.shape[0]
        q_pos = ctx["positions"]
        lengths = ctx["lengths"]
        q, k_new, v_new = attn.project_qkv(p["attn"], x, q_pos, cfg)
        if ctx.get("cache_layout") == "paged":
            return _paged_decode_sub(p, h, x, cache, q, k_new, v_new,
                                     ctx, cfg, local=local,
                                     post_norm=post_norm)
        k_cache = attn.cache_insert(cache["k"], k_new, lengths)
        v_cache = attn.cache_insert(cache["v"], v_new, lengths)
        T = k_cache.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        kv_valid = kv_pos < (lengths + 1)[:, None]
        out = attn.gqa_scores(
            q, k_cache.astype(x.dtype), v_cache.astype(x.dtype),
            q_positions=q_pos, kv_positions=kv_pos,
            causal=True, window=(cfg.sliding_window if local else 0),
            softcap=cfg.attn_logit_softcap, kv_valid=kv_valid,
        )
        y = attn.output_proj(p["attn"], out, x.dtype)
        new_cache = {"k": k_cache, "v": v_cache}
    if post_norm:
        y = apply_norm(p["ln_attn_post"], y, cfg.norm, cfg.norm_eps)
    return h + y, new_cache


def _paged_decode_sub(p, h, x, cache, q, k_new, v_new, ctx, cfg, *,
                      local: bool, post_norm: bool):
    """Decode step against a paged KV cache: cache leaves are the
    stage's stacked page pools (layers, n_pages, page_size, W), a
    token's K*D row padded to W lanes, and this block is layer
    ``ctx["layer"]``; ``ctx["block_tables"]`` (B, n_max) names each
    row's pages.  The owned pages are gathered and attended with
    gqa_scores."""
    lengths = ctx["lengths"]
    tables = ctx["block_tables"]
    layer = ctx["layer"]
    B = x.shape[0]
    k_cache = attn.paged_cache_insert(cache["k"], layer, k_new, tables,
                                      lengths)
    v_cache = attn.paged_cache_insert(cache["v"], layer, v_new, tables,
                                      lengths)
    row = k_new.shape[2:]
    k_seq = attn.paged_gather(k_cache, layer, tables, row)
    v_seq = attn.paged_gather(v_cache, layer, tables, row)
    T = k_seq.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    kv_valid = kv_pos < (lengths + 1)[:, None]
    out = attn.gqa_scores(
        q, k_seq.astype(x.dtype), v_seq.astype(x.dtype),
        q_positions=ctx["positions"], kv_positions=kv_pos,
        causal=True, window=cfg.sliding_window if local else 0,
        softcap=cfg.attn_logit_softcap, kv_valid=kv_valid,
    )
    y = attn.output_proj(p["attn"], out, x.dtype)
    if post_norm:
        y = apply_norm(p["ln_attn_post"], y, cfg.norm, cfg.norm_eps)
    return h + y, {"k": k_cache, "v": v_cache}


def _apply_ffn_sub(p, h, ctx, cfg, *, use_moe: bool, post_norm: bool):
    """-> (h, aux, counts): ``counts`` (E_held,) is the number of valid
    tokens routed to each held expert where ``ctx["token_valid"]`` asks
    for it (prefill and paged decode of a routed stage), else None."""
    x = apply_norm(p["ln_mlp"], h, cfg.norm, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    counts = None
    if use_moe and ctx.get("token_valid") is not None:
        y, aux, counts = moe_lib.moe_apply_dense(
            p["moe"], x, cfg, valid=ctx["token_valid"])
    elif use_moe:
        y, aux = moe_lib.moe_apply(
            p["moe"], x, cfg, mesh=ctx["mesh"], impl=ctx["moe_impl"]
        )
    else:
        y = mlp_apply(p["mlp"], x, cfg.act_fn)
    if post_norm:
        y = apply_norm(p["ln_mlp_post"], y, cfg.norm, cfg.norm_eps)
    return h + y, aux, counts


def _with_counts(new_cache, counts):
    """A counting block's scan output: (cache, counts)."""
    return new_cache if counts is None else (new_cache, counts)


def _attn_block(p, carry, cache, ctx, cfg, *, local: bool, use_moe: bool,
                post_norm: bool):
    h, aux_acc = carry
    h = ctx["constrain"](h)
    h, new_cache = _apply_attn_sub(p, h, cache, ctx, cfg, local=local,
                                   post_norm=post_norm)
    h, aux, counts = _apply_ffn_sub(p, h, ctx, cfg, use_moe=use_moe,
                                    post_norm=post_norm)
    return (h, aux_acc + aux), _with_counts(new_cache, counts)


def _mla_block(p, carry, cache, ctx, cfg, *, use_moe: bool):
    h, aux_acc = carry
    h = ctx["constrain"](h)
    x = apply_norm(p["ln_attn"], h, cfg.norm, cfg.norm_eps)
    B = x.shape[0]
    if ctx["mode"] == "train":
        y, _ = mla_lib.mla_apply(p["attn"], x, positions=ctx["positions"], cfg=cfg)
        new_cache = cache
    elif ctx["mode"] == "prefill":
        S = x.shape[1]
        y, (ckv, kr) = mla_lib.mla_apply(p["attn"], x, positions=ctx["positions"], cfg=cfg)
        new_cache = {
            "ckv": cache["ckv"].at[:, :S].set(ckv.astype(cache["ckv"].dtype)),
            "kr": cache["kr"].at[:, :S].set(kr.astype(cache["kr"].dtype)),
        }
    elif ctx.get("cache_layout") == "paged":
        # cache leaves are the stage's stacked latent page pools (layers,
        # n_pages, page_size, W), R or r lanes padded to W; this block is
        # layer ctx["layer"].  Attention runs absorbed, in the latent space
        lengths, tables = ctx["lengths"], ctx["block_tables"]
        layer = ctx["layer"]
        ckv_new, kr_new = mla_lib.mla_project_kv(
            p["attn"], x, ctx["positions"], cfg)
        ckv_c = attn.paged_cache_insert(cache["ckv"], layer, ckv_new, tables,
                                        lengths)
        kr_c = attn.paged_cache_insert(cache["kr"], layer, kr_new, tables,
                                       lengths)
        ckv_seq = attn.paged_gather(ckv_c, layer, tables,
                                    ckv_new.shape[2:]).astype(x.dtype)
        kr_seq = attn.paged_gather(kr_c, layer, tables,
                                   kr_new.shape[2:]).astype(x.dtype)
        T = ckv_seq.shape[1]
        kv_valid = jnp.arange(T, dtype=jnp.int32)[None, :] < (lengths + 1)[:, None]
        y = mla_lib.mla_attend_absorbed(
            p["attn"], x, positions=ctx["positions"], cfg=cfg,
            ckv_all=ckv_seq, kr_all=kr_seq, kv_valid=kv_valid,
        )
        new_cache = {"ckv": ckv_c, "kr": kr_c}
    else:
        lengths = ctx["lengths"]
        ckv_new, kr_new = mla_lib.mla_project_kv(
            p["attn"], x, ctx["positions"], cfg)
        ckv_c = attn.cache_insert(cache["ckv"], ckv_new, lengths)
        kr_c = attn.cache_insert(cache["kr"], kr_new, lengths)
        T = ckv_c.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        kv_valid = kv_pos < (lengths + 1)[:, None]
        y = mla_lib.mla_attend(
            p["attn"], x, positions=ctx["positions"], cfg=cfg,
            ckv_all=ckv_c.astype(x.dtype), kr_all=kr_c.astype(x.dtype),
            kv_positions=kv_pos, kv_valid=kv_valid,
        )
        new_cache = {"ckv": ckv_c, "kr": kr_c}
    h = h + y
    h, aux, counts = _apply_ffn_sub(p, h, ctx, cfg, use_moe=use_moe,
                                    post_norm=False)
    return (h, aux_acc + aux), _with_counts(new_cache, counts)


def _mamba_block(p, carry, cache, ctx, cfg):
    h, aux = carry
    h = ctx["constrain"](h)
    x = apply_norm(p["ln"], h, cfg.norm, cfg.norm_eps)
    state = cache if ctx["mode"] == "decode" else None
    y, new_state = m2.mamba2_apply(p["mamba"], x, cfg, state=state)
    new_cache = new_state if ctx["mode"] != "train" else cache
    return (h + y, aux), new_cache


def _mlstm_block(p, carry, cache, ctx, cfg):
    h, aux = carry
    h = ctx["constrain"](h)
    state = tuple(cache) if (ctx["mode"] == "decode" and cache is not None) else None
    y, new_state = xl.mlstm_apply(p, h, cfg, state=state)
    new_cache = list(new_state) if ctx["mode"] != "train" else cache
    return (h + y, aux), new_cache


def _slstm_block(p, carry, cache, ctx, cfg):
    h, aux = carry
    h = ctx["constrain"](h)
    state = tuple(cache) if (ctx["mode"] == "decode" and cache is not None) else None
    y, new_state = xl.slstm_apply(p, h, cfg, state=state)
    new_cache = list(new_state) if ctx["mode"] != "train" else cache
    return (h + y, aux), new_cache


# ---------------------------------------------------------------------------
# stages
# ---------------------------------------------------------------------------

@dataclass
class StageDef:
    name: str
    n: int                                   # scanned length
    block_specs: Any                         # unstacked per-block spec tree
    block_fn: Callable                       # (p, carry, cache_l, ctx) -> ((h,aux), cache_l')
    cache_specs: Callable | None             # (cfg, B, T, dtype) -> per-layer WSpec tree
    shared_specs: Any = None                 # non-scanned weights (zamba shared attn)
    routed: bool = False                     # blocks route tokens to experts


def _kv_cache_specs(cfg, B, T, dtype):
    K, D = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": WSpec((B, T, K, D), ("cache_batch", "cache_seq", "cache_heads", None),
                   init="zeros", dtype=dtype),
        "v": WSpec((B, T, K, D), ("cache_batch", "cache_seq", "cache_heads", None),
                   init="zeros", dtype=dtype),
    }


def _mla_cache_specs(cfg, B, T, dtype):
    return {
        "ckv": WSpec((B, T, cfg.kv_lora_rank),
                     ("cache_batch", "cache_seq", None), init="zeros", dtype=dtype),
        "kr": WSpec((B, T, cfg.qk_rope_dim),
                    ("cache_batch", "cache_seq", None), init="zeros", dtype=dtype),
    }


def _mamba_cache_specs(cfg, B, T, dtype):
    d_in, H, N = m2.mamba2_dims(cfg)
    W = cfg.mamba_conv_width
    return {
        "ssm": WSpec((B, H, N, cfg.mamba_head_dim),
                     ("cache_batch", "ssm_heads", None, None), init="zeros",
                     dtype=jnp.float32),
        "conv_x": WSpec((B, W - 1, d_in), ("cache_batch", None, "ssm_inner"),
                        init="zeros", dtype=dtype),
        "conv_B": WSpec((B, W - 1, N), ("cache_batch", None, None), init="zeros",
                        dtype=dtype),
        "conv_C": WSpec((B, W - 1, N), ("cache_batch", None, None), init="zeros",
                        dtype=dtype),
    }


def _mlstm_cache_specs(cfg, B, T, dtype):
    d_in, H, hd = xl.mlstm_dims(cfg)
    return [
        WSpec((B, H, hd, hd), ("cache_batch", "ssm_heads", None, None),
              init="zeros", dtype=jnp.float32),
        WSpec((B, H, hd), ("cache_batch", "ssm_heads", None), init="zeros",
              dtype=jnp.float32),
        WSpec((B, H), ("cache_batch", "ssm_heads"), init="zeros", dtype=jnp.float32),
    ]


def _slstm_cache_specs(cfg, B, T, dtype):
    d = cfg.d_model
    return [
        WSpec((B, d), ("cache_batch", None), init="zeros", dtype=jnp.float32)
        for _ in range(4)
    ]


def make_stages(cfg) -> list[StageDef]:
    fam = cfg.family
    stages: list[StageDef] = []

    if fam in ("dense", "vlm"):
        if cfg.attn_pattern:  # gemma2: scan over (local, global) pairs
            pat = cfg.attn_pattern
            n_pairs = cfg.n_layers // len(pat)

            pair_specs = {
                f"sub{i}": _attn_block_specs(cfg, False, cfg.post_norm)
                for i in range(len(pat))
            }

            def pair_fn(p, carry, cache, ctx, pat=pat):
                caches = []
                for i, kind in enumerate(pat):
                    carry, c = _attn_block(
                        p[f"sub{i}"], carry,
                        None if cache is None else cache[i], ctx, cfg,
                        local=(kind == "local"), use_moe=False,
                        post_norm=cfg.post_norm,
                    )
                    caches.append(c)
                return carry, caches

            def pair_cache(cfg_, B, T, dtype, k=len(pat)):
                return [_kv_cache_specs(cfg_, B, T, dtype) for _ in range(k)]

            stages.append(StageDef("pairs", n_pairs, pair_specs, pair_fn, pair_cache))
        else:
            stages.append(StageDef(
                "blocks", cfg.n_layers, _attn_block_specs(cfg, False, cfg.post_norm),
                partial(_attn_block, cfg=cfg, local=False, use_moe=False,
                        post_norm=cfg.post_norm),
                _kv_cache_specs,
            ))

    elif fam == "moe":
        if cfg.use_mla:
            if cfg.first_dense_layers:
                dense_cfg_specs = {
                    "ln_attn": norm_specs(cfg.d_model, cfg.norm),
                    "attn": mla_lib.mla_specs(cfg),
                    "ln_mlp": norm_specs(cfg.d_model, cfg.norm),
                    "mlp": mlp_specs(cfg.d_model, cfg.dense_d_ff or cfg.d_ff),
                }
                stages.append(StageDef(
                    "dense", cfg.first_dense_layers, dense_cfg_specs,
                    partial(_mla_block, cfg=cfg, use_moe=False), _mla_cache_specs,
                ))
            stages.append(StageDef(
                "moe", cfg.n_layers - cfg.first_dense_layers,
                _mla_block_specs(cfg, True),
                partial(_mla_block, cfg=cfg, use_moe=True), _mla_cache_specs,
                routed=True,
            ))
        else:
            stages.append(StageDef(
                "moe", cfg.n_layers, _attn_block_specs(cfg, True, cfg.post_norm),
                partial(_attn_block, cfg=cfg, local=False, use_moe=True,
                        post_norm=cfg.post_norm),
                _kv_cache_specs, routed=True,
            ))

    elif fam == "hybrid":  # zamba2: superblocks of mamba + shared attention
        k = cfg.n_mamba_per_super
        n_super = cfg.n_layers // k
        tail = cfg.n_layers - n_super * k
        super_specs = {"mamba": stack_specs(_mamba_block_specs(cfg), k)}
        shared = _shared_attention_specs(cfg)

        def super_fn(p, carry, cache, ctx, k=k):
            mcache = None if cache is None else cache["mamba"]

            def inner(lp, c, x_l):
                cc, cl = _mamba_block(lp, c, x_l if mcache is not None else None,
                                      ctx, cfg)
                return cc, (cl if mcache is not None else jnp.zeros((0,)))

            carry, mc = scan_stack(inner, p["mamba"], carry, xs=mcache,
                                   remat=ctx["remat"])
            # shared attention block (weights shared across superblocks)
            acache = None if cache is None else cache["attn"]
            carry, ac = _attn_block(ctx["shared_attn"], carry, acache, ctx, cfg,
                                    local=False, use_moe=False, post_norm=False)
            new_cache = None if cache is None else {"mamba": mc, "attn": ac}
            return carry, (new_cache if cache is not None else jnp.zeros((0,)))

        def super_cache(cfg_, B, T, dtype, k=k):
            return {
                "mamba": jax.tree.map(
                    lambda ws: dataclasses.replace(
                        ws, shape=(k, *ws.shape), axes=("layers", *ws.axes)),
                    _mamba_cache_specs(cfg_, B, T, dtype),
                    is_leaf=lambda x: isinstance(x, WSpec)),
                "attn": _kv_cache_specs(cfg_, B, T, dtype),
            }

        stages.append(StageDef("super", n_super, super_specs, super_fn,
                               super_cache, shared_specs=shared))
        if tail:
            stages.append(StageDef(
                "tail", tail, _mamba_block_specs(cfg), partial(_mamba_block, cfg=cfg),
                _mamba_cache_specs,
            ))

    elif fam == "ssm":  # xLSTM m:1 groups
        m = cfg.mlstm_to_slstm
        group = m + 1
        n_groups = cfg.n_layers // group
        group_specs = {
            "mlstm": stack_specs(xl.mlstm_specs(cfg), m),
            "slstm": xl.slstm_specs(cfg),
        }

        def group_fn(p, carry, cache, ctx, m=m):
            mcache = None if cache is None else cache["mlstm"]

            def inner(lp, c, x_l):
                cc, cl = _mlstm_block(lp, c, x_l if mcache is not None else None,
                                      ctx, cfg)
                return cc, (cl if mcache is not None else jnp.zeros((0,)))

            carry, mc = scan_stack(inner, p["mlstm"], carry, xs=mcache,
                                   remat=ctx["remat"])
            scache = None if cache is None else cache["slstm"]
            carry, sc = _slstm_block(p["slstm"], carry, scache, ctx, cfg)
            new_cache = None if cache is None else {"mlstm": mc, "slstm": sc}
            return carry, (new_cache if cache is not None else jnp.zeros((0,)))

        def group_cache(cfg_, B, T, dtype, m=m):
            return {
                "mlstm": [
                    jax.tree.map(
                        lambda ws: dataclasses.replace(
                            ws, shape=(m, *ws.shape), axes=("layers", *ws.axes)),
                        s, is_leaf=lambda x: isinstance(x, WSpec))
                    for s in _mlstm_cache_specs(cfg_, B, T, dtype)
                ],
                "slstm": _slstm_cache_specs(cfg_, B, T, dtype),
            }

        stages.append(StageDef("xgroup", n_groups, group_specs, group_fn,
                               group_cache))

    else:
        raise ValueError(f"make_stages: unsupported family {fam}")

    return stages
