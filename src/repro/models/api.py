"""Public model API: build_model(cfg) -> ModelBundle.

A ModelBundle packages weight specs + pure step functions for one
architecture.  All functions are jit-compatible; the dry-run lowers them
with ShapeDtypeStruct inputs derived from the same WSpec trees.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.common.config import ArchConfig, ShapeConfig
from repro.common.sharding import merge_rules, spec_for
from repro.layers import attention as attn_lib
from repro.layers import mla as mla_lib
from repro.layers.embedding import embed_apply, embed_specs, head_apply, head_specs
from repro.layers.initializers import (
    WSpec, abstract_tree, init_tree, spec_param_count, stack_specs,
)
from repro.layers.mlp import mlp_specs
from repro.layers.norms import apply_norm, norm_specs
from repro.layers.stack import scan_stack
from repro.models import encdec as encdec_lib
from repro.models.lm import StageDef, make_stages

F32 = jnp.float32


def _is_ws(x):
    return isinstance(x, WSpec)


# ---------------------------------------------------------------------------
# bundle
# ---------------------------------------------------------------------------

@dataclass
class ModelBundle:
    cfg: ArchConfig
    specs: Any                       # weights WSpec tree
    loss_fn: Callable                # (params, batch) -> (loss, metrics)
    # prefill and paged_decode_step also return ``counts``: the valid
    # tokens routed to each held expert per MoE layer, (n_moe_layers,
    # E_held) int32, where routed stages run on one device; (0, 0) else
    prefill: Callable                # (params, batch, cache) -> (logits_last, cache, counts)
    decode_step: Callable            # (params, tokens, cache, lengths) -> (logits, cache)
    cache_specs: Callable            # (B, T) -> WSpec tree
    batch_specs: Callable            # (ShapeConfig) -> WSpec tree
    mesh: Any = None
    rules: Any = None
    # paged-KV decode (serving substrate): present where every leaf of
    # every stage cache has a token axis (attention {k, v}, MLA's latent
    # {ckv, kr}); None where a stage carries recurrent state (mamba,
    # xLSTM) and for enc-dec caches
    paged_decode_step: Callable | None = None   # (params, tokens, cache,
    #                          block_tables, lengths) -> (logits, cache, counts)
    paged_cache_specs: Callable | None = None   # (n_pages, page_size, dtype)

    def init(self, key, param_dtype=jnp.float32):
        return init_tree(key, self.specs, param_dtype)

    def abstract_params(self, param_dtype=jnp.bfloat16):
        return abstract_tree(self.specs, param_dtype)

    def param_count(self) -> int:
        return spec_param_count(self.specs)

    def init_cache(self, B: int, T: int, dtype=jnp.bfloat16):
        return init_tree(jax.random.PRNGKey(0), self.cache_specs(B, T, dtype))

    @property
    def supports_paged_decode(self) -> bool:
        return self.paged_decode_step is not None

    def init_paged_cache(self, n_pages: int, page_size: int,
                         dtype=jnp.bfloat16):
        if self.paged_cache_specs is None:
            raise NotImplementedError(
                f"family {self.cfg.family!r} has no paged-KV cache layout "
                "(only caches with a token axis page)")
        return init_tree(jax.random.PRNGKey(0),
                         self.paged_cache_specs(n_pages, page_size, dtype))

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top-k of routed experts)."""
        cfg = self.cfg
        total = self.param_count()
        if not cfg.n_experts:
            return total
        from repro.layers.moe import padded_experts

        f = cfg.moe_d_ff or cfg.d_ff
        per_expert = 3 * cfg.d_model * f
        n_moe_layers = cfg.n_layers - cfg.first_dense_layers
        routed = padded_experts(cfg) * per_expert * n_moe_layers
        active = cfg.experts_top_k * per_expert * n_moe_layers
        return total - routed + active


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def _constrainer(mesh, rules):
    if mesh is None:
        return lambda h: h

    def constrain(h):
        spec = spec_for(h.shape, ("batch", "seq", "act_embed"), rules, mesh)
        return jax.lax.with_sharding_constraint(
            h, jax.sharding.NamedSharding(mesh, spec))

    return constrain


def _make_ctx(mesh, rules, mode, positions, lengths, remat):
    return {
        "mode": mode,
        "positions": positions,
        "lengths": lengths,
        "mesh": mesh,
        "remat": remat if mode == "train" else "none",
        "moe_impl": "ep" if mesh is not None else "dense",
        "constrain": _constrainer(mesh, rules),
    }


def _run_backbone(stages, params, h, ctx, caches):
    """Run all stages; returns (h, aux_loss, new_caches, counts).  Where
    ``ctx["token_valid"]`` is set, routed stages also give the tokens
    routed to each held expert, and ``counts`` is those of every routed
    layer in order, (n_routed_layers, E_held); else (0, 0)."""
    carry = (h, jnp.zeros((), F32))
    new_caches = {}
    counts = []
    for st in stages:
        p_st = params["stages"][st.name]
        ctx_st = dict(ctx)
        if st.shared_specs is not None:
            ctx_st["shared_attn"] = p_st["shared"]
        cache_st = None if caches is None else caches[st.name]

        def fn(lp, c, x_l, st=st, ctx_st=ctx_st, has_cache=cache_st is not None):
            c2, cache_l = st.block_fn(lp, c, x_l if has_cache else None, ctx_st)
            y = cache_l if has_cache else jnp.zeros((0,))
            return (c2[0], c2[1]), y

        carry, ys = scan_stack(fn, p_st["blocks"], carry, xs=cache_st,
                               remat=ctx["remat"])
        if st.routed and ctx.get("token_valid") is not None:
            ys, c = ys
            counts.append(c)
        if caches is not None:
            new_caches[st.name] = ys
    return (carry[0], carry[1], new_caches,
            jnp.concatenate(counts) if counts
            else jnp.zeros((0, 0), jnp.int32))


def _run_paged_decode(stages, params, h, ctx, pools):
    """Paged decode's layer loop; returns (h, new_pools, counts) as
    ``_run_backbone``.  Each stage's stacked page pool rides in the
    scan's carry instead of its ``xs``/``ys``: block ``l`` (given as
    ``ctx["layer"]``) scatters its new row into the stacked pool and
    gathers its pages straight from it, so a donated pool is updated in
    place and never sliced, copied or rebuilt per layer."""
    aux = jnp.zeros((), F32)
    new_pools = {}
    counts = []
    count = ctx.get("token_valid") is not None
    for st in stages:
        def body(carry, xs, st=st):
            h, aux, pool = carry
            lp, layer = xs
            (h, aux), out = st.block_fn(lp, (h, aux), pool,
                                        dict(ctx, layer=layer))
            c = jnp.zeros((0,), jnp.int32)
            if st.routed and count:
                out, c = out
            return (h, aux, out), c

        (h, aux, new_pools[st.name]), c = jax.lax.scan(
            body, (h, aux, pools[st.name]),
            (params["stages"][st.name]["blocks"], jnp.arange(st.n)))
        if st.routed and count:
            counts.append(c)
    return (h, new_pools,
            jnp.concatenate(counts) if counts
            else jnp.zeros((0, 0), jnp.int32))


def _lm_specs(cfg, stages):
    sp: dict[str, Any] = {
        "embed": embed_specs(cfg.vocab_size, cfg.d_model),
        "stages": {},
        "final_norm": norm_specs(cfg.d_model, cfg.norm),
    }
    for st in stages:
        entry = {"blocks": stack_specs(st.block_specs, st.n)}
        if st.shared_specs is not None:
            entry["shared"] = st.shared_specs
        sp["stages"][st.name] = entry
    if not cfg.tie_embeddings:
        sp["head"] = head_specs(cfg.d_model, cfg.vocab_size)
    if cfg.has_vision_stub:
        sp["img_proj"] = {
            "w": WSpec((cfg.d_model, cfg.d_model), (None, "embed"))
        }
    if cfg.mtp_depth:
        sp["mtp"] = {
            "proj": WSpec((2 * cfg.d_model, cfg.d_model), (None, "embed")),
            "norm_h": norm_specs(cfg.d_model, cfg.norm),
            "norm_e": norm_specs(cfg.d_model, cfg.norm),
            "block": {
                "ln_attn": norm_specs(cfg.d_model, cfg.norm),
                "attn": mla_lib.mla_specs(cfg) if cfg.use_mla
                else attn_lib.attention_specs(
                    cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),
                "ln_mlp": norm_specs(cfg.d_model, cfg.norm),
                "mlp": mlp_specs(cfg.d_model, cfg.dense_d_ff or cfg.d_ff),
            },
            "final_norm": norm_specs(cfg.d_model, cfg.norm),
        }
    return sp


def _embed_inputs(cfg, params, batch, compute_dtype):
    """Token (+modality-stub) embedding. Returns (h, n_prefix)."""
    scale = math.sqrt(cfg.d_model) if cfg.embed_scale_by_dim else 1.0
    h = embed_apply(params["embed"], batch["tokens"], scale=scale,
                    dtype=compute_dtype)
    n_prefix = 0
    if cfg.has_vision_stub:
        img = batch["image_embeds"].astype(compute_dtype)
        img = jnp.einsum("bnd,de->bne", img, params["img_proj"]["w"].astype(compute_dtype))
        h = jnp.concatenate([img, h], axis=1)
        n_prefix = img.shape[1]
    return h, n_prefix


def _logits(cfg, params, h):
    tied = params["embed"]["table"] if cfg.tie_embeddings else None
    return head_apply(params.get("head"), h, softcap=cfg.final_logit_softcap,
                      tied_table=tied)


def cross_entropy(logits, targets, mask):
    logits = logits.astype(F32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = (lse - tgt) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    return ce.sum() / denom


def _mtp_loss(cfg, params, h, batch, ctx, compute_dtype):
    """Simplified DeepSeek MTP: one extra block predicting token t+2."""
    p = params["mtp"]
    tok_next = batch["tokens"][:, 1:]
    emb = embed_apply(params["embed"], tok_next, dtype=compute_dtype)
    hh = apply_norm(p["norm_h"], h[:, :-1], cfg.norm, cfg.norm_eps)
    ee = apply_norm(p["norm_e"], emb, cfg.norm, cfg.norm_eps)
    x = jnp.einsum("bsd,df->bsf", jnp.concatenate([hh, ee], -1),
                   p["proj"].astype(compute_dtype))
    positions = ctx["positions"][:, 1:]
    blk = p["block"]
    xn = apply_norm(blk["ln_attn"], x, cfg.norm, cfg.norm_eps)
    if cfg.use_mla:
        y, _ = mla_lib.mla_apply(blk["attn"], xn, positions=positions, cfg=cfg)
    else:
        y, _ = attn_lib.attention_apply(blk["attn"], xn, positions=positions, cfg=cfg)
    x = x + y
    from repro.layers.mlp import mlp_apply

    x = x + mlp_apply(blk["mlp"], apply_norm(blk["ln_mlp"], x, cfg.norm,
                                             cfg.norm_eps), cfg.act_fn)
    x = apply_norm(p["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = _logits(cfg, params, x)
    # target at t+2 == targets shifted one left
    tgt = batch["targets"][:, 1:]
    msk = batch["mask"][:, 1:] * (jnp.arange(tgt.shape[1]) < tgt.shape[1] - 1)
    return cross_entropy(logits, tgt, msk)


def build_model(cfg: ArchConfig, mesh=None, rules=None, *,
                compute_dtype=jnp.bfloat16, remat: str = "full") -> ModelBundle:
    """The bundle for ``cfg``.  Each step's attention, cache update and
    expert dispatch follow from its mode and the mesh; ``remat`` is the
    training step's rematerialization policy ("full", "dots", "none")."""
    if cfg.is_encoder_decoder:
        return encdec_lib.build_encdec(cfg, mesh=mesh, rules=rules,
                                       compute_dtype=compute_dtype,
                                       remat=remat)

    rules = merge_rules(rules if isinstance(rules, dict) else None)
    stages = make_stages(cfg)
    specs = _lm_specs(cfg, stages)

    # ---- loss (train) ----
    def loss_fn(params, batch):
        h, n_prefix = _embed_inputs(cfg, params, batch, compute_dtype)
        B, S = h.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        ctx = _make_ctx(mesh, rules, "train", positions, None, remat)
        h = ctx["constrain"](h)
        h, aux, _, _ = _run_backbone(stages, params, h, ctx, None)
        h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        if n_prefix:
            h = h[:, n_prefix:]
        logits = _logits(cfg, params, h)
        loss = cross_entropy(logits, batch["targets"], batch["mask"])
        metrics = {"ce": loss, "aux": aux}
        if cfg.router_aux_loss and cfg.n_experts:
            loss = loss + cfg.router_aux_loss * aux
        if cfg.mtp_depth:
            ctx_m = _make_ctx(mesh, rules, "train", positions, None, remat)
            mtp = _mtp_loss(cfg, params, h if not n_prefix else h,
                            batch, ctx_m, compute_dtype)
            metrics["mtp"] = mtp
            loss = loss + 0.3 * mtp
        metrics["loss"] = loss
        return loss, metrics

    # routed stages on one device count their held experts' tokens in
    # prefill and paged decode
    count_experts = mesh is None and any(st.routed for st in stages)

    # ---- prefill ----
    def prefill(params, batch, cache):
        h, n_prefix = _embed_inputs(cfg, params, batch, compute_dtype)
        B, S = h.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        lengths = batch.get("lengths")
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        ctx = _make_ctx(mesh, rules, "prefill", positions, lengths, remat)
        if count_experts:
            ctx["token_valid"] = positions < lengths[:, None]
        h = ctx["constrain"](h)
        h, _, new_caches, counts = _run_backbone(stages, params, h, ctx, cache)
        h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        last = jnp.clip(lengths - 1, 0, S - 1)
        h_last = h[jnp.arange(B), last][:, None, :]
        logits = _logits(cfg, params, h_last)[:, 0]
        return logits, new_caches, counts

    # ---- decode ----
    def decode_step(params, tokens, cache, lengths):
        h = embed_apply(
            params["embed"], tokens,
            scale=math.sqrt(cfg.d_model) if cfg.embed_scale_by_dim else 1.0,
            dtype=compute_dtype)
        B = h.shape[0]
        positions = lengths[:, None].astype(jnp.int32)
        ctx = _make_ctx(mesh, rules, "decode", positions, lengths, remat)
        h, _, new_caches, _ = _run_backbone(stages, params, h, ctx, cache)
        h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        logits = _logits(cfg, params, h)[:, 0]
        return logits, new_caches

    # ---- paged decode (serving substrate) ----
    # A stage cache pages when every leaf has a token axis, (B, T, ...):
    # attention's {"k","v"} and MLA's latent {"ckv","kr"}.  Re-
    # parameterizing (B, T) as (n_pages, page_size), with a token's row
    # flattened to one lane-padded axis, yields the global page pool the
    # block-table scatter and gather consume.  Recurrent state (mamba,
    # xLSTM) has no token axis; those bundles keep the fields None
    # (``paged_supported`` below).

    def paged_decode_step(params, tokens, cache, block_tables, lengths):
        h = embed_apply(
            params["embed"], tokens,
            scale=math.sqrt(cfg.d_model) if cfg.embed_scale_by_dim else 1.0,
            dtype=compute_dtype)
        positions = lengths[:, None].astype(jnp.int32)
        ctx = _make_ctx(mesh, rules, "decode", positions, lengths, remat)
        ctx["cache_layout"] = "paged"
        ctx["block_tables"] = block_tables
        if count_experts:
            # dead rows hold length 0; a live row's cache is never empty
            ctx["token_valid"] = (lengths > 0)[:, None]
        h, new_caches, counts = _run_paged_decode(stages, params, h, ctx,
                                                  cache)
        h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        logits = _logits(cfg, params, h)[:, 0]
        return logits, new_caches, counts

    def paged_cache_specs(n_pages, page_size, dtype=jnp.bfloat16):
        """Every leaf (layers, n_pages, page_size, W): a token's row
        (attention's K*D, MLA's R or r) on one minor axis, zero-padded
        to whole TPU lane tiles (``attn_lib.pool_row_width``)."""
        def page(ws):
            row = attn_lib.pool_row_width(math.prod(ws.shape[3:]))
            return dataclasses.replace(ws, shape=(*ws.shape[:3], row),
                                       axes=(*ws.axes[:3], None))

        return jax.tree.map(page, cache_specs(n_pages, page_size, dtype),
                            is_leaf=_is_ws)

    # ---- cache / batch specs ----
    def cache_specs(B, T, dtype=jnp.bfloat16):
        out = {}
        for st in stages:
            if st.cache_specs is None:
                continue
            per_layer = st.cache_specs(cfg, B, T, dtype)
            out[st.name] = jax.tree.map(
                lambda ws: dataclasses.replace(
                    ws, shape=(st.n, *ws.shape), axes=("layers", *ws.axes)),
                per_layer, is_leaf=_is_ws)
        return out

    def batch_specs(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        text = S
        extra = {}
        if cfg.has_vision_stub:
            text = S - cfg.n_image_tokens
            extra["image_embeds"] = WSpec(
                (B, cfg.n_image_tokens, cfg.d_model), ("batch", None, None),
                dtype=compute_dtype)
        if shape.kind == "train":
            return {
                "tokens": WSpec((B, text), ("batch", "seq"), dtype=jnp.int32),
                "targets": WSpec((B, text), ("batch", "seq"), dtype=jnp.int32),
                "mask": WSpec((B, text), ("batch", "seq"), dtype=F32),
                **extra,
            }
        if shape.kind == "prefill":
            return {
                "tokens": WSpec((B, text), ("batch", "seq"), dtype=jnp.int32),
                "lengths": WSpec((B,), ("batch",), dtype=jnp.int32),
                **extra,
            }
        # decode
        return {
            "tokens": WSpec((B, 1), ("batch", None), dtype=jnp.int32),
            "lengths": WSpec((B,), ("batch",), dtype=jnp.int32),
        }

    paged_supported = all(
        "cache_seq" in ws.axes
        for ws in jax.tree.leaves(cache_specs(1, 1), is_leaf=_is_ws))

    return ModelBundle(
        cfg=cfg, specs=specs, loss_fn=loss_fn, prefill=prefill,
        decode_step=decode_step, cache_specs=cache_specs,
        batch_specs=batch_specs, mesh=mesh, rules=rules,
        paged_decode_step=paged_decode_step if paged_supported else None,
        paged_cache_specs=paged_cache_specs if paged_supported else None,
    )
