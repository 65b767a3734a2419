"""Multi-head Latent Attention (DeepSeek-V2/V3).

K/V are reconstructed from a low-rank latent ``c_kv`` plus a single
shared rotary key ``k_rope``; only (c_kv, k_rope) are cached — the
defining MLA memory win (576 floats/token for deepseek-v3 vs ~32k for
vanilla MHA).  The query is a low-rank projection (``q_lora_rank``) or,
where that rank is 0 (Kimi-VL), one direct projection.

API:
  mla_project_kv(params, x, positions, cfg) -> (ckv, k_rope)
  mla_attend(params, x, positions, cfg, ckv_all, kr_all, ...) -> out
  mla_attend_absorbed(params, x, positions, cfg, ckv_all, kr_all,
                      kv_valid) -> out          # decode, latent space
  mla_apply(...) -> (out, (ckv, k_rope))    # train / prefill convenience
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.layers.initializers import WSpec
from repro.layers.norms import apply_norm, norm_specs
from repro.layers.rope import apply_rope

NEG_INF = -2.0e38


def mla_specs(cfg):
    H = cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        q = {
            "w_dq": WSpec((cfg.d_model, cfg.q_lora_rank), ("embed", "mla_rank")),
            "q_norm": norm_specs(cfg.q_lora_rank),
            "w_uq": WSpec((cfg.q_lora_rank, H, qk), ("mla_rank", "heads", None)),
        }
    else:
        q = {"w_q": WSpec((cfg.d_model, H, qk), ("embed", "heads", None))}
    return {
        **q,
        "w_dkv": WSpec((cfg.d_model, cfg.kv_lora_rank), ("embed", "mla_rank")),
        "kv_norm": norm_specs(cfg.kv_lora_rank),
        "w_kr": WSpec((cfg.d_model, cfg.qk_rope_dim), ("embed", None)),
        "w_uk": WSpec(
            (cfg.kv_lora_rank, H, cfg.qk_nope_dim), ("mla_rank", "heads", None)
        ),
        "w_uv": WSpec(
            (cfg.kv_lora_rank, H, cfg.v_head_dim), ("mla_rank", "heads", None)
        ),
        "w_o": WSpec((H, cfg.v_head_dim, cfg.d_model), ("heads", None, "embed")),
    }


def mla_project_kv(params, x, positions, cfg):
    dt = x.dtype
    ckv = apply_norm(
        params["kv_norm"], jnp.einsum("bsd,dr->bsr", x, params["w_dkv"].astype(dt)),
        cfg.norm, cfg.norm_eps,
    )
    k_rope = apply_rope(
        jnp.einsum("bsd,dr->bsr", x, params["w_kr"].astype(dt)), positions,
        cfg.rope_theta,
    )
    return ckv, k_rope


def _project_q(params, x, positions, cfg):
    """-> (q_nope (B,S,H,nope), q_rope (B,S,H,rope)), RoPE applied."""
    dt = x.dtype
    if cfg.q_lora_rank:
        cq = apply_norm(
            params["q_norm"],
            jnp.einsum("bsd,dr->bsr", x, params["w_dq"].astype(dt)),
            cfg.norm, cfg.norm_eps,
        )
        q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"].astype(dt))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["w_q"].astype(dt))
    q_nope = q[..., : cfg.qk_nope_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_attend(
    params, x, *, positions, cfg,
    ckv_all, kr_all, kv_positions, kv_valid=None, causal: bool = True,
):
    dt = x.dtype
    q_nope, q_rope = _project_q(params, x, positions, cfg)

    k_nope = jnp.einsum("btr,rhk->bthk", ckv_all, params["w_uk"].astype(dt))
    v = jnp.einsum("btr,rhv->bthv", ckv_all, params["w_uv"].astype(dt))

    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    logits = (
        jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
        + jnp.einsum("bshk,btk->bhst", q_rope, kr_all)
    ).astype(jnp.float32) * scale

    qp = positions[:, :, None]
    kp = kv_positions[:, None, :]
    mask = (kp <= qp) if causal else jnp.ones_like(kp <= qp)
    if kv_valid is not None:
        mask &= kv_valid[:, None, :]
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)

    out = jnp.einsum("bhst,bthv->bshv", probs, v)
    return jnp.einsum("bshv,hvd->bsd", out, params["w_o"].astype(dt))


def mla_attend_absorbed(params, x, *, positions, cfg, ckv_all, kr_all,
                        kv_valid):
    """Decode attention in the latent space: ``W_uk`` is absorbed into
    the query (q_nope -> kv_lora_rank) and ``W_uv`` applied once to the
    attention-weighted latent, so scores are taken straight against the
    cached ``c_kv`` plus ``q_rope . k_rope`` and no per-position K/V is
    built.  Equal to ``mla_attend`` up to rounding; every valid
    position lies at or before the query's, so ``kv_valid`` (B, T) is
    the whole mask."""
    dt = x.dtype
    q_nope, q_rope = _project_q(params, x, positions, cfg)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["w_uk"].astype(dt))
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    logits = (
        jnp.einsum("bshr,btr->bhst", q_lat, ckv_all)
        + jnp.einsum("bshk,btk->bhst", q_rope, kr_all)
    ).astype(jnp.float32) * scale
    logits = jnp.where(kv_valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    o_lat = jnp.einsum("bhst,btr->bshr", probs, ckv_all)
    out = jnp.einsum("bshr,rhv->bshv", o_lat, params["w_uv"].astype(dt))
    return jnp.einsum("bshv,hvd->bsd", out, params["w_o"].astype(dt))


def mla_apply(params, x, *, positions, cfg):
    """Self-attention over x (train / prefill)."""
    ckv, kr = mla_project_kv(params, x, positions, cfg)
    out = mla_attend(
        params, x, positions=positions, cfg=cfg,
        ckv_all=ckv, kr_all=kr, kv_positions=positions,
    )
    return out, (ckv, kr)
