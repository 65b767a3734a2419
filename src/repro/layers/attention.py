"""GQA attention with sliding-window masks and logit softcapping, in
XLA, with its dense and paged KV-cache updates.

API:
  project_qkv(params, x, positions, cfg)   -> q, k, v (rope applied)
  gqa_scores(q, k, v, ...)                 -> attention output (pre-wo)
  attention_apply(params, x, ...)          -> full self-attention (train/prefill)
  cache_insert / paged_cache_insert        -> one decode token's K/V row written
  paged_gather                             -> each row's pages, contiguous
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.layers.initializers import WSpec
from repro.layers.rope import apply_rope

NEG_INF = -2.0e38


def attention_specs(d_model: int, n_heads: int, n_kv_heads: int, head_dim: int):
    return {
        "wq": WSpec((d_model, n_heads, head_dim), ("embed", "heads", None),
                    mxu_operand=True),
        "wk": WSpec((d_model, n_kv_heads, head_dim),
                    ("embed", "kv_heads", None), mxu_operand=True),
        "wv": WSpec((d_model, n_kv_heads, head_dim),
                    ("embed", "kv_heads", None), mxu_operand=True),
        "wo": WSpec((n_heads, head_dim, d_model), ("heads", None, "embed"),
                    mxu_operand=True),
    }


def _softcap(logits, cap: float):
    if cap and cap > 0.0:
        return cap * jnp.tanh(logits / cap)
    return logits


def project_qkv(params, x, positions, cfg):
    """Project and (optionally) rope q/k.  x: (B,S,D) -> q (B,S,H,hd), k/v (B,S,K,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def output_proj(params, out, dtype):
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dtype))


def gqa_scores(
    q, k, v, *,
    q_positions, kv_positions,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    kv_valid: Optional[jax.Array] = None,   # (B, T) bool — cache validity
    scale: Optional[float] = None,
):
    """Grouped-query attention core.

    q: (B, S, H, D); k, v: (B, T, K, D) with H = K * G.  K/V are repeated
    to the full H head dim so the scores tensor (B, H, S, T) carries the
    tensor-parallel head sharding — with the grouped (B, K, G, S, T)
    layout XLA cannot shard K*G and replicates the quadratic scores on
    every model rank (measured: 16x temp memory on the dry-run).
    """
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    logits = _softcap(logits, softcap)

    qp = q_positions[:, :, None]                      # (B, S, 1)
    kp = kv_positions[:, None, :]                     # (B, 1, T)
    mask = jnp.ones((B, S, T), dtype=bool)
    if causal:
        mask &= kp <= qp
    if window and window > 0:
        mask &= kp > qp - window
    if kv_valid is not None:
        mask &= kv_valid[:, None, :]
    logits = jnp.where(mask[:, None, :, :], logits,
                       jnp.asarray(NEG_INF, jnp.float32))

    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def attention_apply(
    params, x, *,
    positions,
    cfg,
    local: bool = False,
    causal: bool = True,
    cross_kv=None,            # (k, v) from an encoder for cross-attention
    cross_positions=None,
):
    """Self- (or cross-) attention over the given sequence (train / prefill).

    Returns (out, (k, v)) — the freshly projected k/v for cache insertion.
    """
    if cross_kv is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
        if cfg.use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
        k, v = cross_kv
        out = gqa_scores(
            q, k, v, q_positions=positions, kv_positions=cross_positions,
            causal=False, window=0, softcap=cfg.attn_logit_softcap,
        )
        return output_proj(params, out, x.dtype), (k, v)

    q, k, v = project_qkv(params, x, positions, cfg)
    out = gqa_scores(
        q, k, v,
        q_positions=positions, kv_positions=positions, causal=causal,
        window=cfg.sliding_window if local else 0,
        softcap=cfg.attn_logit_softcap,
    )
    return output_proj(params, out, x.dtype), (k, v)


def cross_kv_project(params, enc_out, cfg):
    """Project encoder output into cross-attention K/V once (cached)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(enc_out.dtype))
    return k, v


def cache_insert(cache_arr, new_val, lengths):
    """Insert new_val (B, 1, ...) into cache (B, T, ...) at per-batch
    position `lengths`."""
    B = cache_arr.shape[0]
    return cache_arr.at[jnp.arange(B), lengths].set(
        new_val[:, 0].astype(cache_arr.dtype))


LANES = 128   # minor dimension of a TPU vector tile (8 x 128 float32)


def pool_row_width(row: int) -> int:
    """Width of a page-pool leaf's minor axis for a token row of ``row``
    elements: whole lane tiles.  The TPU compiler keeps a pool carried
    through the decode layer loop in place only when a token's row is a
    multiple of 128 lanes; otherwise it gives the pool a pages-minor
    layout and relayouts the whole stack every step."""
    return -(-row // LANES) * LANES


def to_pool_rows(x, n_lead: int, width: int):
    """The page pool's token-row layout: ``x``'s axes after the first
    ``n_lead`` flattened into one, zero-padded to ``width`` lanes."""
    x = x.reshape(*x.shape[:n_lead], -1)
    return jnp.pad(x, ((0, 0),) * n_lead + ((0, width - x.shape[-1]),))


def from_pool_rows(x, row_shape):
    """Undo ``to_pool_rows``: drop the padding lanes of the last axis
    and restore each token's ``row_shape``."""
    return x[..., :math.prod(row_shape)].reshape(*x.shape[:-1], *row_shape)


def paged_cache_insert(pool, layer, new_val, block_tables, lengths):
    """Write new_val (B, 1, ...) into layer ``layer`` of a stacked page
    pool (layers, n_pages, page_size, W) at per-sequence position
    ``lengths``, resolving the owning page through ``block_tables`` (B,
    n_max).  Each row is flattened and zero-padded to the pool's W lanes
    and scattered into the stacked pool itself, which is updated in
    place where it is donated.

    Live sequences never share pages, so the batched scatter indices
    are unique across rows; rows whose table points at a dummy page
    (dead decode rows) collide only with each other, on a page no
    sequence reads.
    """
    ps, W = pool.shape[2], pool.shape[3]
    B = new_val.shape[0]
    n_max = block_tables.shape[1]
    page = block_tables[jnp.arange(B), jnp.clip(lengths // ps, 0, n_max - 1)]
    off = lengths % ps
    row = to_pool_rows(new_val.astype(pool.dtype), 1, W)
    return pool.at[layer, page, off].set(row)


def paged_gather(pool, layer, block_tables, row_shape):
    """Materialize each sequence's pages of layer ``layer`` contiguously,
    in one gather straight from the stacked pool (layers, n_pages, ps,
    W) + tables (B, n_max) -> (B, n_max*ps, *row_shape) — the XLA-path
    view the paged Pallas kernel avoids building."""
    B, n_max = block_tables.shape
    seq = pool[layer, block_tables].reshape(B, n_max * pool.shape[2], -1)
    return from_pool_rows(seq, row_shape)
