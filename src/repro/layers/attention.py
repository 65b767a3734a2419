"""GQA attention with sliding-window masks and logit softcapping.

The XLA path (default) is what the dry-run lowers; a Pallas flash kernel
(repro.kernels) can be selected with ``impl="pallas"`` for TPU execution
or ``impl="pallas_interpret"`` for CPU validation.

API:
  project_qkv(params, x, positions, cfg)   -> q, k, v (rope applied)
  gqa_scores(q, k, v, ...)                 -> attention output (pre-wo)
  attention_apply(params, x, ...)          -> full self-attention (train/prefill)
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
        "wq": WSpec((d_model, n_heads, head_dim), ("embed", "heads", None)),
        "wk": WSpec((d_model, n_kv_heads, head_dim), ("embed", "kv_heads", None)),
        "wv": WSpec((d_model, n_kv_heads, head_dim), ("embed", "kv_heads", None)),
        "wo": WSpec((n_heads, head_dim, d_model), ("heads", None, "embed")),
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
    softmax_dtype=jnp.float32,
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
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(softmax_dtype) * scale
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
    neg = jnp.asarray(NEG_INF if softmax_dtype == jnp.float32 else -3e38,
                      softmax_dtype) if softmax_dtype == jnp.float32 else \
        jnp.asarray(jnp.finfo(softmax_dtype).min, softmax_dtype)
    logits = jnp.where(mask[:, None, :, :], logits, neg)

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
    impl: str = "xla",
    constrain_kv=None,        # SP: pin k/v replicated over model so the
                              # scores keep the seq sharding (see §Perf)
    softmax_dtype=jnp.float32,
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
    if constrain_kv is not None:
        k = constrain_kv(k)
        v = constrain_kv(v)
    window = cfg.sliding_window if local else 0

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        out = kops.flash_attention(
            q, k, v,
            causal=causal, window=window, softcap=cfg.attn_logit_softcap,
            interpret=(impl == "pallas_interpret"),
        )
    else:
        out = gqa_scores(
            q, k, v,
            q_positions=positions, kv_positions=positions,
            causal=causal, window=window, softcap=cfg.attn_logit_softcap,
            softmax_dtype=softmax_dtype,
        )
    return output_proj(params, out, x.dtype), (k, v)


def cross_kv_project(params, enc_out, cfg):
    """Project encoder output into cross-attention K/V once (cached)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(enc_out.dtype))
    return k, v


def decode_attention_shardmap(q, k_cache, v_cache, lengths, *, mesh, rules,
                              window: int = 0, softcap: float = 0.0):
    """Distributed partial-softmax decode attention under shard_map.

    q: (B, 1, H, D) batch-sharded; cache: (B, T, K, D) batch-sharded over
    the data axes and seq-sharded over 'model'.  Each chip computes
    logits/softmax partials over its local seq tile; a pmax + two psums
    (scalars and (B,H,D)) combine — the cache never moves.  This is the
    flash-decoding communication pattern expressed manually because
    GSPMD keeps resolving the q-heads/cache-seq sharding conflict by
    all-gathering the cache (measured: 270 GB/step on llama3-405b).
    """
    import math as _math

    from repro.common.sharding import spec_for

    B, _, H, D = q.shape
    T, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / _math.sqrt(D)
    # q follows the CACHE's batch sharding (pjit auto-reshards q if the
    # activation rules keep it replicated)
    spec_q = spec_for(q.shape, ("cache_batch", None, None, None), rules, mesh)
    spec_c = spec_for(k_cache.shape,
                      ("cache_batch", "cache_seq", None, None), rules, mesh)
    spec_l = spec_for(lengths.shape, ("cache_batch",), rules, mesh)
    t_entry = spec_c[1]
    seq_axes = (() if t_entry is None else
                (t_entry if isinstance(t_entry, tuple) else (t_entry,)))

    def f(q_l, k_l, v_l, len_l):
        B_loc, _, _, _ = q_l.shape
        T_loc = k_l.shape[1]
        t_off = jnp.zeros((), jnp.int32)
        idx = 0
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        t_off = (idx * T_loc) if seq_axes else 0
        kv_pos = t_off + jnp.arange(T_loc, dtype=jnp.int32)       # (T_loc,)
        if G > 1:
            k_rep = jnp.repeat(k_l, G, axis=2)
            v_rep = jnp.repeat(v_l, G, axis=2)
        else:
            k_rep, v_rep = k_l, v_l
        logits = jnp.einsum("bshd,bthd->bhst", q_l,
                            k_rep.astype(q_l.dtype)).astype(jnp.float32) * scale
        if softcap and softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        pos = len_l[:, None]                                       # (B,1)
        valid = kv_pos[None, :] < (len_l + 1)[:, None]             # (B,T_loc)
        if window and window > 0:
            valid &= kv_pos[None, :] > pos - window
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
        m_loc = jnp.max(logits, axis=-1)                           # (B,H,1)
        if seq_axes:
            m = jax.lax.pmax(m_loc, seq_axes if len(seq_axes) > 1
                             else seq_axes[0])
        else:
            m = m_loc
        safe_m = jnp.where(m > NEG_INF / 2, m, 0.0)
        p = jnp.exp(logits - safe_m[..., None])
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        s_loc = jnp.sum(p, axis=-1)                                # (B,H,1)
        o_loc = jnp.einsum("bhst,bthd->bshd", p.astype(q_l.dtype), v_rep)
        if seq_axes:
            ax = seq_axes if len(seq_axes) > 1 else seq_axes[0]
            s = jax.lax.psum(s_loc, ax)
            o = jax.lax.psum(o_loc.astype(jnp.float32), ax)
        else:
            s, o = s_loc, o_loc.astype(jnp.float32)
        out = o / jnp.maximum(s, 1e-30).transpose(0, 2, 1)[..., None]
        return out.astype(q_l.dtype)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(spec_q, spec_c, spec_c, spec_l),
        out_specs=spec_q, check_vma=False,
    )(q, k_cache, v_cache, lengths)


def cache_insert(cache_arr, new_val, lengths, *, mode: str = "scatter",
                 mesh=None, rules=None):
    """Insert new_val (B, 1, ...) into cache (B, T, ...) at per-batch
    position `lengths`.

    mode="scatter": gather/scatter update — natural but hostile to a
    seq-sharded cache (GSPMD replicates the operand: "involuntary full
    rematerialization", measured as a full-cache all-gather per layer).
    mode="blend": one-hot masked rewrite — elementwise, but the traffic
    model charges a full cache rewrite (measured worse; kept as a
    refuted-hypothesis record, see EXPERIMENTS.md §Perf).
    mode="shard": shard_map update — each chip scatters into its local
    (batch, seq) tile only when the position falls inside it; exactly
    partitioned, zero collectives.
    """
    B, T = cache_arr.shape[:2]
    if mode == "shard" and mesh is not None:
        return _cache_insert_shardmap(cache_arr, new_val, lengths, mesh, rules)
    if mode == "blend":
        onehot = (jnp.arange(T, dtype=jnp.int32)[None, :]
                  == lengths[:, None])                       # (B, T)
        oh = onehot.reshape(B, T, *([1] * (cache_arr.ndim - 2)))
        newb = new_val[:, :1].astype(cache_arr.dtype)        # (B,1,...)
        return jnp.where(oh, newb, cache_arr)
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


def _cache_insert_shardmap(cache_arr, new_val, lengths, mesh, rules):
    import numpy as np

    from repro.common.sharding import spec_for

    nd = cache_arr.ndim
    axes_c = ("cache_batch", "cache_seq") + (None,) * (nd - 2)
    spec_c = spec_for(cache_arr.shape, axes_c, rules, mesh)
    axes_n = ("cache_batch", None) + (None,) * (nd - 2)
    spec_n = spec_for(new_val.shape, axes_n, rules, mesh)
    spec_l = spec_for(lengths.shape, ("cache_batch",), rules, mesh)
    t_entry = spec_c[1]

    def f(c, nv, ln):
        B_loc, T_loc = c.shape[:2]
        t_off = 0
        if t_entry is not None:
            names = t_entry if isinstance(t_entry, tuple) else (t_entry,)
            idx = 0
            for a in names:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
            t_off = idx * T_loc
        pos = ln - t_off                                     # (B_loc,)
        inb = (pos >= 0) & (pos < T_loc)
        posc = jnp.clip(pos, 0, T_loc - 1)
        rows = jnp.arange(B_loc)
        old = c[rows, posc]
        mask = inb.reshape(-1, *([1] * (nd - 2)))
        new_rows = jnp.where(mask, nv[:, 0].astype(c.dtype), old)
        return c.at[rows, posc].set(new_rows)

    return jax.shard_map(
        f, mesh=mesh, in_specs=(spec_c, spec_n, spec_l), out_specs=spec_c,
        check_vma=False,
    )(cache_arr, new_val, lengths)
