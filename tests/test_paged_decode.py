"""The paged decode step's layer loop against a per-layer reference.

The step carries each stage's stacked page pool through its layer loop
and writes each layer's new row into it in place.  The reference here
does what the step did before: it scans the layers with the pool as
per-layer ``xs``/``ys``, in the unpadded (n_pages, page_size, ...)
layout, and per layer scatters the new row into the layer's slice and
gathers the rows' pages from it.  The rest of each block is the model's
own layer functions, so the two must agree bit for bit in float32: the
logits, and every pool entry a live row owns, read back through its
block table.  Rows cross page boundaries during the ticks; a dead row
(length 0, an all-zero table) writes to the dummy page 0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import get_config
from repro.layers import attention as attn
from repro.layers import mla as mla_lib
from repro.layers import moe as moe_lib
from repro.layers.embedding import embed_apply, head_apply
from repro.layers.initializers import WSpec
from repro.layers.mlp import mlp_apply
from repro.layers.norms import apply_norm
from repro.models.api import build_model
from repro.models.lm import make_stages

F32 = jnp.float32
PS = 4                                   # page size
N_PAGES = 16
# rows 0, 1 and 3 live; row 2 dead.  Lengths before the first tick:
# rows 0 and 1 cross a page boundary during the ticks
LENGTHS = [3, 6, 0, 9]
TABLES = [[3, 7, 1, 12], [5, 2, 9, 0], [0, 0, 0, 0], [4, 8, 11, 6]]
TICKS = 5
# attention: Qwen2-style blocks, K x D = 2 x 16 padded to 128 lanes;
# MLA + MoE: a dense stage and a routed stage, latent 16 and rotary 8
ARCHS = ["internvl2-1b", "deepseek-v3-671b"]


def _old_insert(pages, new, tables, lengths):
    """The per-layer scatter into one layer's (n_pages, ps, ...) slice."""
    ps, n_max = pages.shape[1], tables.shape[1]
    page = tables[jnp.arange(new.shape[0]),
                  jnp.clip(lengths // ps, 0, n_max - 1)]
    return pages.at[page, lengths % ps].set(new[:, 0].astype(pages.dtype))


def _old_gather(pages, tables):
    B, n_max = tables.shape
    return pages[tables].reshape(B, n_max * pages.shape[1], *pages.shape[2:])


def _attn_layer(cfg, lp, h, pool, tables, lengths):
    x = apply_norm(lp["ln_attn"], h, cfg.norm, cfg.norm_eps)
    pos = lengths[:, None]
    q, k_new, v_new = attn.project_qkv(lp["attn"], x, pos, cfg)
    k = _old_insert(pool["k"], k_new, tables, lengths)
    v = _old_insert(pool["v"], v_new, tables, lengths)
    k_seq, v_seq = _old_gather(k, tables), _old_gather(v, tables)
    T = k_seq.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (len(h), T))
    out = attn.gqa_scores(
        q, k_seq, v_seq, q_positions=pos, kv_positions=kv_pos, causal=True,
        softcap=cfg.attn_logit_softcap,
        kv_valid=kv_pos < (lengths + 1)[:, None])
    return h + attn.output_proj(lp["attn"], out, x.dtype), {"k": k, "v": v}


def _mla_layer(cfg, lp, h, pool, tables, lengths):
    x = apply_norm(lp["ln_attn"], h, cfg.norm, cfg.norm_eps)
    pos = lengths[:, None]
    ckv_new, kr_new = mla_lib.mla_project_kv(lp["attn"], x, pos, cfg)
    ckv = _old_insert(pool["ckv"], ckv_new, tables, lengths)
    kr = _old_insert(pool["kr"], kr_new, tables, lengths)
    ckv_seq, kr_seq = _old_gather(ckv, tables), _old_gather(kr, tables)
    T = ckv_seq.shape[1]
    y = mla_lib.mla_attend_absorbed(
        lp["attn"], x, positions=pos, cfg=cfg, ckv_all=ckv_seq,
        kr_all=kr_seq,
        kv_valid=jnp.arange(T, dtype=jnp.int32)[None, :]
        < (lengths + 1)[:, None])
    return h + y, {"ckv": ckv, "kr": kr}


def _ref_step(cfg, params, tokens, pools, tables, lengths):
    """(logits, pools, counts) with per-layer pools (L, P, ps, ...)."""
    h = embed_apply(params["embed"], tokens, dtype=F32,
                    scale=cfg.d_model ** 0.5 if cfg.embed_scale_by_dim else 1.0)
    valid = (lengths > 0)[:, None]
    attend = _mla_layer if cfg.use_mla else _attn_layer
    new_pools, counts = {}, []
    for st in make_stages(cfg):
        use_moe = "moe" in params["stages"][st.name]["blocks"]

        def layer(h, xs, use_moe=use_moe):
            lp, pool = xs
            h, pool = attend(cfg, lp, h, pool, tables, lengths)
            x = apply_norm(lp["ln_mlp"], h, cfg.norm, cfg.norm_eps)
            if use_moe:
                y, _, c = moe_lib.moe_apply_dense(lp["moe"], x, cfg,
                                                  valid=valid)
            else:
                y, c = mlp_apply(lp["mlp"], x, cfg.act_fn), jnp.zeros((0,))
            return h + y, (pool, c)

        h, (new_pools[st.name], c) = jax.lax.scan(
            layer, h, (params["stages"][st.name]["blocks"], pools[st.name]))
        if use_moe:
            counts.append(c)
    h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
    tied = params["embed"]["table"] if cfg.tie_embeddings else None
    logits = head_apply(params.get("head"), h,
                        softcap=cfg.final_logit_softcap, tied_table=tied)
    return (logits[:, 0], new_pools,
            jnp.concatenate(counts) if counts else jnp.zeros((0, 0)))


def _unpad(pools, rows):
    """The program's (L, P, ps, W) leaves as the reference's (L, P, ps,
    ...), from each leaf's token row shape."""
    return jax.tree.map(
        lambda a, row: a[..., :int(np.prod(row))].reshape(*a.shape[:3], *row),
        pools, rows)


def _live_entries(pools, tables, lengths):
    """Every live row's owned positions, gathered through its table."""
    out = []
    for leaf in jax.tree.leaves(pools):
        for b, n in enumerate(lengths):
            if n:
                seq = np.asarray(leaf)[:, tables[b]].reshape(
                    leaf.shape[0], -1, *leaf.shape[3:])
                out.append(seq[:, :n])
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_decode_step_matches_per_layer_reference(arch):
    cfg = get_config(arch, smoke=True)
    bundle = build_model(cfg, compute_dtype=F32)
    params = bundle.init(jax.random.PRNGKey(0))
    rows = jax.tree.map(lambda ws: ws.shape[3:], bundle.cache_specs(1, 1),
                        is_leaf=lambda x: isinstance(x, WSpec))
    # a pool holding earlier tokens: random rows, zero padding lanes
    pools = bundle.init_paged_cache(N_PAGES, PS, F32)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    pools = jax.tree.map(
        lambda a, row: a.at[..., :int(np.prod(row))].set(
            jax.random.normal(next(keys), (*a.shape[:3], int(np.prod(row))))),
        pools, rows)
    ref_pools = _unpad(pools, rows)
    step = jax.jit(bundle.paged_decode_step, donate_argnums=(2,))
    ref = jax.jit(lambda *a: _ref_step(cfg, *a))
    tables = jnp.asarray(TABLES, jnp.int32)
    lengths = np.asarray(LENGTHS)
    tokens = jnp.asarray([[5], [17], [0], [42]], jnp.int32)
    for _ in range(TICKS):
        lens = jnp.asarray(lengths, jnp.int32)
        want, ref_pools, want_counts = ref(params, tokens, ref_pools, tables,
                                           lens)
        old = pools
        got, pools, counts = step(params, tokens, pools, tables, lens)
        assert all(a.is_deleted() for a in jax.tree.leaves(old))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        lengths = np.where(lengths > 0, lengths + 1, 0)
        for g, w in zip(_live_entries(_unpad(pools, rows), TABLES, lengths),
                        _live_entries(ref_pools, TABLES, lengths)):
            np.testing.assert_array_equal(g, w)
        # padding lanes hold zeros
        for a, row in zip(jax.tree.leaves(pools), jax.tree.leaves(
                rows, is_leaf=lambda x: isinstance(x, tuple))):
            assert not np.asarray(a[..., int(np.prod(row)):]).any()
        tokens = jnp.argmax(got, -1)[:, None].astype(jnp.int32)
    assert max(LENGTHS) + TICKS <= PS * len(TABLES[0])
