"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode + hypothesis on decode lengths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                                  # property tests need hypothesis; the
    import hypothesis.strategies as st   # rest of the file runs without it
    from hypothesis import given, settings
except ModuleNotFoundError:           # pragma: no cover - minimal install
    st = None

from repro.common.config import get_config
from repro.kernels import ops, ref
from repro.layers import attention as attn

TOLS = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
        jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,K,D,bq,bk", [
    (1, 32, 2, 2, 16, 16, 16),
    (2, 64, 4, 2, 32, 16, 32),     # GQA 2:1
    (1, 128, 8, 1, 16, 32, 32),    # MQA
    (2, 64, 4, 4, 64, 64, 16),     # MHA, tall blocks
])
def test_flash_attention_sweep(B, S, H, K, D, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, K, D), dtype)
    v = jax.random.normal(ks[2], (B, S, K, D), dtype)
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    expect = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **TOLS[dtype])


@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (False, 0, 0.0), (True, 16, 0.0), (True, 8, 50.0),
])
def test_flash_attention_variants(causal, window, softcap):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 64, 2, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=16, block_k=16,
                              interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                     softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


if st is not None:
    @settings(max_examples=10, deadline=None)
    @given(l1=st.integers(1, 64), l2=st.integers(1, 64))
    def test_decode_attention_random_lengths(l1, l2):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        B, H, K, D, T = 2, 4, 2, 16, 64
        q = jax.random.normal(ks[0], (B, H, D))
        k = jax.random.normal(ks[1], (B, T, K, D))
        v = jax.random.normal(ks[2], (B, T, K, D))
        lengths = jnp.array([l1, l2], jnp.int32)
        out = ops.decode_attention(q, k, v, lengths, block_k=16,
                                   interpret=True)
        expect = ref.decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_decode_attention_random_lengths():
        pass


def _paged_from_contiguous(k, v, page_size, *, seed=0, spare=1):
    """Scatter contiguous (B,T,K,D) caches into a shuffled page pool;
    returns (k_pages, v_pages, block_tables)."""
    B, T, K, D = k.shape
    n_max = -(-T // page_size)
    n_pages = B * n_max + spare
    perm = np.random.default_rng(seed).permutation(n_pages - 1) + 1
    tables = np.asarray(perm[:B * n_max].reshape(B, n_max), np.int32)
    kp = np.zeros((n_pages, page_size, K, D), np.float32)
    vp = np.zeros((n_pages, page_size, K, D), np.float32)
    for b in range(B):
        for j in range(n_max):
            lo = j * page_size
            sl = np.asarray(k[b, lo:lo + page_size])
            kp[tables[b, j], :sl.shape[0]] = sl
            vp[tables[b, j], :sl.shape[0]] = np.asarray(
                v[b, lo:lo + page_size])
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables)


@pytest.mark.parametrize("B,H,K,D,T,ps,softcap", [
    (2, 4, 2, 16, 64, 16, 0.0),    # GQA 2:1
    (1, 8, 1, 16, 48, 8, 0.0),     # MQA, ragged last page
    (2, 4, 4, 32, 64, 16, 30.0),   # MHA + logit softcap
])
def test_paged_decode_attention_matches_oracles(B, H, K, D, T, ps, softcap):
    """The batched paged kernel == its paged oracle == the contiguous
    decode oracle over the same logical cache (pages shuffled)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, T, K, D))
    v = jax.random.normal(ks[2], (B, T, K, D))
    lengths = jnp.asarray([T - i * 7 - 1 for i in range(B)], jnp.int32)
    kp, vp, tables = _paged_from_contiguous(k, v, ps)
    out = ops.paged_decode_attention(q, kp, vp, tables, lengths,
                                     softcap=softcap, interpret=True)
    paged_ref = ref.paged_decode_attention_ref(q, kp, vp, tables, lengths,
                                               softcap=softcap)
    contig_ref = ref.decode_attention_ref(q, k, v, lengths, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(paged_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(contig_ref),
                               rtol=2e-4, atol=2e-4)


if st is not None:
    @settings(max_examples=8, deadline=None)
    @given(l1=st.integers(1, 64), l2=st.integers(1, 64))
    def test_paged_decode_attention_random_lengths(l1, l2):
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        B, H, K, D, T, ps = 2, 4, 2, 16, 64, 16
        q = jax.random.normal(ks[0], (B, H, D))
        k = jax.random.normal(ks[1], (B, T, K, D))
        v = jax.random.normal(ks[2], (B, T, K, D))
        lengths = jnp.array([l1, l2], jnp.int32)
        kp, vp, tables = _paged_from_contiguous(k, v, ps, seed=l1 * 65 + l2)
        out = ops.paged_decode_attention(q, kp, vp, tables, lengths,
                                         interpret=True)
        expect = ref.decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_paged_decode_attention_random_lengths():
        pass


def test_paged_decode_attention_reads_the_model_pool():
    """The paged kernel reads one layer of the model's stacked page pool
    (layers, n_pages, page_size, W), each token's K*D row zero-padded to
    whole 128-lane tiles, through ``from_pool_rows``, and gives what the
    served XLA path (``paged_gather`` + ``gqa_scores``) gives on live
    rows, after this step's rows are written with ``paged_cache_insert``."""
    cfg = get_config("internvl2-1b", smoke=True)
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_layers, n_pages, ps, layer = 2, 16, 4, 1
    # rows 0, 1 and 3 live, row 2 dead; row 1 writes the first slot of
    # a new page
    tables = jnp.asarray([[3, 7, 1, 12], [5, 2, 9, 0], [0, 0, 0, 0],
                          [4, 8, 11, 6]], jnp.int32)
    lengths = jnp.asarray([3, 8, 0, 9], jnp.int32)
    B, row = tables.shape[0], (K, D)
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    width = attn.pool_row_width(K * D)
    assert width % 128 == 0 and width > K * D
    # earlier tokens: random rows, zero padding lanes
    pools = [jnp.zeros((n_layers, n_pages, ps, width)).at[..., :K * D].set(
        jax.random.normal(kk, (n_layers, n_pages, ps, K * D)))
        for kk in ks[:2]]
    k_pool, v_pool = (
        attn.paged_cache_insert(pool, layer,
                                jax.random.normal(kk, (B, 1, K, D)),
                                tables, lengths)
        for pool, kk in zip(pools, ks[2:4]))
    q = jax.random.normal(ks[4], (B, 1, H, D))
    out = ops.paged_decode_attention(
        q[:, 0], attn.from_pool_rows(k_pool[layer], row),
        attn.from_pool_rows(v_pool[layer], row), tables, lengths + 1,
        softcap=cfg.attn_logit_softcap, interpret=True)
    k_seq = attn.paged_gather(k_pool, layer, tables, row)
    v_seq = attn.paged_gather(v_pool, layer, tables, row)
    T = k_seq.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    want = attn.gqa_scores(
        q, k_seq, v_seq, q_positions=lengths[:, None], kv_positions=kv_pos,
        causal=True, softcap=cfg.attn_logit_softcap,
        kv_valid=kv_pos < (lengths + 1)[:, None])[:, 0]
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 32, 2, 8, 4, 8),
    (2, 64, 3, 8, 4, 16),
    (1, 128, 1, 16, 8, 32),
])
def test_ssd_kernel_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    Bm = jax.random.normal(ks[1], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[2], (B, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    A_log = jnp.linspace(-1.0, 0.0, H)
    y, fin = ops.ssd_chunked(x, Bm, Cm, dt, A_log, chunk=chunk,
                             interpret=True)
    ye, fe = ref.ssd_chunk_ref(x, Bm, Cm, dt, A_log)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fe),
                               rtol=3e-4, atol=3e-4)


def test_ssd_kernel_state_continuation():
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    B, S, H, P, N = 1, 64, 2, 8, 4
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    Bm = jax.random.normal(ks[1], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[2], (B, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    A_log = jnp.zeros((H,))
    _, s1 = ops.ssd_chunked(x[:, :32], Bm[:, :32], Cm[:, :32], dt[:, :32],
                            A_log, chunk=16, interpret=True)
    y2, s2 = ops.ssd_chunked(x[:, 32:], Bm[:, 32:], Cm[:, 32:], dt[:, 32:],
                             A_log, chunk=16, initial_state=s1,
                             interpret=True)
    y_full, s_full = ops.ssd_chunked(x, Bm, Cm, dt, A_log, chunk=16,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(y_full[:, 32:]), np.asarray(y2),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("B,S,H,hd,bs", [
    (1, 16, 2, 8, 8),
    (2, 32, 2, 8, 8),
    (2, 32, 4, 4, 16),
])
def test_slstm_scan_kernel(B, S, H, hd, bs):
    d = H * hd
    pre = jax.random.normal(jax.random.PRNGKey(0), (B, S, 4, d)) * 0.5
    R = jax.random.normal(jax.random.PRNGKey(1), (4, H, hd, hd)) * 0.2
    out = ops.slstm_scan(pre, R, block_s=bs, interpret=True)
    expect = ref.slstm_cell_ref(pre, R)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_slstm_scan_kernel_matches_layer_cell():
    """The kernel's cell equations == layers.xlstm.slstm_apply's scan."""
    from repro.common.config import ArchConfig
    from repro.layers import xlstm as xl
    from repro.layers.initializers import init_tree

    cfg = ArchConfig(name="x", family="ssm", n_layers=1, d_model=16,
                     n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=16)
    params = init_tree(jax.random.PRNGKey(0), xl.slstm_specs(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    # pre-activations exactly as slstm_apply computes them (post-ln)
    from repro.layers.norms import apply_norm

    xn = apply_norm(params["ln"], x, cfg.norm, cfg.norm_eps).astype(jnp.float32)
    pre = jnp.stack([
        jnp.einsum("bsd,de->bse", xn, params[f"w_{g}"].astype(jnp.float32))
        + params[f"b_{g}"].astype(jnp.float32)
        for g in ("i", "f", "z", "o")], axis=2)
    R = jnp.stack([params[f"r_{g}"] for g in ("i", "f", "z", "o")])
    h_kernel = ops.slstm_scan(pre, R, block_s=4, interpret=True)
    # oracle: the layer's own recurrence, pre-FFN (reconstruct from ref)
    h_ref = ref.slstm_cell_ref(pre, R)
    np.testing.assert_allclose(np.asarray(h_kernel), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)
