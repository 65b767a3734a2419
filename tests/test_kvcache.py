"""Paged KV allocation: PagePool lifecycle (alloc/extend/free), the
double-free guards on both allocators, LIFO page reuse, fragmentation
accounting, block-table views, and the cache scatter helpers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.layers.attention import (
    paged_cache_insert, paged_gather, pool_row_width,
)
from repro.serving.kvcache import (
    PagePool, PagesExhausted, SlotPool, insert_pages,
)


# ---- SlotPool ------------------------------------------------------------

def test_slotpool_alloc_release_cycle():
    pool = SlotPool(2)
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1}
    assert pool.alloc() is None          # exhausted -> None, not a raise
    assert pool.n_live == 2
    pool.release(a)
    assert pool.n_live == 1
    assert pool.alloc() == a             # LIFO reuse of the freed row


def test_slotpool_double_free_raises():
    pool = SlotPool(2)
    s = pool.alloc()
    pool.release(s)
    with pytest.raises(ValueError, match="double"):
        pool.release(s)
    with pytest.raises(ValueError, match="not live"):
        pool.release(1)                  # never allocated


# ---- PagePool lifecycle --------------------------------------------------

def test_pagepool_alloc_rounds_up_to_pages():
    pool = PagePool(8, page_size=4)
    assert pool.pages_for(0) == 0
    assert pool.pages_for(1) == 1
    assert pool.pages_for(4) == 1
    assert pool.pages_for(5) == 2
    pages = pool.alloc("a", 9)           # 9 tokens -> 3 pages of 4
    assert len(pages) == 3
    assert pool.n_live_pages == 3 and pool.n_free == 5
    assert pool.used_tokens["a"] == 9


def test_pagepool_zero_token_alloc_still_owns_a_page():
    pool = PagePool(4, page_size=4)
    assert len(pool.alloc("a", 0)) == 1  # block table is never empty


def test_pagepool_extend_crosses_page_boundary():
    pool = PagePool(8, page_size=4)
    pool.alloc("a", 3)
    assert pool.extend("a", 4) == []     # tail page still has room
    added = pool.extend("a", 5)          # crosses into page 2
    assert len(added) == 1
    assert pool.block_table("a") != [] and len(pool.block_table("a")) == 2
    assert pool.used_tokens["a"] == 5
    # extend never shrinks the used count
    pool.extend("a", 2)
    assert pool.used_tokens["a"] == 5


def test_pagepool_alloc_twice_same_seq_raises():
    pool = PagePool(4, page_size=4)
    pool.alloc("a", 1)
    with pytest.raises(ValueError, match="already live"):
        pool.alloc("a", 1)


def test_pagepool_double_free_raises():
    pool = PagePool(4, page_size=4)
    pool.alloc("a", 1)
    pool.free("a")
    with pytest.raises(ValueError, match="double"):
        pool.free("a")
    with pytest.raises(ValueError, match="not live"):
        pool.free("never-seen")


def test_pagepool_exhaustion_raises_and_leaves_pool_intact():
    pool = PagePool(3, page_size=4)
    pool.alloc("a", 8)                   # 2 pages
    with pytest.raises(PagesExhausted, match="need 2 pages"):
        pool.alloc("b", 5)               # would need 2, only 1 free
    assert pool.n_free == 1              # failed alloc claimed nothing
    pool.alloc("b", 4)                   # 1 page still fits
    with pytest.raises(PagesExhausted):
        pool.extend("b", 5)
    assert not pool.can_alloc(1)


def test_pagepool_lifo_reuse_after_free():
    """Freed pages are recycled hottest-first: a new sequence gets the
    pages the dead one just released, in the same order."""
    pool = PagePool(8, page_size=4)
    a_pages = pool.alloc("a", 12)
    pool.alloc("b", 4)
    pool.free("a")
    c_pages = pool.alloc("c", 12)
    assert c_pages == a_pages


def test_pagepool_fragmentation_accounting():
    pool = PagePool(8, page_size=4)
    pool.alloc("a", 5)                   # 2 pages, 5/8 tokens used
    pool.alloc("b", 4)                   # 1 page, full
    frag = pool.fragmentation()
    assert frag["pages_live"] == 3
    assert frag["tokens_capacity"] == 12
    assert frag["tokens_used"] == 9
    assert frag["slack_tokens"] == 3
    assert frag["internal_frag"] == pytest.approx(1 - 9 / 12, abs=1e-4)
    assert frag["pages_peak"] == 3
    pool.free("a")
    assert pool.fragmentation()["pages_peak"] == 3   # peak is sticky
    empty = PagePool(4, page_size=4).fragmentation()
    assert empty["internal_frag"] == 0.0


# ---- block-table views ---------------------------------------------------

def test_table_array_pads_and_guards_overflow():
    pool = PagePool(8, page_size=4)
    pool.alloc("a", 8)                   # 2 pages
    pool.alloc("b", 1)                   # 1 page
    arr = pool.table_array(["a", "b", "ghost"], n_max=3)
    assert arr.shape == (3, 3) and arr.dtype == np.int32
    assert list(arr[0, :2]) == pool.block_table("a")
    assert arr[0, 2] == 0 and arr[1, 1] == 0         # padded
    assert (arr[2] == 0).all()                       # unknown seq -> zeros
    with pytest.raises(ValueError, match="n_max"):
        pool.table_array(["a"], n_max=1)


def test_block_table_is_a_copy():
    pool = PagePool(4, page_size=4)
    pool.alloc("a", 4)
    view = pool.block_table("a")
    view.append(99)
    assert pool.block_table("a") != view


# ---- cache scatter helpers -----------------------------------------------

# paged leaves as ``paged_cache_specs`` lays them out: a token's row
# (attention's K x D, MLA's latent R or rotary r) flattened onto one
# minor axis of whole 128-lane tiles
LEAF_ROWS = {"kv_padded": (2, 3), "kv_one_tile": (2, 64), "ckv": (512,),
             "kr_padded": (64,)}


def _dense_and_pool(row, layers=2, n_pages=6, ps=4, T=8):
    n = int(np.prod(row))
    dense = jnp.arange(layers * T * n, dtype=jnp.float32).reshape(
        layers, 1, T, *row) + 1.0
    paged = jnp.zeros((layers, n_pages, ps, pool_row_width(n)))
    return dense, paged


@pytest.mark.parametrize("leaf", sorted(LEAF_ROWS))
def test_insert_pages_scatters_dense_prefill_into_pool(leaf):
    row = LEAF_ROWS[leaf]
    n = int(np.prod(row))
    dense, paged = _dense_and_pool(row)
    ps = paged.shape[2]
    assert paged.shape[3] % 128 == 0 and paged.shape[3] - n < 128
    pages = [4, 1]                       # deliberately non-contiguous
    out = insert_pages({"k": paged}, {"k": dense}, pages, n_tokens=8)
    got = np.asarray(out["k"])
    want = np.asarray(dense[:, 0]).reshape(dense.shape[0], -1, n)
    for j, pid in enumerate(pages):
        np.testing.assert_array_equal(got[:, pid, :, :n],
                                      want[:, j * ps:(j + 1) * ps])
    assert (got[..., n:] == 0).all()     # padding lanes stay zero
    # untouched pages stay zero
    for pid in set(range(paged.shape[1])) - set(pages):
        assert (got[:, pid] == 0).all()


@pytest.mark.parametrize("leaf", sorted(LEAF_ROWS))
def test_prefill_rows_land_where_the_decode_gather_reads(leaf):
    """A prefill's tokens, inserted page by page, come back in order
    through the decode step's gather of each layer; a position the
    decode step then writes lands behind them."""
    row = LEAF_ROWS[leaf]
    dense, paged = _dense_and_pool(row, T=12)   # spans the owned pages
    T = 8
    pages = [5, 2, 3]                    # 8 tokens, then room to decode
    pool = insert_pages({"k": paged}, {"k": dense}, pages, n_tokens=T)["k"]
    tables = jnp.asarray([pages, [0, 0, 0]], jnp.int32)   # row 1 dead
    lengths = jnp.asarray([T, 0], jnp.int32)
    new = jnp.full((2, 1, *row), -7.0)
    for layer in range(dense.shape[0]):
        pool = paged_cache_insert(pool, layer, new, tables, lengths)
        seq = np.asarray(paged_gather(pool, layer, tables, row))
        assert seq.shape == (2, 3 * paged.shape[2], *row)
        want = np.asarray(dense[layer, 0])
        np.testing.assert_array_equal(seq[0, :T], want[:T])
        np.testing.assert_array_equal(seq[0, T], np.asarray(new[0, 0]))
        np.testing.assert_array_equal(seq[0, T + 1:], want[T + 1:])
    # the dead row wrote only to the dummy page 0
    got = np.asarray(pool)
    n = int(np.prod(row))
    assert (got[:, 0, 0, :n] == -7).all() and (got[:, 0, 1:] == 0).all()
    for pid in set(range(1, paged.shape[1])) - set(pages):
        assert (got[:, pid] == 0).all()
