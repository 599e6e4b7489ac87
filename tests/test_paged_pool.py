"""Paged KV arena (DESIGN.md §12): page lifecycle, fixed-budget
exhaustion, table-widening buffer growth, kernel-level page-table
indirection, and paged-vs-contiguous bit-identity of the slot model
ops — the contiguous arena is the oracle throughout."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    decode_attention_op,
    decode_attention_paged_op,
    flash_attention_op,
    flash_attention_paged_op,
    gather_kv_pages,
)
from repro.models import (
    CachePool,
    ModelConfig,
    PagePoolExhausted,
    PagedCachePool,
    decode_step_slots,
    decode_step_slots_paged,
    init_params,
    prefill_slots,
    prefill_slots_paged,
    verify_step_slots,
    verify_step_slots_paged,
)

CFG = ModelConfig(name="p", family="dense", num_layers=2, d_model=32,
                  num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
                  vocab_size=32, dtype="float32")


def make_paged(slots=2, rows=2, buf=16, page=4, num_pages=None):
    return PagedCachePool({"m": CFG}, num_slots=slots, rows_per_slot=rows,
                          buf_len=buf, page_size=page, num_pages=num_pages)


# ---- page lifecycle ---------------------------------------------------

def test_detach_attach_round_trips_chains_and_content():
    # Suspend/resume primitives (DESIGN.md §12): detach parks a slot's
    # chains in a handle (pages stay resident, table rows zero, slot
    # free), attach re-binds them to ANY free slot with the bytes and
    # position intact — a host table rewrite, zero recompute.
    pool = make_paged(num_pages=16)
    s0 = pool.alloc()
    pool.reserve(s0, 7)
    pool.set_pos(s0, 7)
    rows = pool.rows_of(s0)
    chains_before = pool.page_table[rows].copy()
    held = pool.held_pages(s0)
    free_before = pool.free_pages

    handle = pool.detach(s0)
    # Slot freed, table rows zeroed — but the pages did NOT return to
    # the free heap: the handle owns them.
    assert (pool.page_table[rows] == 0).all()
    assert pool.free_pages == free_before

    # Re-attach to a DIFFERENT slot: same chains, same pos.
    s1 = pool.alloc()
    assert s1 == s0          # detach freed the slot (lowest-free-first)
    s2 = pool.alloc()
    assert s2 != s0
    pool.attach(s2, handle)
    np.testing.assert_array_equal(
        pool.page_table[pool.rows_of(s2)], chains_before)
    assert pool.pos[s2] == 7
    assert pool.held_pages(s2) == held

    # Dropping a handle (strip demotion) returns its pages to the heap.
    h2 = pool.detach(s2)
    pool.release_handle(h2)
    assert pool.free_pages == free_before + held
    assert h2["chain_len"] == 0

def test_reserve_is_lowest_free_page_first_in_row_lockstep():
    pool = make_paged(num_pages=16)
    s = pool.alloc()
    pool.reserve(s, 5)                       # ceil(5/4)=2 pages x 2 rows
    assert pool.held_pages(s) == 4
    assert pool.free_pages == 12
    rows = pool.rows_of(s)
    # Deterministic allocation: lowest physical pages first, rows in
    # lockstep (chains advance together because positions are shared).
    assert sorted(pool.page_table[rows, :2].reshape(-1).tolist()) == \
        [1, 2, 3, 4]
    assert (pool.page_table[rows, 2:] == 0).all()
    pool.reserve(s, 5)                       # idempotent: already covered
    assert pool.held_pages(s) == 4


def test_release_returns_pages_and_zeroes_table_rows():
    pool = make_paged(num_pages=16)
    a, b = pool.alloc(), pool.alloc()
    pool.reserve(a, 8)
    pool.reserve(b, 4)
    held_a = pool.held_pages(a)
    pool.release(a)
    assert (pool.page_table[pool.rows_of(a)] == 0).all()
    assert pool.free_pages == 16 - pool.held_pages(b)
    # released pages are reallocated lowest-first: slot a held the
    # lowest physical pages, so the next reservation reuses them.
    c = pool.alloc()
    pool.reserve(c, 8)
    assert pool.held_pages(c) == held_a
    assert pool.page_table[pool.rows_of(c), 0].min() == 1


def test_fixed_budget_exhaustion_raises_without_partial_state():
    pool = make_paged(num_pages=4)           # room for 4 pages total
    s = pool.alloc()
    pool.reserve(s, 8)                       # 2 pages x 2 rows = all 4
    table_before = pool.page_table.copy()
    with pytest.raises(PagePoolExhausted):
        pool.reserve(s, 9)                   # needs a 3rd page per row
    np.testing.assert_array_equal(pool.page_table, table_before)
    assert pool.free_pages == 0
    pool.release(s)
    assert pool.free_pages == 4


def test_auto_grow_doubles_storage_with_stable_page_indices():
    pool = make_paged(buf=16, page=4, num_pages=None)
    total0 = pool.num_pages
    s = pool.alloc()
    pool.reserve(s, 16)
    rows = pool.rows_of(s)
    chains = pool.page_table[rows].copy()
    pool.ensure_buf(2 * pool.buf_len)        # widening only
    t = pool.alloc()
    pool.reserve(t, 32)                      # overflows the initial pool
    assert pool.num_pages > total0
    # Growth never remaps: the first slot's chain entries are unchanged.
    np.testing.assert_array_equal(pool.page_table[rows, :chains.shape[1]],
                                  chains)


def test_ensure_buf_is_table_widening_not_storage_copy():
    pool = make_paged(num_pages=8)
    leaf_before = pool.pages["m"]["k"]
    n_lp0 = pool.page_table.shape[1]
    pool.ensure_buf(32)
    assert pool.buf_len == 32
    assert pool.page_table.shape[1] > n_lp0
    assert pool.pages["m"]["k"] is leaf_before   # no whole-pool regrowth
    pool.ensure_buf(16)                          # monotonic: no shrink
    assert pool.buf_len == 32


def test_contiguous_caches_attr_fails_loudly():
    pool = make_paged()
    with pytest.raises(AttributeError):
        pool.caches["m"]


# ---- kernel-level page-table indirection ------------------------------

def _random_pages(key, p=6, hkv=2, page=4, d=8):
    pages = jax.random.normal(key, (p, hkv, page, d), jnp.float32)
    return pages.at[0].set(0.0)              # physical page 0 is the zero page


def test_gather_kv_pages_matches_manual_chain():
    pages = _random_pages(jax.random.PRNGKey(0))
    table = jnp.array([[1, 3, 0], [2, 4, 5]], jnp.int32)
    got = gather_kv_pages(pages, table, 10)
    pg = np.asarray(pages)
    for b, chain in enumerate(np.asarray(table)):
        want = np.concatenate([pg[p] for p in chain], axis=1)[:, :10]
        np.testing.assert_array_equal(np.asarray(got[b]), want)
    # unmapped entries resolve to zeros
    assert not np.asarray(got[0, :, 8:]).any()


def test_attention_paged_ops_bit_identical_to_contiguous():
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(1), 3)
    k_pages = _random_pages(k0)
    v_pages = _random_pages(k1)
    table = jnp.array([[1, 3, 0], [2, 4, 5]], jnp.int32)
    buf = 10
    k = gather_kv_pages(k_pages, table, buf)
    v = gather_kv_pages(v_pages, table, buf)
    kv_len = jnp.array([7, 10], jnp.int32)

    q1 = jax.random.normal(k2, (2, 4, 8), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(decode_attention_paged_op(
            q1, k_pages, v_pages, table, kv_len, buf_len=buf,
            use_kernel=False)),
        np.asarray(decode_attention_op(q1, k, v, kv_len,
                                       use_kernel=False)))

    qs = jax.random.normal(k2, (2, 4, 3, 8), jnp.float32)
    qo = jnp.array([4, 7], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(flash_attention_paged_op(
            qs, k_pages, v_pages, table, qo, kv_len, buf_len=buf,
            use_kernel=False)),
        np.asarray(flash_attention_op(qs, k, v, qo, kv_len,
                                      use_kernel=False)))


# ---- model-op bit-identity: paged vs contiguous -----------------------

@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _prefilled_pair(params, pos=5):
    """Contiguous and paged pools holding identical prefilled state in
    slot 0; slot 1 stays dead (unmapped / zero rows)."""
    cpool = CachePool({"m": CFG}, num_slots=2, rows_per_slot=2, buf_len=16)
    ppool = make_paged(slots=2, rows=2, buf=16, page=4)
    sc, sp = cpool.alloc(), ppool.alloc()
    assert sc == sp == 0
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, pos), 0, 32)
    full = jnp.zeros((4, pos), jnp.int32).at[:2].set(toks)
    start = jnp.zeros((4,), jnp.int32)
    write = jnp.array([True, True, False, False])
    cpool.update("m", prefill_slots(params, CFG, full, cpool.caches["m"],
                                    start, write))
    ppool.reserve(sp, pos)
    ppool.update("m", prefill_slots_paged(
        params, CFG, full, ppool.pages["m"], ppool.pt_device(), start,
        write, buf_len=ppool.buf_len))
    cpool.set_pos(sc, pos)
    ppool.set_pos(sp, pos)
    return cpool, ppool, 0


def test_prefill_scatter_bit_identical(params):
    cpool, ppool, slot = _prefilled_pair(params)
    rows = cpool.rows_of(slot)
    got = ppool.materialize("m")
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(got[leaf])[:, rows],
            np.asarray(cpool.caches["m"][leaf])[:, rows])


def test_decode_and_verify_steps_bit_identical(params):
    cpool, ppool, slot = _prefilled_pair(params)
    rows = cpool.rows_of(slot)
    pos = jnp.asarray(cpool.row_positions())
    tok1 = jax.random.randint(jax.random.PRNGKey(2), (4, 1), 0, 32)

    ppool.reserve(slot, int(cpool.pos[slot]) + 1)
    lc, nc = decode_step_slots(params, CFG, tok1, cpool.caches["m"], pos)
    lp, np_ = decode_step_slots_paged(params, CFG, tok1, ppool.pages["m"],
                                      ppool.pt_device(), pos, buf_len=16)
    np.testing.assert_array_equal(np.asarray(lc)[rows], np.asarray(lp)[rows])
    cpool.update("m", nc)
    ppool.update("m", np_)

    pos = pos + 1
    cpool.set_pos(slot, int(cpool.pos[slot]) + 1)
    ppool.set_pos(slot, int(ppool.pos[slot]) + 1)
    tokm = jax.random.randint(jax.random.PRNGKey(3), (4, 3), 0, 32)
    ppool.reserve(slot, int(cpool.pos[slot]) + 3)
    lc, nc = verify_step_slots(params, CFG, tokm, cpool.caches["m"], pos)
    lp, np_ = verify_step_slots_paged(params, CFG, tokm, ppool.pages["m"],
                                      ppool.pt_device(), pos, buf_len=16)
    np.testing.assert_array_equal(np.asarray(lc)[rows], np.asarray(lp)[rows])
    cpool.update("m", nc)
    ppool.update("m", np_)

    # rollback: replicate row content through winner lanes
    row_src = np.array([1, 1, 2, 3], np.int32)
    cpool.rollback_rows(row_src)
    ppool.rollback_rows(row_src)
    got = ppool.materialize("m")
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(got[leaf])[:, rows],
            np.asarray(cpool.caches["m"][leaf])[:, rows])


def test_dead_rows_gather_zeros(params):
    _, ppool, _ = _prefilled_pair(params)
    dead = ppool.rows_of(1)
    got = ppool.materialize("m")
    assert not np.asarray(got["k"])[:, dead].any()
    assert not np.asarray(got["v"])[:, dead].any()
