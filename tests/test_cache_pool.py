"""Slot-based KV-cache arena: slot lifecycle, buffer growth, prefill
into slot rows, and rollback-by-row-replication (DESIGN.md §7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import CachePool, ModelConfig, init_params, prefill_slots

CFG = ModelConfig(name="p", family="dense", num_layers=2, d_model=32,
                  num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
                  vocab_size=32, dtype="float32")


def make_pool(slots=3, rows=2, buf=16):
    return CachePool({"m": CFG}, num_slots=slots, rows_per_slot=rows,
                     buf_len=buf)


def prefill_slot(pool, params, slot, toks):
    """Prefill ``toks`` (rows_per_slot, n) into ``slot``'s rows of arena
    "m" the way admission does: one arena-wide ``prefill_slots`` with
    every other row write-masked."""
    rows = pool.rows_of(slot)
    n_rows = pool.num_slots * pool.rows_per_slot
    full = jnp.zeros((n_rows, toks.shape[1]), jnp.int32).at[rows].set(toks)
    write = np.zeros((n_rows,), bool)
    write[rows] = True
    pool.update("m", prefill_slots(params, CFG, full, pool.caches["m"],
                                   jnp.zeros((n_rows,), jnp.int32),
                                   jnp.asarray(write)))
    pool.set_pos(slot, toks.shape[1])


def test_alloc_is_lowest_free_slot_first():
    pool = make_pool()
    assert [pool.alloc(), pool.alloc(), pool.alloc()] == [0, 1, 2]
    with pytest.raises(RuntimeError):
        pool.alloc()
    pool.release(1)
    pool.release(0)
    assert pool.alloc() == 0          # lowest free wins, not LIFO
    assert pool.alloc() == 1
    assert pool.num_free == 0


def test_release_resets_position():
    pool = make_pool()
    slot = pool.alloc()
    pool.pos[slot] = 7
    pool.release(slot)
    assert pool.pos[slot] == 0
    with pytest.raises(AssertionError):
        pool.release(slot)            # double free


def test_row_positions_and_free_default():
    pool = make_pool(slots=2, rows=3)
    s = pool.alloc()
    pool.pos[s] = 5
    got = pool.row_positions()
    assert got.tolist() == [5, 5, 5, 0, 0, 0]


def test_write_prefill_and_rollback_replication():
    pool = make_pool(slots=2, rows=2, buf=16)
    params = init_params(jax.random.PRNGKey(0), CFG)
    slot = pool.alloc()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 32)
    prefill_slot(pool, params, slot, toks)
    assert pool.pos[slot] == 5
    k = np.asarray(pool.caches["m"]["k"])
    # The slot's rows hold the prompt's KV in positions 0..4 only; the
    # other slot's rows are untouched.
    assert np.abs(k[:, 0:2, :, :5]).max(axis=-1).all()
    assert not k[:, 0:2, :, 5:].any() and not k[:, 2:4].any()
    # Replicate row 1 of slot 0 across the slot; slot 1 untouched.
    pool.rollback_rows(np.array([1, 1, 2, 3]))
    got = np.asarray(pool.caches["m"]["k"])
    np.testing.assert_array_equal(got[:, 0], k[:, 1])
    np.testing.assert_array_equal(got[:, 1], k[:, 1])
    np.testing.assert_array_equal(got[:, 2:4], k[:, 2:4])


def test_ensure_buf_grows_and_preserves_content():
    pool = make_pool(slots=1, rows=2, buf=8)
    params = init_params(jax.random.PRNGKey(0), CFG)
    slot = pool.alloc()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 32)
    prefill_slot(pool, params, slot, toks)
    old_k = np.asarray(pool.caches["m"]["k"])
    pool.ensure_buf(20)
    assert pool.buf_len == 20
    new_k = np.asarray(pool.caches["m"]["k"])
    assert new_k.shape[3] == 20
    np.testing.assert_array_equal(new_k[:, :, :, :8], old_k)
    assert not new_k[:, :, :, 8:].any()
    pool.ensure_buf(10)               # never shrinks
    assert pool.buf_len == 20


def test_prefill_of_one_slot_leaves_other_slots_bit_untouched():
    """Admission into a second slot rewrites only its own rows: the
    first slot's prefilled KV and position survive bit for bit."""
    pool = make_pool(slots=2, rows=2, buf=16)
    params = init_params(jax.random.PRNGKey(0), CFG)
    a, b = pool.alloc(), pool.alloc()
    prefill_slot(pool, params, a,
                 jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 32))
    before = {kk: np.asarray(v) for kk, v in pool.caches["m"].items()}
    prefill_slot(pool, params, b,
                 jax.random.randint(jax.random.PRNGKey(2), (2, 7), 0, 32))
    for kk, v in pool.caches["m"].items():
        got = np.asarray(v)
        np.testing.assert_array_equal(got[:, 0:2], before[kk][:, 0:2])
        assert got[:, 2:4, :, :7].any() and not got[:, 2:4, :, 7:].any()
    assert (pool.pos[a], pool.pos[b]) == (5, 7)


def test_ring_caches_rejected():
    swa = CFG.replace(name="swa", sliding_window=8)
    with pytest.raises(AssertionError):
        CachePool({"m": swa}, num_slots=1, rows_per_slot=1, buf_len=16)
