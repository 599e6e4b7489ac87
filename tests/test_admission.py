"""Batched bucketed admission (DESIGN.md §9): bucket planning, the
device-side prefill write, per-slot position invalidation, dispatch
bounds, overlap-with-round deferral — and the hard contract that none
of it changes a single emitted token versus per-request admission or
the sequential reference, for all six verification strategies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (
    CachePool,
    ModelConfig,
    init_params,
    prefill_slots,
)
from repro.specdec import (
    STRATEGIES,
    CachedSpecDecEngine,
    SpecDecConfig,
    SpecDecEngine,
    SpecDecServer,
)
from repro.specdec.engine_cached import _bucket_plan, _max_bucket

TCFG = ModelConfig(name="t", family="dense", num_layers=2, d_model=48,
                   num_heads=4, num_kv_heads=2, head_dim=12, d_ff=96,
                   vocab_size=32, dtype="float32")
DCFG = TCFG.replace(name="d", num_layers=1)


@pytest.fixture(scope="module")
def pair():
    return (init_params(jax.random.PRNGKey(0), TCFG),
            init_params(jax.random.PRNGKey(1), DCFG))


# Mixed lengths: in-bucket, exactly on a bucket boundary (17 tokens ->
# 16 prefilled == bucket), straddling boundaries, and one longer than
# the largest bucket the test arena admits (so it chunks).
PROMPT_LENS = (3, 17, 9, 33, 5, 16)


def _prompts(lens=PROMPT_LENS):
    rng = np.random.RandomState(0)
    return [rng.randint(1, 30, size=n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# Bucket planning
# ---------------------------------------------------------------------------


def test_bucket_plan_chunking_rule():
    assert _bucket_plan(0, 64) == []
    assert _bucket_plan(1, 64) == [(0, 1, 16)]
    assert _bucket_plan(16, 64) == [(0, 16, 16)]
    assert _bucket_plan(17, 64) == [(0, 17, 32)]
    assert _bucket_plan(64, 64) == [(0, 64, 64)]
    # Longer than the largest bucket: max-bucket chunks, then remainder.
    assert _bucket_plan(150, 64) == [(0, 64, 64), (64, 64, 64),
                                     (128, 22, 32)]
    # Chunks tile the prompt exactly, in order.
    for n in (0, 1, 15, 16, 17, 63, 64, 65, 200):
        plan = _bucket_plan(n, 64)
        off = 0
        for o, ln, b in plan:
            assert o == off and 0 < ln <= b and b <= 64
            off += ln
        assert off == n


def test_max_bucket_is_pow2_within_buffer():
    assert _max_bucket(16) == 16
    assert _max_bucket(63) == 32
    assert _max_bucket(64) == 64
    assert _max_bucket(65) == 64
    # Floored for tiny test arenas (oversized chunk pads drop at T).
    assert _max_bucket(8) == 16


# ---------------------------------------------------------------------------
# Device-side prefill write
# ---------------------------------------------------------------------------


def test_prefill_slots_matches_write_prefill(pair):
    """The §9 device-write contract: per-request ``admit`` (a
    one-request wave) and a multi-request bucketed wave run the same
    arena-wide, write-masked ``prefill_slots`` program, so every slot's
    rows come out bit-equal whichever way it was admitted, and rows
    outside the wave stay untouched.  (A separately shaped (K, n)
    prefill scattered into the slot rows matches only while the
    backend rounds a matmul row the same at every row count, which
    XLA:CPU's small-dot kernel does not.)"""
    tp, dp = pair
    sd = SpecDecConfig(num_drafts=2, draft_len=2, strategy="gls", top_k=0)
    S, BUF = 3, 40
    prompts = _prompts((11, 20))            # buckets 16 and 32

    one = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd, pool_slots=S)
    for uid, p in enumerate(prompts):
        one.admit(uid, p, BUF)
    wave = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd, pool_slots=S)
    wave.admit_batch(list(enumerate(prompts)), BUF)

    used = []
    for uid, p in enumerate(prompts):
        slot = one._sessions[uid].slot
        assert wave._sessions[uid].slot == slot
        assert one.pool.pos[slot] == wave.pool.pos[slot] == len(p) - 1
        used.append((one.pool.rows_of(slot), len(p) - 1))
    other = [r for r in range(S * sd.num_drafts)
             if not any(r in rows for rows, _ in used)]
    assert other
    for name in ("target", "drafter"):
        for kk in ("k", "v"):
            a = np.asarray(one.pool.caches[name][kk])
            b = np.asarray(wave.pool.caches[name][kk])
            for rows, n in used:
                np.testing.assert_array_equal(a[:, rows, :, :n],
                                              b[:, rows, :, :n])
            np.testing.assert_array_equal(b[:, other],
                                          np.zeros_like(b[:, other]))
            np.testing.assert_array_equal(a[:, other],
                                          np.zeros_like(a[:, other]))


def test_prefill_slots_kernel_route_allclose(pair):
    """prefill_kernel=True streams chunk attention through the
    flash-attention Pallas kernel: same caches up to reduction order."""
    tp, _ = pair
    S, K, BUF = 2, 2, 48
    prompt = _prompts((20,))[0]
    n = len(prompt) - 1
    caches = {}
    for use_kernel in (False, True):
        pool = CachePool({"m": TCFG}, num_slots=S, rows_per_slot=K,
                         buf_len=BUF)
        slot = pool.alloc()
        rows = pool.rows_of(slot)
        tok = np.zeros((S * K, 32), np.int32)
        write = np.zeros((S * K,), bool)
        tok[rows, :n] = prompt[:-1]
        write[rows] = True
        new = prefill_slots(tp, TCFG, jnp.asarray(tok), pool.caches["m"],
                            jnp.zeros((S * K,), jnp.int32),
                            jnp.asarray(write), use_kernel=use_kernel)
        caches[use_kernel] = np.asarray(new["k"])[:, rows, :, :n]
    np.testing.assert_allclose(caches[True], caches[False],
                               atol=2e-5, rtol=2e-5)


def test_per_slot_position_invalidation():
    """Satellite contract: a lifecycle write touches ONE device position
    element; it no longer throws away (and re-uploads) the whole array."""
    pool = CachePool({"m": TCFG}, num_slots=4, rows_per_slot=2, buf_len=32)
    s0 = pool.alloc()
    dev = pool.pos_device()
    pool.set_pos(s0, 7)
    assert pool._pos_dev is not None, \
        "per-slot touch must keep the device array alive"
    s1 = pool.alloc()
    assert pool._pos_dev is not None
    np.testing.assert_array_equal(np.asarray(pool.pos_device()),
                                  pool.pos.astype(np.int32))
    pool.release(s0)
    np.testing.assert_array_equal(np.asarray(pool.pos_device()),
                                  pool.pos.astype(np.int32))
    assert s1 == 1
    del dev


# ---------------------------------------------------------------------------
# Bit-identity of the full serving path
# ---------------------------------------------------------------------------


def _serve(pair, strategy, cache_mode, admission, prompts, max_new=5,
           max_batch=2):
    tp, dp = pair
    k = 1 if strategy in ("single", "daliri") else 2
    sd = SpecDecConfig(num_drafts=k, draft_len=2, strategy=strategy,
                       top_k=0)
    if cache_mode == "reprefill":
        eng = SpecDecEngine((tp, TCFG), [(dp, DCFG)], sd)
    else:
        eng = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd,
                                  pool_slots=max_batch)
    server = SpecDecServer(eng, max_batch=max_batch, cache_mode=cache_mode,
                           admission=admission)
    for p in prompts:
        server.submit(p, max_new=max_new)
    done = server.run(jax.random.PRNGKey(7))
    return {r.uid: list(r.output) for r in done}, server


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_bucketed_admission_bit_identical(pair, strategy):
    """Bucketed (and, under kv_fused, overlapped/deferred) admission
    must emit exactly the sequential reference's tokens — every
    strategy, prompts straddling bucket boundaries and longer than the
    largest bucket."""
    prompts = _prompts()
    ref, _ = _serve(pair, strategy, "reprefill", "per_request", prompts)
    for cache_mode in ("kv", "kv_fused"):
        out, _ = _serve(pair, strategy, cache_mode, "bucketed", prompts)
        assert out == ref, (strategy, cache_mode)


def test_admission_policies_agree(pair):
    """per_request (one-request waves) and bucketed (one multi-request
    wave) admission are interchangeable token-wise: the same prefill
    program, with other rows write-masked and prompts padded to their
    bucket, writes the same caches (the §9 bit-identity contract)."""
    prompts = _prompts()
    a, _ = _serve(pair, "gls", "kv_fused", "per_request", prompts)
    b, _ = _serve(pair, "gls", "kv_fused", "bucketed", prompts)
    assert a == b


def test_prompt_longer_than_buffer_bucket_chunks(pair):
    """A prompt longer than the largest admission bucket prefills in
    chunks and still matches the reference trace."""
    prompts = _prompts((70, 4))
    ref, _ = _serve(pair, "gls", "reprefill", "per_request", prompts,
                    max_new=4)
    out, srv = _serve(pair, "gls", "kv_fused", "bucketed", prompts,
                      max_new=4)
    assert out == ref
    # 70-token prompt: buf = 70+4+2+2 = 78 -> max bucket 64 -> 69
    # prefill tokens chunk as 64 + 5.
    assert srv.engine.num_prefill_dispatches >= 4


# ---------------------------------------------------------------------------
# Dispatch bounds and overlap scheduling
# ---------------------------------------------------------------------------


def test_admission_wave_dispatches_bounded_by_buckets(pair):
    """One admission wave of R same-bucket requests costs 2 dispatches
    (one per model), not 2R."""
    tp, dp = pair
    sd = SpecDecConfig(num_drafts=2, draft_len=2, strategy="gls", top_k=0)
    eng = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd, pool_slots=4)
    pairs = [(i, p) for i, p in enumerate(_prompts((5, 9, 12, 7)))]
    eng.admit_batch(pairs, buf_len=40)
    assert eng.num_prefill_dispatches == 2
    for uid, _ in pairs:
        eng.release(uid)

    # Two buckets (16 and 32) -> four dispatches.
    eng2 = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd, pool_slots=4)
    pairs2 = [(i, p) for i, p in enumerate(_prompts((5, 30, 12, 25)))]
    eng2.admit_batch(pairs2, buf_len=40)
    assert eng2.num_prefill_dispatches == 4


def test_overlap_defers_first_block_one_round(pair):
    """kv_fused + bucketed: a request admitted this step only prefills;
    its first tokens arrive next step (§9 join-next-round rule)."""
    tp, dp = pair
    sd = SpecDecConfig(num_drafts=2, draft_len=2, strategy="gls", top_k=0)
    eng = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd, pool_slots=2)
    server = SpecDecServer(eng, max_batch=2, cache_mode="kv_fused")
    server.submit(np.array([1, 2, 3], np.int32), max_new=4)
    key = jax.random.PRNGKey(0)
    server.step(key)
    (req,) = server.live
    assert req.output == [] and req.blocks == 0, \
        "admission round must not advance the request"
    assert server.metrics.rounds == 0
    server.step(key)
    assert len(req.output) > 0 and req.blocks == 1
    assert server.metrics.rounds == 1
