"""Quantized serving tests (DESIGN.md §11): int8 KV cache-pool arena
mechanics (quantize-on-write, bit-exact row replication and buffer
growth, per-vector dequant error bound), dequant-in-kernel attention
reads, and the gate that matters — quantized-vs-bf16 ACCEPTANCE-RATE
equivalence across all six verification strategies (quantization moves
logits by design, so bit-identity is the wrong contract; the coupling
quality the paper measures is acceptance)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import CachePool, ModelConfig, init_params, prefill_slots
from repro.serving.quant import dequantize_kv, quantize_kv
from repro.specdec.block_verify import RACE_STRATEGIES, RS_STRATEGIES
from repro.specdec.engine import SpecDecConfig
from repro.specdec.engine_cached import CachedSpecDecEngine


T_CFG = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                    num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                    vocab_size=64, dtype="float32")
D_CFG = dataclasses.replace(T_CFG, name="d", d_model=32, d_ff=64,
                            num_heads=2, num_kv_heads=1)


def _quant_pool(buf=16, slots=3, rows=2, quant=True):
    return CachePool({"target": T_CFG, "drafter": D_CFG}, num_slots=slots,
                     rows_per_slot=rows, buf_len=buf, quant=quant)


def _prefill_target(pool, slot, n, seed=0):
    """Prefill ``n`` seeded tokens into ``slot``'s target rows with the
    admission program (arena-wide ``prefill_slots``, other rows
    write-masked)."""
    params = init_params(jax.random.PRNGKey(seed), T_CFG)
    rows = pool.rows_of(slot)
    n_rows = pool.num_slots * pool.rows_per_slot
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (len(rows), n), 0, T_CFG.vocab_size)
    full = jnp.zeros((n_rows, n), jnp.int32).at[rows].set(toks)
    write = np.zeros((n_rows,), bool)
    write[rows] = True
    pool.update("target", prefill_slots(
        params, T_CFG, full, pool.caches["target"],
        jnp.zeros((n_rows,), jnp.int32), jnp.asarray(write)))
    pool.set_pos(slot, n)


# ---------------------------------------------------------------------------
# quantize_kv / dequantize_kv
# ---------------------------------------------------------------------------


def test_quantize_kv_roundtrip_error_bounded_per_vector():
    """|dequant(quantize(x)) - x| <= scale/2 elementwise, with scale the
    per-KV-vector max-abs/127 — the §11 arena error contract."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4, 17, 8))
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1] + (1,)
    err = np.abs(np.asarray(dequantize_kv(q, s)) - np.asarray(x))
    bound = 0.5 * np.asarray(s) + 1e-7
    assert (err <= bound).all()
    # Scales are strictly positive (1e-8 floor) even for all-zero vectors.
    z_q, z_s = quantize_kv(jnp.zeros((1, 4)))
    assert (np.asarray(z_q) == 0).all() and (np.asarray(z_s) > 0).all()


def test_quantize_kv_exact_for_representable_values():
    """Values already on the int8 grid survive the round trip exactly."""
    ints = jax.random.randint(jax.random.PRNGKey(1), (3, 5, 8), -127, 128)
    x = ints.astype(jnp.float32) * 0.03
    # Force a known scale by planting max magnitude 127 in every vector.
    x = x.at[..., 0].set(127 * 0.03)
    q, s = quantize_kv(x)
    np.testing.assert_allclose(np.asarray(dequantize_kv(q, s)),
                               np.asarray(x), rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# int8 cache-pool arena mechanics
# ---------------------------------------------------------------------------


def test_quant_pool_arena_layout_and_prefill_install():
    """Quant pools hold 4-leaf arenas; admission prefill quantizes on
    write — layer 0's KV (which no quantized read precedes) is bit-exact
    against ``quantize_kv`` of the same prefill into a dense arena, and
    rows outside the slot stay zero."""
    pool = _quant_pool()
    for arena in pool.caches.values():
        assert set(arena) == {"k", "v", "k_s", "v_s"}
        assert arena["k"].dtype == jnp.int8
        assert arena["k_s"].shape == arena["k"].shape[:-1] + (1,)
    dense = _quant_pool(quant=False)
    slot, dslot = pool.alloc(), dense.alloc()
    _prefill_target(pool, slot, 5)
    _prefill_target(dense, dslot, 5)
    rows = pool.rows_of(slot)
    arena = pool.caches["target"]
    for kk in ("k", "v"):
        q, s = quantize_kv(dense.caches["target"][kk][0, rows, :, :5])
        np.testing.assert_array_equal(
            np.asarray(arena[kk][0, rows, :, :5]), np.asarray(q))
        np.testing.assert_array_equal(
            np.asarray(arena[kk + "_s"][0, rows, :, :5]), np.asarray(s))
        assert not np.asarray(arena[kk][:, rows[-1] + 1:]).any()


def test_quant_pool_rollback_and_growth_bit_exact():
    """Row replication (rollback) and ensure_buf growth are index/copy
    ops — on a quant pool they must move int8 payloads AND their scales
    identically, bit for bit."""
    pool = _quant_pool(buf=8, slots=2, rows=2)
    slot = pool.alloc()
    _prefill_target(pool, slot, 3, seed=4)
    before = {kk: np.asarray(v) for kk, v in pool.caches["target"].items()}

    # Replicate row 1 of the slot across both its rows.
    rows = pool.rows_of(slot)
    row_src = np.arange(pool.num_slots * pool.rows_per_slot)
    row_src[rows] = rows[1]
    pool.rollback_rows(row_src)
    after = pool.caches["target"]
    for kk in before:
        np.testing.assert_array_equal(np.asarray(after[kk][:, rows[0]]),
                                      before[kk][:, rows[1]])

    # Growth preserves every live leaf bit-exactly in the old prefix.
    grown = {kk: np.asarray(v) for kk, v in after.items()}
    pool.ensure_buf(32)
    for kk, v in pool.caches["target"].items():
        assert v.shape[3] == 32
        np.testing.assert_array_equal(np.asarray(v[:, :, :, :8]), grown[kk])
        assert not np.asarray(v[:, :, :, 8:]).any()


# ---------------------------------------------------------------------------
# dequant-in-kernel attention reads
# ---------------------------------------------------------------------------


def test_attention_kernels_dequantize_in_kernel():
    """The interpret-mode kernels (same body that compiles on TPU/GPU)
    must match the scale-aware references on int8 KV + scales."""
    from repro.kernels.decode_attention.kernel import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    key = jax.random.PRNGKey(6)
    b, h, hkv, t, d, s = 3, 4, 2, 40, 16, 6
    kd = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, t, d))
    vd = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, t, d))
    k8, ks = quantize_kv(kd)
    v8, vs = quantize_kv(vd)
    q1 = jax.random.normal(jax.random.fold_in(key, 3), (b, h, d))
    kv_len = jnp.asarray([40, 11, 1], jnp.int32)
    out = decode_attention(q1, k8, v8, kv_len, ks, vs, tk=16,
                           interpret=True)
    ref = decode_attention_ref(q1, k8, v8, kv_len, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # ...and the dequantized ref stays close to the unquantized one.
    exact = decode_attention_ref(q1, kd, vd, kv_len)
    assert np.max(np.abs(np.asarray(ref) - np.asarray(exact))) < 0.05

    qs = jax.random.normal(jax.random.fold_in(key, 4), (b, h, s, d))
    q_off = jnp.asarray([0, 5, 30], jnp.int32)
    fout = flash_attention(qs, k8, v8, q_off, q_off + s, ks, vs,
                           causal=True, tq=8, tk=16, interpret=True)
    fref = flash_attention_ref(qs, k8, v8, q_off, q_off + s, ks, vs,
                               causal=True)
    np.testing.assert_allclose(np.asarray(fout), np.asarray(fref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# acceptance-rate equivalence, all six strategies
# ---------------------------------------------------------------------------


def _acceptance(quant: bool, strategy: str, seeds=(11, 12, 13),
                max_new=32):
    kt, kd = jax.random.split(jax.random.PRNGKey(0))
    tp = init_params(kt, T_CFG)
    dp = init_params(kd, D_CFG)
    cfg = SpecDecConfig(num_drafts=2, draft_len=3, strategy=strategy,
                        quant=quant)
    eng = CachedSpecDecEngine((tp, T_CFG), (dp, D_CFG), cfg, pool_slots=1)
    prompt = np.arange(1, 9, dtype=np.int32)
    acc = blocks = 0
    for seed in seeds:
        st = eng.generate(jax.random.PRNGKey(seed), prompt,
                          max_new=max_new, fused=True)
        acc += st.accepted_drafts
        blocks += st.blocks
    return acc / (blocks * cfg.draft_len)


@pytest.mark.parametrize("strategy", RACE_STRATEGIES + RS_STRATEGIES)
def test_quant_acceptance_matches_bf16_all_strategies(strategy):
    """The §11 quantization gate: int8 KV arenas + W8A8 verify must not
    move the per-strategy acceptance rate beyond statistical tolerance.
    Shared RNG (same keys both runs) removes most sampling variance, so
    the residual gap is the quantization effect itself."""
    rate_f = _acceptance(False, strategy)
    rate_q = _acceptance(True, strategy)
    assert abs(rate_q - rate_f) <= 0.2, (
        f"{strategy}: quant acceptance {rate_q:.3f} vs bf16 {rate_f:.3f}")
