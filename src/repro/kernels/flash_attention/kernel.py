"""Pallas TPU flash-attention (prefill) kernel with GQA, causal and
sliding-window masking, and per-row offsets for cache-arena prefill.

Tiling: grid (B, H, S/TQ, T/TK); online-softmax carry (m, l, acc) lives in
VMEM scratch across the sequential KV-tile axis.  Block shapes keep the
MXU busy (TQ x D and TK x D tiles, lane dim = head_dim, sublane = seq) and
the working set ~ (TQ + 2*TK) * D * 4B well under VMEM.  KV heads are
indexed as h // group so grouped query heads reuse the same KV tiles
(no repeated-KV materialization in HBM).

Arena prefill (DESIGN.md §9): each batch row may sit at its own decode
position, so the kernel takes per-row ``q_offset`` (position of the
row's first query) and ``kv_len`` (valid KV prefix length) as SMEM
scalars — the same per-row masking contract as the dense
``layers.attention`` path and the decode-attention kernel.  Rows whose
queries are entirely masked (bucket padding) emit zeros, not NaN.

int8 KV arenas (DESIGN.md §11) pass per-KV-vector scales ``k_scale`` /
``v_scale`` (B, Hkv, T, 1), dequantized in-kernel tile by tile so the
HBM stream stays int8.  Execution mode follows ``resolve_pallas_mode``:
``interpret=None`` compiles on TPU/GPU and falls back to the
bit-for-bit jnp reference elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.pallas_mode import resolve_pallas_mode

DEFAULT_TQ = 256
DEFAULT_TK = 256


def _kernel(q_off_ref, kv_len_ref, q_ref, k_ref, v_ref, *refs,
            tq: int, tk: int, n_kv: int,
            causal: bool, window: int, t_real: int, quant: bool):
    if quant:
        k_s_ref, v_s_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)      # (TQ, D)
    k = k_ref[0, 0].astype(jnp.float32)      # (TK, D)
    v = v_ref[0, 0].astype(jnp.float32)
    if quant:
        k = k * k_s_ref[0, 0]                # (TK, 1) broadcasts over D
        v = v * v_s_ref[0, 0]
    d = q.shape[-1]
    q_off = q_off_ref[pl.program_id(0)]
    kv_len = kv_len_ref[pl.program_id(0)]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))
    q_pos = q_off + iq * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    k_pos = ik * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    mask = (k_pos < t_real) & (k_pos < kv_len)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, -jnp.inf)

    # Masked-row contract shared with ref.py's masked_softmax: a row
    # whose running max never leaves -inf (fully masked so far) pins the
    # exp argument at -inf via m_safe, so its weights are exactly 0.0 —
    # never a NaN that needs scrubbing after the fact.
    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[:, 0] = m_new

    @pl.when(ik == n_kv - 1)
    def _emit():
        # Fully-masked query rows (bucket padding, kv_len == 0) have
        # l == 0; the 1e-30 floor turns them into zeros rather than
        # NaN — matching ref.py's masked_softmax denominator floor
        # bitwise.  Rows with any valid key have l >= 1 (the max entry
        # contributes exp(0) = 1), so the floor is inert there.
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "tq", "tk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_offset: jax.Array = None, kv_len: jax.Array = None,
                    k_scale: jax.Array = None, v_scale: jax.Array = None, *,
                    causal: bool = True, window: int = 0,
                    tq: int = DEFAULT_TQ, tk: int = DEFAULT_TK,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, Hkv, T, D) -> (B, H, S, D).

    ``q_offset``/``kv_len`` are optional (B,) i32 per-row masks: row b's
    queries sit at positions ``q_offset[b] + arange(S)`` and attend only
    keys below ``kv_len[b]`` (defaults: offset 0, full T).
    ``k_scale``/``v_scale`` (B, Hkv, T, 1), both or neither: per-KV-vector
    dequant scales for int8 k/v, applied in-kernel tile by tile.
    """
    assert (k_scale is None) == (v_scale is None)
    quant = k_scale is not None
    mode = resolve_pallas_mode(interpret)
    if mode == "fallback":
        return flash_attention_ref(q, k, v, q_offset, kv_len, k_scale,
                                   v_scale, causal=causal, window=window)
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    tq = min(tq, s)
    tk = min(tk, t)
    if s % tq:
        qpad = tq - s % tq
        q = jnp.pad(q, ((0, 0), (0, 0), (0, qpad), (0, 0)))
    if t % tk:
        kpad = tk - t % tk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, kpad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, kpad), (0, 0)))
        if quant:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, 0), (0, kpad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, 0), (0, kpad), (0, 0)))
    s_pad, t_pad = q.shape[2], k.shape[2]
    n_q, n_kv = s_pad // tq, t_pad // tk
    if q_offset is None:
        q_offset = jnp.zeros((b,), jnp.int32)
    if kv_len is None:
        kv_len = jnp.full((b,), t, jnp.int32)
    q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))

    kernel = functools.partial(_kernel, tq=tq, tk=tk, n_kv=n_kv,
                               causal=causal, window=window, t_real=t,
                               quant=quant)
    in_specs = [
        # Whole (B,) offset/length vectors in SMEM, indexed by the
        # batch program id: a (1,) block of a rank-1 array is not a
        # legal TPU tile (it must equal the array or be a multiple of
        # 128).
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, tq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        pl.BlockSpec((1, 1, tk, d),
                     lambda b_, h_, iq, ik, g=g: (b_, h_ // g, ik, 0)),
        pl.BlockSpec((1, 1, tk, d),
                     lambda b_, h_, iq, ik, g=g: (b_, h_ // g, ik, 0)),
    ]
    operands = [q_offset, kv_len, q, k, v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, 1, tk, 1),
                         lambda b_, h_, iq, ik, g=g: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, tk, 1),
                         lambda b_, h_, iq, ik, g=g: (b_, h_ // g, ik, 0)),
        ]
        operands += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, tq, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),   # running max m
            pltpu.VMEM((tq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((tq, d), jnp.float32),   # running numerator acc
        ],
        interpret=(mode == "interpret"),
    )(*operands)
    return out[:, :, :s]
