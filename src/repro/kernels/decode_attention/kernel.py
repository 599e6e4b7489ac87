"""Pallas TPU decode-attention kernel: one query token per head against a
(possibly ring-buffered) KV cache.

Decode attention is memory-bound — the whole KV cache streams through
once per step — so the kernel's job is to keep that stream dense: grid
(B, Hkv, T/TK) walks KV tiles sequentially while the G grouped query
heads ride the sublane dimension, with the online-softmax carry
(m, l, acc) in VMEM.  kv_len masks the invalid tail (ring caches pass
min(pos+1, T)).

int8 KV arenas (DESIGN.md §11) pass per-KV-vector scales ``k_scale`` /
``v_scale`` (B, Hkv, T, 1): the kernel dequantizes IN the tile loop —
``k_f32 = k_int8 * scale`` right after the tile lands in VMEM — so what
streams from HBM is the 4x-smaller int8 arena plus one f32 scale per
vector, never a dequantized copy.

Execution mode follows ``resolve_pallas_mode``: ``interpret=None``
compiles on TPU/GPU and falls back to the bit-for-bit jnp reference
elsewhere; ``True`` forces the interpreter (kernel-body tests)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.pallas_mode import resolve_pallas_mode

DEFAULT_TK = 512


def _kernel(kv_len_ref, q_ref, k_ref, v_ref, *refs,
            tk: int, n_kv: int, quant: bool):
    if quant:
        k_s_ref, v_s_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)        # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)        # (TK, D)
    v = v_ref[0, 0].astype(jnp.float32)
    if quant:
        k = k * k_s_ref[0, 0]                  # (TK, 1) broadcasts over D
        v = v * v_s_ref[0, 0]
    d = q.shape[-1]
    g = q.shape[0]
    kv_len = kv_len_ref[pl.program_id(0)]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(d))                        # (G, TK)
    k_pos = ik * tk + jax.lax.broadcasted_iota(jnp.int32, (g, tk), 1)
    mask = k_pos < kv_len
    s = jnp.where(mask, s, -jnp.inf)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(mask, jnp.exp(s - m_safe[:, None]), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[:, 0] = m_new

    @pl.when(ik == n_kv - 1)
    def _emit():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tk", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, k_scale: jax.Array = None,
                     v_scale: jax.Array = None, *, tk: int = DEFAULT_TK,
                     interpret: bool | None = None) -> jax.Array:
    """q: (B, H, D); k/v: (B, Hkv, T, D); kv_len: (B,) -> (B, H, D).

    ``k_scale``/``v_scale`` (B, Hkv, T, 1), both or neither: per-KV-vector
    dequant scales for int8 k/v, applied in-kernel tile by tile."""
    assert (k_scale is None) == (v_scale is None)
    quant = k_scale is not None
    mode = resolve_pallas_mode(interpret)
    if mode == "fallback":
        return decode_attention_ref(q, k, v, kv_len, k_scale, v_scale)
    b, h, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    tk = min(tk, t)
    if t % tk:
        pad = tk - t % tk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if quant:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, 0), (0, pad), (0, 0)))
    t_pad = k.shape[2]
    n_kv = t_pad // tk
    # (B, Hkv, G, D) — grouped query heads per KV head.
    qg = q.reshape(b, hkv, g, d)
    kv_len = kv_len.astype(jnp.int32)

    kernel = functools.partial(_kernel, tk=tk, n_kv=n_kv, quant=quant)
    in_specs = [
        # The whole (B,) length vector sits in SMEM: a (1,) block of a
        # rank-1 array is not a legal TPU tile (it must equal the array
        # or be a multiple of 128), so each program reads its own entry.
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, g, d), lambda b_, h_, ik: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, tk, d), lambda b_, h_, ik: (b_, h_, ik, 0)),
        pl.BlockSpec((1, 1, tk, d), lambda b_, h_, ik: (b_, h_, ik, 0)),
    ]
    operands = [kv_len, qg, k, v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, 1, tk, 1), lambda b_, h_, ik: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, tk, 1), lambda b_, h_, ik: (b_, h_, ik, 0)),
        ]
        operands += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h_, ik: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=(mode == "interpret"),
    )(*operands)
    return out.reshape(b, h, d)
