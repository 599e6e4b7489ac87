"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk computation.

Per grid cell (batch, chunk, head) the kernel holds one chunk's tiles in
VMEM — x (Q, P), dt as (Q, 1) and (1, Q), B/C (Q, N) — and runs three
MXU matmuls:

  cb      = C @ B^T                       (Q x N) x (N x Q)  -> (Q, Q)
  y_intra = (cb ⊙ L_decay) @ (x·dt)       (Q x Q) x (Q x P)  -> (Q, P)
  state   = (x·dt)^T @ (B ⊙ rem)          (P x Q) x (Q x N)  -> (P, N)

with the decay matrix L built from the in-chunk cumulative log-decays
(triangle-masked sums in both orientations; double-where masked so no
inf leaks).  Q, N, P are all 64-256 —
MXU-aligned tiles, working set ≈ (2QN + QP + Q² + NP)·4B « VMEM.  The
O(seq) inter-chunk recurrence stays in jnp (lax.scan over chunk
boundaries), exactly as in the pure-jnp model path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref,
            y_ref, st_ref, tot_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)          # (Q, P)
    dt_col = dtc_ref[0, 0, 0].astype(jnp.float32)   # (Q, 1)
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)   # (1, Q)
    a = a_ref[pl.program_id(2)]                     # scalar (this head)
    b = b_ref[0, 0].astype(jnp.float32)             # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)             # (Q, N)
    q = x.shape[0]

    # In-chunk cumulative log-decay in both orientations, as masked
    # sums (no scan): cum[i] = sum_{j <= i} dt[j] * a.
    la_col = dt_col * a
    la_row = dt_row * a
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = ii >= jj                                  # lower-triangular
    cum_col = jnp.sum(jnp.where(tri, la_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(ii <= jj, la_col, 0.0), axis=0,
                      keepdims=True)
    total = jnp.sum(la_row, axis=1, keepdims=True)  # (1, 1)

    diff = jnp.where(tri, cum_col - cum_row, 0.0)
    decay = jnp.where(tri, jnp.exp(diff), 0.0)

    nt = (((1,), (1,)), ((), ()))                   # A @ B^T
    tn = (((0,), (0,)), ((), ()))                   # A^T @ B
    cb = jax.lax.dot_general(c, b, nt,
                             preferred_element_type=jnp.float32)  # (Q, Q)
    xdt = x * dt_col                                           # (Q, P)
    y = jnp.dot(cb * decay, xdt, preferred_element_type=jnp.float32)

    rem = jnp.exp(total - cum_col)                             # (Q, 1)
    state = jax.lax.dot_general(xdt, b * rem, tn,
                                preferred_element_type=jnp.float32)  # (P, N)

    y_ref[0, 0, 0] = y
    st_ref[0, 0, 0] = state
    tot_ref[0, 0, 0] = jnp.broadcast_to(total, (1, q))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(x, dt, a, b_in, c_in, *, interpret: bool = True):
    """x: (B, NC, Q, H, P); dt: (B, NC, Q, H) f32; a: (H,) f32;
    b_in/c_in: (B, NC, Q, N).  Returns (y_intra, states, total) matching
    ref.ssd_chunk_ref.

    The kernel works head-major — (Q, P) tiles of ``x`` and (1, Q) /
    (Q, 1) views of ``dt`` — because a TPU block's two minor dimensions
    must equal the array's or be (8, 128)-aligned, which a per-head
    slice of the (H, P) minor pair is not."""
    bsz, nc, q, h, p = x.shape
    n = b_in.shape[-1]
    xt = x.transpose(0, 1, 3, 2, 4)                 # (B, NC, H, Q, P)
    dtt = dt.transpose(0, 1, 3, 2)                  # (B, NC, H, Q)
    y, states, tot = pl.pallas_call(
        _kernel,
        grid=(bsz, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda b, c, hh: (b, c, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, 1), lambda b, c, hh: (b, c, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, q), lambda b, c, hh: (b, c, hh, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # whole (H,) a
            pl.BlockSpec((1, 1, q, n), lambda b, c, hh: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b, c, hh: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda b, c, hh: (b, c, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda b, c, hh: (b, c, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, q), lambda b, c, hh: (b, c, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nc, h, q, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, h, p, n), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, h, 1, q), jnp.float32),
        ],
        interpret=interpret,
    )(xt, dtt[..., None], dtt[..., None, :], a, b_in, c_in)
    return y.transpose(0, 1, 3, 2, 4), states, tot[..., 0, 0]
