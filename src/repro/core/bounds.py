"""Theoretical bounds from the paper.

* ``lml_bound`` — Theorem 1 (List Matching Lemma), eq. (3).
* ``lml_conditional_bound`` — Theorem 1 eq. (4): Pr[accept | Y=j].
* ``lml_relaxed_bound`` — the relaxed form  Σ_j q_j (1 + q_j/(K p_j))^-1
  derived at the end of App. A.2.
* ``conditional_lml_bound`` — Theorem 2 (compression setting).
* ``tv_distance`` / ``maximal_coupling_acceptance`` — classical 1 - d_TV.
* ``single_draft_gumbel_bound`` — Daliri et al. (1-TV)/(1+TV).
* ``iid_draft_acceptance_upper`` — Σ_j min(q_j, 1-(1-p_j)^K), the optimal
  *with-communication* upper bound for K i.i.d. drafts (used in place of
  the paper's LP optimum in Fig. 6; see DESIGN.md §6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "tv_distance",
    "maximal_coupling_acceptance",
    "single_draft_gumbel_bound",
    "lml_bound",
    "lml_conditional_bound",
    "lml_relaxed_bound",
    "conditional_lml_bound",
    "iid_draft_acceptance_upper",
    "wz_error_upper_bound",
]


def tv_distance(p: jax.Array, q: jax.Array) -> jax.Array:
    """Total variation distance between two discrete distributions."""
    return 0.5 * jnp.sum(jnp.abs(p - q), axis=-1)


def maximal_coupling_acceptance(p: jax.Array, q: jax.Array) -> jax.Array:
    """Optimal single-sample matching probability WITH communication."""
    return 1.0 - tv_distance(p, q)


def single_draft_gumbel_bound(p: jax.Array, q: jax.Array) -> jax.Array:
    """Daliri et al. communication-free bound: (1-TV)/(1+TV)."""
    tv = tv_distance(p, q)
    return (1.0 - tv) / (1.0 + tv)


def _ratio_grid(v: jax.Array) -> jax.Array:
    """r[i, j] = v_i / v_j with 0/0 -> inf kept out of the support."""
    num = v[:, None]
    den = v[None, :]
    r = num / jnp.where(den > 0, den, 1.0)
    # Columns j with v_j == 0 never have Y=j / X=j; mask handled by caller.
    return r


def lml_bound(p: jax.Array, q: jax.Array, k: int) -> jax.Array:
    """Theorem 1 eq. (3):

    Pr[Y in {X}] >= Σ_j  K / Σ_i [ max(q_i/q_j, p_i/p_j) + (K-1) q_i/q_j ].

    Terms with q_j == 0 contribute nothing (Y=j has probability 0); terms
    with p_j == 0 make the i=argmax p_i ratio blow up, correctly driving
    the j-th summand to 0.
    """
    qr = _ratio_grid(q)  # q_i / q_j at [i, j]
    pr = _ratio_grid(p)
    # Where p_j == 0, p_i/p_j should be +inf for any p_i > 0.
    pj_zero = (p <= 0)[None, :]
    pr = jnp.where(pj_zero & (p[:, None] > 0), jnp.inf, pr)
    qj_zero = (q <= 0)[None, :]
    qr = jnp.where(qj_zero & (q[:, None] > 0), jnp.inf, qr)
    denom = jnp.sum(jnp.maximum(qr, pr) + (k - 1) * qr, axis=0)  # over i, per j
    summand = k / denom
    summand = jnp.where(q > 0, summand, 0.0)
    return jnp.sum(summand)


def lml_conditional_bound(p_j: jax.Array, q_j: jax.Array, k: int) -> jax.Array:
    """Theorem 1 eq. (4): Pr[accept | Y=j] >= (1 + q_j/(K p_j))^-1."""
    return 1.0 / (1.0 + q_j / (k * jnp.maximum(p_j, jnp.finfo(jnp.float32).tiny)))


def lml_relaxed_bound(p: jax.Array, q: jax.Array, k: int) -> jax.Array:
    """Relaxed LML (end of App. A.2):  Σ_j q_j (1 + q_j/(K p_j))^-1."""
    terms = q * lml_conditional_bound(p, q, k)
    return jnp.sum(jnp.where((q > 0) & (p > 0), terms, 0.0))


def conditional_lml_bound(q_j_a: jax.Array, p_j_zk: jax.Array, k: int) -> jax.Array:
    """Theorem 2:  Pr[match | Y=j, A=a, Z^K] >= Σ_k (K + q_j(a)/p_j(z_k))^-1.

    Args:
      q_j_a: scalar — encoder target prob of the selected index.
      p_j_zk: (K,) — each decoder's target prob of that index.
    """
    tiny = jnp.finfo(jnp.float32).tiny
    return jnp.sum(1.0 / (k + q_j_a / jnp.maximum(p_j_zk, tiny)))


def iid_draft_acceptance_upper(p: jax.Array, q: jax.Array, k: int) -> jax.Array:
    """Upper bound on acceptance for ANY scheme with K i.i.d. drafts:

    Pr[Y in list] <= Σ_j min(q_j, 1 - (1-p_j)^K)

    (the list contains symbol j with probability 1-(1-p_j)^K; a coupling
    cannot beat the pointwise min). Used as the Fig.-6 reference curve.
    Clipped at 1: it is a probability, and the float32 sum of a
    normalized ``q`` can land one ulp above 1.
    """
    return jnp.minimum(jnp.sum(jnp.minimum(q, 1.0 - (1.0 - p) ** k)), 1.0)


def wz_error_upper_bound(info_density: jax.Array, k: int, l_max: int) -> jax.Array:
    """Proposition 4: Pr[err] <= 1 - E[(1 + 2^{i(W;A|T)} / (K L_max))^-1].

    Args:
      info_density: samples of i(W;A|T) in *bits* (log2), any shape.
    """
    inner = 1.0 / (1.0 + jnp.exp2(info_density) / (k * l_max))
    return 1.0 - jnp.mean(inner)
