"""Tensor-parallel serving collectives (DESIGN.md §15).

This module is the glue between the fused speculative round
(``engine_cached.build_round_core``) and a tensor-parallel mesh
(``launch/mesh.make_tp_mesh``):

  * ``tp_round_specs`` — the fused round's in/out PartitionSpecs on a
    1-D ("model",) mesh: weights per ``sharding.rules.serve_param_spec``
    (every matmul output dim sharded), KV arenas head-sharded, and all
    round control state (positions, pending tokens, liveness, RNG keys)
    replicated;
  * ``tp_fused_round`` — wraps the round body in shard_map with those
    specs.  The wrapped program contains ONLY all-gather collectives
    (no psum — see rules.py), so it is bit-identical to the
    single-device round and the packed result stays exactly one
    replicated device->host fetch per round;
  * ``make_sharded_gls_verify`` — the O(K)-byte vocab-sharded race
    combine.  The serving engine does NOT use it (bit-identity for all
    six strategies needs the full replicated distributions for residual
    sampling), but it is the comm-optimal endgame for GLS-only serving
    at vocab scales where the lm_head gather dominates: each shard
    races its local vocab slice with the same Gumbel-race score as
    ``verify.gumbel_race_argmin`` and winners combine with two
    pmin-sized collectives, independent of vocab size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import serve_cache_pspec, serve_params_pspecs

_TINY = 1e-30


# ---------------------------------------------------------------------------
# Fused-round sharding (the serving engine's TP path)
# ---------------------------------------------------------------------------


def tp_round_specs(t_params, d_params, t_arena: dict, d_arena: dict, mesh,
                   axis: str = "model"):
    """(in_specs, out_specs) for ``build_round_core`` under shard_map.

    Positional contract matches the round body:
    ``(t_params, d_params, t_kv, d_kv, pos, pending, live, subs) ->
    (t_kv, d_kv, new_pos, packed)``.  Arenas shard their heads axis;
    params follow the exact-collective serving layout; everything else
    — including the packed result — is replicated, which is why the
    per-round host fetch stays ONE transfer at any tp.
    """
    kv_t = {kk: serve_cache_pspec(t_arena[kk].ndim, axis) for kk in t_arena}
    kv_d = {kk: serve_cache_pspec(d_arena[kk].ndim, axis) for kk in d_arena}
    rep = P()
    in_specs = (serve_params_pspecs(t_params, mesh, axis),
                serve_params_pspecs(d_params, mesh, axis),
                kv_t, kv_d, rep, rep, rep, rep)
    out_specs = (kv_t, kv_d, rep,
                 {"tokens": rep, "accepted": rep, "active": rep,
                  "pos": rep})
    return in_specs, out_specs


def tp_fused_round(round_core, mesh, in_specs, out_specs):
    """shard_map-wrap a fused round body (or the engine's admission
    prefill) for the tensor-parallel mesh.

    ``check_vma=False``: the body runs replicated math (verification,
    rollback indexing, RNG) on every device between all-gathers, which
    the static replication checker cannot always prove; correctness is
    instead enforced end-to-end by the bit-identity test battery
    (tests/test_sharded_round.py).
    """
    return jax.shard_map(round_core, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Vocab-sharded GLS race (O(K) combine; GLS-only serving endgame)
# ---------------------------------------------------------------------------


def _local_race(log_u, probs, active):
    """Race a local vocab shard.  log_u/probs: (K, N_loc); active: (K,).
    Same score convention as ``verify.gumbel_race_argmin``: the racer
    minimizes ``log(-log U) - log p`` over the support.  Returns
    (K-draft local minima/argmins, target local min/argmin)."""
    log_s = jnp.log(-log_u)
    score = log_s - jnp.log(jnp.maximum(probs, _TINY))
    score = jnp.where(probs > 0, score, jnp.inf)
    draft_min = jnp.min(score, axis=-1)
    draft_arg = jnp.argmin(score, axis=-1).astype(jnp.int32)
    t_score = jnp.where(active[:, None], score, jnp.inf)
    col = jnp.min(t_score, axis=0)
    t_min = jnp.min(col)
    t_arg = jnp.argmin(col).astype(jnp.int32)
    return draft_min, draft_arg, t_min, t_arg


def make_sharded_gls_verify(mesh, vocab_axis: str = "model"):
    """Returns verify(log_u, target_probs, active) operating on
    vocab-sharded (K, N) inputs; outputs are replicated.

    The K draft races and the target race share one collective: the
    (min, global-argmin) pairs are reduced with psum-of-masked-argmin
    after a pmin — two scalar-sized collectives total, O(K) bytes.
    """

    def kernel(log_u, target_probs, active):
        # Shapes inside shard_map: (K, N/axis) slices.
        k, n_loc = log_u.shape
        dmin, darg, tmin, targ = _local_race(log_u, target_probs, active)
        shard = jax.lax.axis_index(vocab_axis)
        offset = shard * n_loc
        # Global argmin via min-reduce then masked index reduce.
        dmin_g = jax.lax.pmin(dmin, vocab_axis)                # (K,)
        darg_global = jnp.where(dmin <= dmin_g, offset + darg,
                                jnp.int32(2**30))
        darg_g = jax.lax.pmin(darg_global, vocab_axis)         # ties -> low idx
        tmin_g = jax.lax.pmin(tmin, vocab_axis)
        targ_global = jnp.where(tmin <= tmin_g, offset + targ, jnp.int32(2**30))
        targ_g = jax.lax.pmin(targ_global, vocab_axis)
        return darg_g, targ_g

    spec_in = P(None, vocab_axis)
    fn = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(spec_in, spec_in, P(None)),
        out_specs=(P(None), P()))

    def verify(log_u, target_probs, active):
        """log_u/target_probs: (K, N) sharded on the vocab axis.
        Returns ((K,) per-draft race winners, target race winner)."""
        return fn(log_u, target_probs, active)

    return verify
