"""Dense llama-family decoder-only transformer (GQA + RoPE + SwiGLU +
RMSNorm).  Covers granite-8b/34b, llama3-405b, smollm-360m, and is the
backbone reused by the MoE and VLM families.

Uniform model API (same across all families; see registry.py):

  init_params(key, cfg)                         -> params
  forward(params, cfg, batch)                   -> logits (B, S, Vpad)
  init_cache(cfg, batch, max_len)               -> cache
  prefill(params, cfg, batch, cache)            -> (last_logits (B,Vpad), cache)
  decode_step(params, cfg, tokens (B,1), cache) -> (logits (B,Vpad), cache)

KV caches hold RoPE'd keys; sliding-window configs use a ring buffer of
size ``window`` so long_500k decode state stays O(window).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.stack import scan_blocks, stack_init

# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def _block_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    hd = cfg.resolved_head_dim
    return {
        "attn_norm": L.rmsnorm_params(cfg.d_model, cfg.activation_dtype),
        "attn": L.attn_params(k1, cfg.d_model, cfg.num_heads, cfg.kv_heads,
                              hd, cfg.activation_dtype),
        "mlp_norm": L.rmsnorm_params(cfg.d_model, cfg.activation_dtype),
        "mlp": L.swiglu_params(k2, cfg.d_model, cfg.d_ff, cfg.activation_dtype),
    }


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    dt = cfg.activation_dtype
    return {
        "embed": L.embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dt),
        "layers": stack_init(k_layers, cfg.num_layers,
                             lambda k: _block_init(k, cfg)),
        "final_norm": L.rmsnorm_params(cfg.d_model, dt),
        "lm_head": L.dense_init(k_head, cfg.d_model, cfg.padded_vocab, dt),
    }


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _attn_full(p, cfg: ModelConfig, x, positions, chunked: bool):
    """Full-sequence (train / prefill) self-attention."""
    hd = cfg.resolved_head_dim
    q, k, v = L.project_qkv(p, x, cfg.num_heads, cfg.kv_heads, hd)
    q = L.apply_rope(q, positions[:, None, :], cfg.rope_theta)
    k = L.apply_rope(k, positions[:, None, :], cfg.rope_theta)
    if chunked:
        out = L.chunked_attention(q, k, v, causal=True,
                                  window=cfg.sliding_window)
    else:
        out = L.attention(q, k, v, causal=True, window=cfg.sliding_window)
    return L.project_out(p, out), (k, v)


def _block_train(params_l, x_and_pos, _cache, cfg: ModelConfig, chunked):
    x, positions = x_and_pos
    from repro.sharding.context import constrain
    x = constrain(x, "layer_carry")
    h, _ = _attn_full(params_l["attn"], cfg,
                      L.rmsnorm(params_l["attn_norm"], x, cfg.norm_eps),
                      positions, chunked)
    x = x + h
    x = x + L.swiglu(params_l["mlp"],
                     L.rmsnorm(params_l["mlp_norm"], x, cfg.norm_eps))
    x = constrain(x, "layer_carry")
    return (x, positions), None


def forward(params: dict, cfg: ModelConfig, batch: dict, *,
            remat: bool = True, chunked: Optional[bool] = None,
            return_hidden: bool = False) -> jax.Array:
    tokens = batch["tokens"]
    b, s = tokens.shape
    if chunked is None:
        chunked = s > 2048
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    fn = functools.partial(_block_train, cfg=cfg, chunked=chunked)
    (x, _), _ = scan_blocks(params["layers"], (x, positions), fn, remat=remat)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x
    return L.dense(x, params["lm_head"])


# ---------------------------------------------------------------------------
# KV cache + serving paths
# ---------------------------------------------------------------------------


def cache_len(cfg: ModelConfig, max_len: int) -> int:
    return min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    t = cache_len(cfg, max_len)
    hd = cfg.resolved_head_dim
    dt = cfg.activation_dtype
    return {
        "k": jnp.zeros((cfg.num_layers, batch, cfg.kv_heads, t, hd), dt),
        "v": jnp.zeros((cfg.num_layers, batch, cfg.kv_heads, t, hd), dt),
        "pos": jnp.zeros((), jnp.int32),
    }


def _block_prefill(params_l, carry, cache_l, cfg: ModelConfig, chunked):
    """Prefill: full self-attention AND cache write (ring for SWA)."""
    x, positions = carry
    from repro.sharding.context import constrain
    x = constrain(x, "layer_carry")
    h, (k, v) = _attn_full(params_l["attn"], cfg,
                           L.rmsnorm(params_l["attn_norm"], x, cfg.norm_eps),
                           positions, chunked)
    x = x + h
    x = x + L.swiglu(params_l["mlp"],
                     L.rmsnorm(params_l["mlp_norm"], x, cfg.norm_eps))
    t_cache = cache_l["k"].shape[2]
    s = k.shape[2]
    if s >= t_cache:
        # Keep the last t_cache positions (ring semantics: slot = pos % t).
        tail = jax.lax.dynamic_slice_in_dim(k, s - t_cache, t_cache, axis=2)
        tail_v = jax.lax.dynamic_slice_in_dim(v, s - t_cache, t_cache, axis=2)
        shift = s % t_cache
        idx = (jnp.arange(t_cache) - shift) % t_cache
        new_k = tail[:, :, idx] if shift else tail
        new_v = tail_v[:, :, idx] if shift else tail_v
    else:
        new_k = jax.lax.dynamic_update_slice_in_dim(cache_l["k"], k, 0, axis=2)
        new_v = jax.lax.dynamic_update_slice_in_dim(cache_l["v"], v, 0, axis=2)
    return (x, positions), {"k": new_k, "v": new_v}


def prefill(params: dict, cfg: ModelConfig, batch: dict, cache: dict):
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    fn = functools.partial(_block_prefill, cfg=cfg, chunked=s > 2048)
    layer_cache = {"k": cache["k"], "v": cache["v"]}
    (x, _), new_cache = scan_blocks(params["layers"], (x, positions), fn,
                                    cache=layer_cache)
    x = L.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = L.dense(x, params["lm_head"])[:, 0]
    return logits, {"k": new_cache["k"], "v": new_cache["v"],
                    "pos": jnp.asarray(s, jnp.int32)}


def _block_decode(params_l, carry, cache_l, cfg: ModelConfig):
    x, pos = carry  # x: (B, 1, D); pos: scalar current position
    from repro.sharding.context import constrain
    x = constrain(x, "layer_carry")
    p = params_l["attn"]
    hd = cfg.resolved_head_dim
    xin = L.rmsnorm(params_l["attn_norm"], x, cfg.norm_eps)
    q, k, v = L.project_qkv(p, xin, cfg.num_heads, cfg.kv_heads, hd)
    posb = jnp.broadcast_to(pos[None, None], (x.shape[0], 1, 1))
    q = L.apply_rope(q, posb, cfg.rope_theta)
    k = L.apply_rope(k, posb, cfg.rope_theta)
    t_cache = cache_l["k"].shape[2]
    slot = pos % t_cache
    new_k = jax.lax.dynamic_update_slice_in_dim(cache_l["k"], k, slot, axis=2)
    new_v = jax.lax.dynamic_update_slice_in_dim(cache_l["v"], v, slot, axis=2)
    kv_len = jnp.minimum(pos + 1, t_cache)
    out = L.attention(q, new_k, new_v, causal=False, kv_len=kv_len)
    x = x + L.project_out(p, out)
    x = x + L.swiglu(params_l["mlp"],
                     L.rmsnorm(params_l["mlp_norm"], x, cfg.norm_eps))
    return (x, pos), {"k": new_k, "v": new_v}


def decode_step(params: dict, cfg: ModelConfig, tokens: jax.Array, cache: dict):
    """tokens: (B, 1) -> (logits (B, Vpad), new cache)."""
    x = params["embed"][tokens]
    pos = cache["pos"]
    fn = functools.partial(_block_decode, cfg=cfg)
    layer_cache = {"k": cache["k"], "v": cache["v"]}
    (x, _), new_cache = scan_blocks(params["layers"], (x, pos), fn,
                                    cache=layer_cache)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.dense(x, params["lm_head"])[:, 0]
    return logits, {"k": new_cache["k"], "v": new_cache["v"], "pos": pos + 1}


def _maybe_quantize_kv(cache_l, k, v):
    """Quantize-on-write hook for int8 KV arenas (DESIGN.md §11): when the
    layer cache carries scale leaves (``k_s``/``v_s``), the freshly
    projected k/v quantize per KV vector and the caller writes int8 plus
    scales; otherwise k/v pass through and scales are None."""
    if "k_s" not in cache_l:
        return k, v, None, None
    from repro.serving.quant import quantize_kv
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    return kq, vq, ks, vs


def _rowwise_cache_write(cache_k, cache_v, k, v, starts):
    """Write each row's (H, m, hd) keys/values at its own time offset.
    cache_k/v: (B, H, T, hd); k/v: (B, H, m, hd); starts: (B,) i32."""
    upd = lambda c, kk, p: jax.lax.dynamic_update_slice_in_dim(
        c, kk, p, axis=1)
    return (jax.vmap(upd)(cache_k, k, starts),
            jax.vmap(upd)(cache_v, v, starts))


def _rowwise_cache_write_masked(cache_k, cache_v, k, v, starts, write):
    """Row-offset cache write that can skip rows: rows where ``write`` is
    False scatter to index T (out of bounds, ``mode="drop"``) so their
    cache content is untouched bit-for-bit.  Written rows land exactly
    where ``_rowwise_cache_write`` would put them.  cache_k/v:
    (B, H, T, hd); k/v: (B, H, m, hd); starts: (B,) i32; write: (B,)
    bool.  Chunk tails running past T (bucket padding near the buffer
    end) drop the same way."""
    t = cache_k.shape[2]
    m = k.shape[2]

    def upd(c, kk, p, w):
        idx = jnp.where(w, p + jnp.arange(m), t)   # t == OOB -> dropped
        return c.at[:, idx].set(kk, mode="drop")

    return (jax.vmap(upd)(cache_k, k, starts, write),
            jax.vmap(upd)(cache_v, v, starts, write))


def _block_prefill_slots(params_l, carry, cache_l, cfg: ModelConfig,
                         write, use_kernel: bool,
                         interpret: Optional[bool],
                         tp_axis: Optional[str] = None):
    """Prompt-chunk prefill with per-row start positions, straight into a
    cache arena (the batched admission step, DESIGN.md §9).  Identical
    attention structure to ``_block_verify_slots`` — causal over the
    row's own cache prefix plus the freshly written chunk — with two
    differences: rows outside the admission wave are write-masked, and
    ``use_kernel`` routes the chunk attention through the
    ``kernels/flash_attention`` Pallas kernel.  ``tp_axis`` as in
    ``_block_decode_slots`` (serving TP, DESIGN.md §15)."""
    x, pos = carry  # x: (B, m, D); pos: (B,) per-row chunk start position
    p = params_l["attn"]
    hd = cfg.resolved_head_dim
    b, m, _ = x.shape
    xin = L.rmsnorm(params_l["attn_norm"], x, cfg.norm_eps)
    q, k, v = L.project_qkv(p, xin, cfg.num_heads, cfg.kv_heads, hd)
    positions = pos[:, None, None] + jnp.arange(m, dtype=jnp.int32)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    k, v, ks, vs = _maybe_quantize_kv(cache_l, k, v)
    new_k, new_v = _rowwise_cache_write_masked(cache_l["k"], cache_l["v"],
                                               k, v, pos, write)
    new_cache = {"k": new_k, "v": new_v}
    k_scale = v_scale = None
    if ks is not None:
        k_scale, v_scale = _rowwise_cache_write_masked(
            cache_l["k_s"], cache_l["v_s"], ks, vs, pos, write)
        new_cache.update(k_s=k_scale, v_s=v_scale)
    out = L.attention(q, new_k, new_v, causal=True, q_offset=pos,
                      kv_len=pos + m, k_scale=k_scale, v_scale=v_scale,
                      use_kernel=use_kernel, interpret=interpret)
    x = x + L.project_out(p, out, tp_axis=tp_axis)
    x = x + L.swiglu(params_l["mlp"],
                     L.rmsnorm(params_l["mlp_norm"], x, cfg.norm_eps),
                     tp_axis=tp_axis)
    return (x, pos), new_cache


def prefill_slots(params: dict, cfg: ModelConfig, tokens: jax.Array,
                  cache: dict, pos: jax.Array,
                  write: Optional[jax.Array] = None, *,
                  use_kernel: bool = False,
                  interpret: Optional[bool] = None,
                  tp_axis: Optional[str] = None) -> dict:
    """Device-side admission prefill: tokens (B, m) prompt chunks land
    directly in their arena rows at per-row offsets ``pos`` (B,) —
    no temporary cache, no host scatter (DESIGN.md §9).  Returns the new
    {k, v} arena; NO logits are computed (the lm_head matmul is the
    single largest flop term of a small-model admission and its output
    is discarded — the last prompt token stays *pending* and is scored
    by the first round's verify chunk instead).

    ``write`` (B,) bool masks rows outside the admission wave: their
    cache rows are bit-untouched and their (garbage) activations are
    discarded.  Rows shorter than the chunk are padded by the caller;
    pad KV lands above the row's live prefix, where every consumer
    overwrites before attending (§9 safety argument).  Non-ring caches
    only.  ``tp_axis`` runs the serving-TP sharded variant (pass a LOCAL
    cfg and the local KV-head shard of the arena)."""
    assert not cfg.sliding_window, "prefill_slots: non-ring caches only"
    x = params["embed"][tokens]
    if write is None:
        write = jnp.ones((tokens.shape[0],), bool)
    fn = functools.partial(_block_prefill_slots, cfg=cfg, write=write,
                           use_kernel=use_kernel, interpret=interpret,
                           tp_axis=tp_axis)
    layer_cache = {kk: cache[kk] for kk in cache if kk != "pos"}
    (_, _), new_cache = scan_blocks(params["layers"], (x, pos), fn,
                                    cache=layer_cache)
    return dict(new_cache)


def _block_decode_slots(params_l, carry, cache_l, cfg: ModelConfig,
                        use_kernel: bool = False,
                        interpret: Optional[bool] = None,
                        tp_axis: Optional[str] = None):
    """Single-token decode where every batch row sits at its own position
    (cache-arena serving: rows = slots x drafts, DESIGN.md §7).

    ``tp_axis``: serving tensor parallelism (DESIGN.md §15) — the block
    runs inside shard_map with output-dim-sharded weights and a LOCAL
    ``cfg`` (num_heads/kv_heads already divided by tp), so qkv/attention
    are a pure batch split over heads; only project_out/swiglu gather."""
    x, pos = carry  # x: (B, 1, D); pos: (B,) per-row current position
    from repro.sharding.context import constrain
    x = constrain(x, "layer_carry")
    p = params_l["attn"]
    hd = cfg.resolved_head_dim
    xin = L.rmsnorm(params_l["attn_norm"], x, cfg.norm_eps)
    q, k, v = L.project_qkv(p, xin, cfg.num_heads, cfg.kv_heads, hd)
    posb = pos[:, None, None]                        # (B, 1, 1)
    q = L.apply_rope(q, posb, cfg.rope_theta)
    k = L.apply_rope(k, posb, cfg.rope_theta)
    t_cache = cache_l["k"].shape[2]
    k, v, ks, vs = _maybe_quantize_kv(cache_l, k, v)
    new_k, new_v = _rowwise_cache_write(cache_l["k"], cache_l["v"], k, v,
                                        pos % t_cache)
    new_cache = {"k": new_k, "v": new_v}
    k_scale = v_scale = None
    if ks is not None:
        k_scale, v_scale = _rowwise_cache_write(
            cache_l["k_s"], cache_l["v_s"], ks, vs, pos % t_cache)
        new_cache.update(k_s=k_scale, v_s=v_scale)
    kv_len = jnp.minimum(pos + 1, t_cache)
    out = L.attention(q, new_k, new_v, causal=False, kv_len=kv_len,
                      k_scale=k_scale, v_scale=v_scale,
                      use_kernel=use_kernel, interpret=interpret)
    x = x + L.project_out(p, out, tp_axis=tp_axis)
    x = x + L.swiglu(params_l["mlp"],
                     L.rmsnorm(params_l["mlp_norm"], x, cfg.norm_eps),
                     tp_axis=tp_axis)
    return (x, pos), new_cache


def decode_step_slots(params: dict, cfg: ModelConfig, tokens: jax.Array,
                      cache: dict, pos: jax.Array, *,
                      use_kernel: bool = False,
                      interpret: Optional[bool] = None,
                      tp_axis: Optional[str] = None):
    """Per-row-position decode: tokens (B, 1), pos (B,) -> (logits
    (B, Vpad), new {k, v} cache).  Position tracking lives with the
    caller (host-side in the cache pool), not in the cache dict.
    ``use_kernel`` streams the per-row attention through the Pallas
    decode-attention kernel (numerically equivalent, not bit-equal).
    ``tp_axis`` runs the serving-TP sharded variant (pass a LOCAL cfg;
    logits gather the vocab shards back to the full padded vocab)."""
    x = params["embed"][tokens]
    fn = functools.partial(_block_decode_slots, cfg=cfg,
                           use_kernel=use_kernel, interpret=interpret,
                           tp_axis=tp_axis)
    layer_cache = {kk: cache[kk] for kk in cache if kk != "pos"}
    (x, _), new_cache = scan_blocks(params["layers"], (x, pos), fn,
                                    cache=layer_cache)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.dense(x, params["lm_head"])[:, 0]
    if tp_axis is not None:
        logits = L.tp_all_gather(logits, tp_axis, logits.ndim - 1)
    return logits, dict(new_cache)


def _block_verify(params_l, carry, cache_l, cfg: ModelConfig):
    """Multi-token decode ("verify chunk"): process m draft tokens against
    the cache in one pass — the serving step for multi-draft speculative
    decoding (paper Alg. 2).  Non-ring caches only (full attention)."""
    x, pos = carry  # x: (B, m, D); pos: scalar start position
    p = params_l["attn"]
    hd = cfg.resolved_head_dim
    b, m, _ = x.shape
    xin = L.rmsnorm(params_l["attn_norm"], x, cfg.norm_eps)
    q, k, v = L.project_qkv(p, xin, cfg.num_heads, cfg.kv_heads, hd)
    positions = (pos + jnp.arange(m, dtype=jnp.int32))[None, None, :]
    positions = jnp.broadcast_to(positions, (b, 1, m))
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    new_k = jax.lax.dynamic_update_slice_in_dim(cache_l["k"], k, pos, axis=2)
    new_v = jax.lax.dynamic_update_slice_in_dim(cache_l["v"], v, pos, axis=2)
    kv_len = pos + m
    out = L.attention(q, new_k, new_v, causal=True, q_offset=pos,
                      kv_len=kv_len)
    x = x + L.project_out(p, out)
    x = x + L.swiglu(params_l["mlp"],
                     L.rmsnorm(params_l["mlp_norm"], x, cfg.norm_eps))
    return (x, pos), {"k": new_k, "v": new_v}


def verify_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                cache: dict):
    """tokens: (B, m) — the pending token + m-1 draft tokens.  Returns
    (logits (B, m, Vpad), new cache) with logits[:, j] = q(. | ...tokens
    up to j), i.e. the q^(1..m) distributions Algorithm 2 verifies."""
    assert not cfg.sliding_window, "verify_step: non-ring caches only"
    x = params["embed"][tokens]
    pos = cache["pos"]
    fn = functools.partial(_block_verify, cfg=cfg)
    layer_cache = {"k": cache["k"], "v": cache["v"]}
    (x, _), new_cache = scan_blocks(params["layers"], (x, pos), fn,
                                    cache=layer_cache)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.dense(x, params["lm_head"])
    return logits, {"k": new_cache["k"], "v": new_cache["v"],
                    "pos": pos + tokens.shape[1]}


def _block_verify_slots(params_l, carry, cache_l, cfg: ModelConfig,
                        tp_axis: Optional[str] = None):
    """Multi-token verify chunk with per-row start positions (the batched
    cache-arena step: rows of different requests verify their own drafts
    at their own offsets in one forward, DESIGN.md §7).  ``tp_axis`` as
    in ``_block_decode_slots`` (serving TP, DESIGN.md §15)."""
    x, pos = carry  # x: (B, m, D); pos: (B,) per-row start position
    p = params_l["attn"]
    hd = cfg.resolved_head_dim
    b, m, _ = x.shape
    xin = L.rmsnorm(params_l["attn_norm"], x, cfg.norm_eps)
    q, k, v = L.project_qkv(p, xin, cfg.num_heads, cfg.kv_heads, hd)
    positions = pos[:, None, None] + jnp.arange(m, dtype=jnp.int32)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    k, v, ks, vs = _maybe_quantize_kv(cache_l, k, v)
    new_k, new_v = _rowwise_cache_write(cache_l["k"], cache_l["v"], k, v,
                                        pos)
    new_cache = {"k": new_k, "v": new_v}
    k_scale = v_scale = None
    if ks is not None:
        k_scale, v_scale = _rowwise_cache_write(
            cache_l["k_s"], cache_l["v_s"], ks, vs, pos)
        new_cache.update(k_s=k_scale, v_s=v_scale)
    out = L.attention(q, new_k, new_v, causal=True, q_offset=pos,
                      kv_len=pos + m, k_scale=k_scale, v_scale=v_scale)
    x = x + L.project_out(p, out, tp_axis=tp_axis)
    x = x + L.swiglu(params_l["mlp"],
                     L.rmsnorm(params_l["mlp_norm"], x, cfg.norm_eps),
                     tp_axis=tp_axis)
    return (x, pos), new_cache


def verify_step_slots(params: dict, cfg: ModelConfig, tokens: jax.Array,
                      cache: dict, pos: jax.Array, *,
                      tp_axis: Optional[str] = None):
    """Per-row-position verify chunk: tokens (B, m), pos (B,) -> (logits
    (B, m, Vpad), new {k, v} cache).  Row b's logits[:, j] are
    q(. | row-b cache prefix, tokens[b, :j+1]) — the Algorithm-2 target
    rows for a whole cache arena in ONE forward.  Non-ring caches only.
    ``tp_axis`` runs the serving-TP sharded variant (pass a LOCAL cfg)."""
    assert not cfg.sliding_window, "verify_step_slots: non-ring caches only"
    x = params["embed"][tokens]
    fn = functools.partial(_block_verify_slots, cfg=cfg, tp_axis=tp_axis)
    layer_cache = {kk: cache[kk] for kk in cache if kk != "pos"}
    (x, _), new_cache = scan_blocks(params["layers"], (x, pos), fn,
                                    cache=layer_cache)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.dense(x, params["lm_head"])
    if tp_axis is not None:
        logits = L.tp_all_gather(logits, tp_axis, logits.ndim - 1)
    return logits, dict(new_cache)


# ---------------------------------------------------------------------------
# Paged-arena serving paths (DESIGN.md §12)
# ---------------------------------------------------------------------------
#
# Same slot-aware serving steps, but the KV lives in fixed-size pages
# behind a per-row page table (models/paged.py) instead of one
# contiguous arena.  Each wrapper scans the SAME per-layer block
# function as its contiguous twin through ``paged.paged_block``: the
# layer's contiguous view is gathered from its pages, the block runs
# unchanged (identical reduction shapes — ``buf_len`` is the compiled
# view length), and the updated leaves scatter back through the table.
# Only one layer's view is ever materialized, and the attention math is
# bit-identical to the contiguous arena by construction.


def prefill_slots_paged(params: dict, cfg: ModelConfig, tokens: jax.Array,
                        pages: dict, table: jax.Array, pos: jax.Array,
                        write: Optional[jax.Array] = None, *,
                        buf_len: int, use_kernel: bool = False,
                        interpret: Optional[bool] = None) -> dict:
    """``prefill_slots`` against paged storage: pages {leaf: (layers,
    P+1, H, page, d)}, table (rows, n_lp) -> new pages.  The caller must
    have reserved pages covering ``pos + m`` tokens for written rows;
    masked rows' writes drop through their unmapped entries."""
    from repro.models import paged
    assert not cfg.sliding_window, "prefill_slots_paged: non-ring only"
    x = params["embed"][tokens]
    if write is None:
        write = jnp.ones((tokens.shape[0],), bool)
    inner = functools.partial(_block_prefill_slots, cfg=cfg, write=write,
                              use_kernel=use_kernel, interpret=interpret)
    fn = paged.paged_block(inner, table, buf_len)
    (_, _), new_pages = scan_blocks(params["layers"], (x, pos), fn,
                                    cache=dict(pages))
    return dict(new_pages)


def decode_step_slots_paged(params: dict, cfg: ModelConfig,
                            tokens: jax.Array, pages: dict,
                            table: jax.Array, pos: jax.Array, *,
                            buf_len: int, use_kernel: bool = False,
                            interpret: Optional[bool] = None):
    """``decode_step_slots`` against paged storage -> (logits (B, Vpad),
    new pages)."""
    from repro.models import paged
    x = params["embed"][tokens]
    inner = functools.partial(_block_decode_slots, cfg=cfg,
                              use_kernel=use_kernel, interpret=interpret)
    fn = paged.paged_block(inner, table, buf_len)
    (x, _), new_pages = scan_blocks(params["layers"], (x, pos), fn,
                                    cache=dict(pages))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.dense(x, params["lm_head"])[:, 0]
    return logits, dict(new_pages)


def verify_step_slots_paged(params: dict, cfg: ModelConfig,
                            tokens: jax.Array, pages: dict,
                            table: jax.Array, pos: jax.Array, *,
                            buf_len: int):
    """``verify_step_slots`` against paged storage -> (logits
    (B, m, Vpad), new pages)."""
    from repro.models import paged
    assert not cfg.sliding_window, "verify_step_slots_paged: non-ring only"
    x = params["embed"][tokens]
    inner = functools.partial(_block_verify_slots, cfg=cfg)
    fn = paged.paged_block(inner, table, buf_len)
    (x, _), new_pages = scan_blocks(params["layers"], (x, pos), fn,
                                    cache=dict(pages))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.dense(x, params["lm_head"])
    return logits, dict(new_pages)
