"""KV-cached speculative decoding engine (production path, dense family).

The reference engine (engine.py) re-scores the full prefix each block —
simple and family-agnostic but O(T^2) per sequence.  This engine keeps
persistent KV caches for target and drafter in a slot-based cache arena
(``models/cache_pool.py``) and advances ALL live requests at once with
the slot-aware multi-token ``verify_step_slots`` (DESIGN.md §7):

  per round: drafter: L ``decode_step_slots`` sweeps over the whole
             arena (drafts x slots ride the batch dim)
             target:  ONE ``verify_step_slots`` over every live
             request's (pending token + L drafts)
             fused block verification on shared uniforms (Alg. 2,
             block_verify.py — same dispatcher as the reference engine)
             cache rollback = arena-wide surviving-row replication

Cache rollback correctness: row k* survived steps 1..a, so its cache
slots [pos, pos+a] hold exactly [pending, Y_1..Y_a]; replicating row k*
into all of the slot's rows and rewinding pos to pos+a+1 leaves every
row's cache equal to the accepted prefix.  The bonus/residual token
Y_{a+1} becomes the next block's pending token (its KV enters the cache
when scored).  Row selection contract: when a == 0 every row's slot[pos]
(the shared pending token) is identical, so row 0 is valid; when a > 0
at least one row MUST be active (``_select_rollback_row`` asserts this
invariant instead of letting ``argmax`` silently pick a dead row 0).

Host-sync accounting (DESIGN.md §7.3): ``GenerationStats.host_syncs``
counts every device->host transfer the verification path performs.  The
fused verifier's single ``device_get`` already lands ``active`` on the
host, so rollback row selection is sync-free; per-slot positions are
tracked host-side by the pool, so the former ``int(cache["pos"])`` sync
no longer exists.  Draft-token materialization (one transfer per draft
step, shared with the reference engine) is reported separately as
``draft_syncs`` on the block outcome.

Serving contract: ``gen_block`` / ``gen_blocks`` match the reference
engine's scheduler API (subs, prefixes, buf_len), extended with ``uids``
so the scheduler's ``cache_mode="kv"`` path can pin each request to a
pool slot across rounds (``admit`` at first sight, ``release`` on
completion).  Without uids each call admits and releases an ephemeral
slot — correct, but it re-prefills per block.

``gen_blocks(..., fused=True)`` (the scheduler's ``cache_mode=
"kv_fused"``) replaces the host-driven round above with ONE jitted
device program (DESIGN.md §8): the L-step drafter sweep runs as a
``lax.scan`` with drafted tokens staying device-resident, the stacked
verify, batched Algorithm-2 verification, surviving-row selection,
arena-wide rollback, and the residual drafter catch-up all execute in
the same dispatch with donated cache buffers, and the only
device->host transfer per round is the packed result fetch
(``draft_syncs == 0``, one ``host_sync`` per round).  Token streams are
bit-identical to the host-driven path for every strategy and device
verifier backend.

Admission (DESIGN.md §9): ``admit_batch`` drains an admission wave into
power-of-two length buckets and issues ONE stacked ``prefill_slots``
dispatch per bucket per model — prompts land directly in their arena
rows on device (no temporary cache, no host scatter), rows outside the
wave are write-masked, bucket padding rides the §9 dead-zone argument,
and prompts longer than the largest bucket chunk through repeated
calls, so compile count is bounded by the bucket set rather than by
observed prompt lengths.  ``round_with_admission`` additionally
OVERLAPS admission with decoding: the fused round is dispatched first,
the admission prefills are dispatched against its output arenas, and
only then does the host block on the round's packed fetch — the
admitted sessions join the live set next round.  Per-request ``admit``
is a one-request wave of the same program, so both admission paths
write bit-identical caches (tests/test_admission.py); the scheduler's
``admission="per_request"`` (one request per wave) is kept only as the
TTFT baseline of the bursty-admission bench.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import (
    CachePool,
    PagedCachePool,
    decode_step_slots,
    decode_step_slots_paged,
    prefill_slots,
    prefill_slots_paged,
    verify_step_slots,
    verify_step_slots_paged,
)
from repro.models import paged as paged_kv
from repro.serving.guard import check_packed
from repro.serving.spans import span
from repro.specdec import verify as V
from repro.specdec.block_verify import (
    RS_STRATEGIES,
    block_verify_batched,
    run_block_verify,
)
from repro.specdec.engine import (
    BlockOutcome,
    GenerationStats,
    SpecDecConfig,
    block_randomness,
    probs_from_logits,
)


_MIN_BUCKET = 16


def _max_bucket(buf_len: int) -> int:
    """Largest admission bucket: the largest power of two <= buf_len
    (floored at _MIN_BUCKET for tiny test arenas — oversized chunks are
    safe, their pad writes drop at the buffer edge)."""
    b = _MIN_BUCKET
    while b * 2 <= buf_len:
        b *= 2
    return b


def _bucket_plan(n: int, max_bucket: int) -> list:
    """Chunk an n-token prefill into the power-of-two bucket set:
    ``[(offset, length, bucket), ...]``.  Full ``max_bucket`` chunks
    first, then the remainder in the smallest bucket that holds it —
    so the set of compiled prefill shapes is the bucket set, not the
    set of observed prompt lengths (DESIGN.md §9)."""
    chunks = []
    off = 0
    while n - off > max_bucket:
        chunks.append((off, max_bucket, max_bucket))
        off += max_bucket
    rem = n - off
    if rem > 0:
        bucket = _MIN_BUCKET
        while bucket < rem:
            bucket *= 2
        chunks.append((off, rem, bucket))
    return chunks


def _arena_bytes(arenas: dict) -> tuple:
    """(bytes the arenas reserve, bytes per arena row and position),
    from leaf shapes and dtypes alone.  Every leaf is laid out
    ``(layers, rows, kv_heads, T, head_dim or 1)``."""
    reserved = per_position = 0
    for arena in arenas.values():
        for leaf in arena.values():
            reserved += leaf.nbytes
            per_position += leaf.nbytes // (leaf.shape[1] * leaf.shape[3])
    return reserved, per_position


def _select_rollback_row(active: np.ndarray, num_accepted: int) -> int:
    """Surviving draft row for cache rollback.

    With a == 0 no draft row was accepted: every row's cache agrees on
    the only live position (the shared pending token), so row 0 is
    correct by symmetry.  With a > 0 an accepted path exists and the
    final active mask must contain it — an all-False mask here means the
    verifier and engine disagree about the block, which would silently
    roll the cache back to a rejected row; fail loudly instead.
    """
    active = np.asarray(active)
    if num_accepted <= 0:
        return 0
    hits = np.flatnonzero(active)
    if hits.size == 0:
        raise AssertionError(
            f"rollback invariant violated: num_accepted={num_accepted} "
            "but no draft row is active")
    return int(hits[0])


def build_round_core(cfg: SpecDecConfig, t_cfg, d_cfg, vocab: int,
                     num_slots: int, tp_axis: Optional[str] = None):
    """The fused speculative round as a pure function (DESIGN.md §8):

    ``(t_params, d_params, t_kv, d_kv, pos, pending, live, subs) ->
    (t_kv, d_kv, new_pos, packed)``

    Module-level so the engine (which jits it, optionally under
    shard_map) and ``launch/dryrun.py`` (which lowers it with abstract
    shapes for HLO/collective analysis) compile the SAME program.

    Its stages run under ``jax.named_scope``s (``draft``, ``topk``,
    ``target_verify``, ``block_verify``, ``rollback``, ``catch_up``),
    which a profile shows in each op's name stack (DESIGN.md §16); the
    HLO instruction names and the program's name ``jit_round_core`` do
    not depend on them.

    ``tp_axis`` selects the tensor-parallel variant (DESIGN.md §15):
    the body then expects LOCAL model configs (num_heads / kv_heads
    divided by tp) and weight/KV shards, and reassembles activations
    with exact all-gathers inside the model calls.  Everything outside
    the model calls — drafting, verification, rollback indexing, RNG —
    computes on replicated values identically on every device.

    Paged rounds (§12) run this SAME program: the engine holds a
    persistent contiguous view of the page pool (gathered once,
    donation-chained round to round), so the steady-state round pays
    ZERO paging cost — no table input, no per-round gather/scatter.
    Page storage syncs per-slot at events only (suspend/resume/
    admission); an earlier design gathered and scattered both arenas
    inside every round and cost ~50% extra wall per round on CPU.
    Bit-identity is untouched: the view holds exactly the contiguous
    arena's bytes on live rows, and dead-row garbage is masked by
    kv_len like it always was.
    """
    K, L, N = cfg.num_drafts, cfg.draft_len, vocab
    S = num_slots
    rows = S * K
    slot_of = jnp.repeat(jnp.arange(S, dtype=jnp.int32), K)
    row_ids = jnp.arange(rows, dtype=jnp.int32)
    need_probs = cfg.strategy in RS_STRATEGIES

    def round_core(t_params, d_params, t_kv, d_kv, pos, pending, live,
                   subs):
        live_row = jnp.repeat(live, K)
        # Rows of slots NOT advancing this round (free, or occupied
        # but unlisted) still ride along as dead rows; they must
        # decode at their own position — the pool zeroes ``pos`` on
        # release, and an occupied slot's garbage writes land at
        # [pos, pos+L], beyond everything its next real round reads
        # (the same safety argument as the host-driven sweep).
        row_pos = jnp.repeat(pos, K)
        # Per-slot shared uniforms + strategy keys, drawn in-program:
        # vmapped jax.random equals its per-lane unbatched draws, so
        # each live slot sees exactly the sheet the host-driven
        # round would hand it (the §3.2 RNG contract).
        log_u, strat_keys = jax.vmap(
            lambda s: block_randomness(s, L, K, N))(subs)

        # --- drafter sweep: L decode steps, tokens device-resident
        cur0 = jnp.where(live_row, jnp.repeat(pending, K),
                         0).astype(jnp.int32)[:, None]

        def dstep(carry, inp):
            cur, dc = carry
            log_u_j, j = inp
            logits, dc = decode_step_slots(
                d_params, d_cfg, cur, dc, row_pos + j,
                use_kernel=cfg.decode_kernel,
                interpret=cfg.pallas_interpret, tp_axis=tp_axis)
            with jax.named_scope("topk"):
                p_all = probs_from_logits(logits, cfg.temps[0], cfg.top_k,
                                          N)
            tok = V.draft_token_from_uniforms(
                log_u_j.reshape(rows, N), p_all)
            tok = jnp.where(live_row, tok, 0).astype(jnp.int32)
            ys = (tok, p_all) if need_probs else tok
            return (tok[:, None], dc), ys

        xs = (jnp.swapaxes(log_u[:, :L], 0, 1),
              jnp.arange(L, dtype=jnp.int32))
        with jax.named_scope("draft"):
            (_, d_kv1), ys = jax.lax.scan(dstep, (cur0, dict(d_kv)), xs)
        toks = ys[0] if need_probs else ys            # (L, rows)
        d_tokens = toks.T.reshape(S, K, L)
        d_probs = (ys[1].reshape(L, S, K, N).transpose(1, 2, 0, 3)
                   if need_probs else None)

        # --- target: ONE stacked verify chunk over the arena ------
        chunk = jnp.concatenate([cur0, toks.T], axis=1)
        with jax.named_scope("target_verify"):
            t_logits, t_kv2 = verify_step_slots(
                t_params, t_cfg, chunk, t_kv, row_pos, tp_axis=tp_axis)
        with jax.named_scope("topk"):
            q = probs_from_logits(t_logits, cfg.target_temp, cfg.top_k,
                                  N).reshape(S, K, L + 1, N)

        # --- Algorithm 2, batched over slots ----------------------
        with jax.named_scope("block_verify"):
            res = block_verify_batched(
                log_u, d_tokens, d_probs, q, strat_keys,
                strategy=cfg.strategy, backend=cfg.verifier_backend,
                interpret=cfg.pallas_interpret)
        a = jnp.where(live, res.num_accepted, 0)
        # Surviving row: a == 0 -> row 0 (all rows agree on the
        # pending token); a > 0 -> first active row.  The a>0 ⇒
        # some-row-active invariant is re-checked host-side on the
        # packed result, where it can still fail loudly (§7.2).
        k_star = jnp.where(
            a > 0, jnp.argmax(res.active, axis=1).astype(jnp.int32), 0)

        # --- arena rollback: in-program surviving-row gather ------
        surv = slot_of * K + k_star[slot_of]
        row_src = jnp.where(live_row, surv, row_ids)
        with jax.named_scope("rollback"):
            t_kv2 = {kk: jnp.take(t_kv2[kk], row_src, axis=1)
                     for kk in t_kv2}
            d_kv2 = {kk: jnp.take(d_kv1[kk], row_src, axis=1)
                     for kk in d_kv1}
        new_pos = jnp.where(live, pos + 1 + a, pos)

        # --- residual drafter catch-up ----------------------------
        # Fully-accepted slots write Y_L at base_pos + L; every
        # other row decodes a dummy token at its post-rollback
        # position, which the next round's first sweep (or the next
        # admission's prefill scatter) overwrites before anything
        # attends it.  Unlike the host-driven round this step is
        # unconditional — a fixed program cannot branch on host
        # data — and the dummy writes are harmless for the same
        # reason they are in the conditional path.
        full = live & (a == L)
        y_l = res.tokens[:, L - 1]
        extra_tok = jnp.where(full[slot_of], y_l[slot_of],
                              0).astype(jnp.int32)[:, None]
        extra_pos = jnp.where(full, pos + L, new_pos)
        with jax.named_scope("catch_up"):
            _, d_kv3 = decode_step_slots(
                d_params, d_cfg, extra_tok, d_kv2,
                jnp.repeat(extra_pos, K),
                use_kernel=cfg.decode_kernel,
                interpret=cfg.pallas_interpret, tp_axis=tp_axis)

        packed = {"tokens": res.tokens, "accepted": a,
                  "active": res.active, "pos": new_pos}
        return t_kv2, d_kv3, new_pos, packed

    return round_core


def tp_local_config(cfg, tp: int):
    """The per-device model config for a tp-way sharded model call:
    heads and KV heads divide by tp (the GQA group size is preserved —
    both divide, so H_loc / Hkv_loc == H / Hkv); every other field is
    untouched because the sharded entry points never consult d_ff or
    vocab for shapes (the weight shards carry them)."""
    if tp == 1:
        return cfg
    return cfg.replace(num_heads=cfg.num_heads // tp,
                       num_kv_heads=cfg.kv_heads // tp)


def validate_tp_divisibility(cfg, tp: int, role: str) -> None:
    """Fail loudly at engine construction when a model's dims cannot
    shard tp ways (DESIGN.md §15): every sharded weight's output dim
    and the KV heads axis must divide."""
    hd = cfg.resolved_head_dim
    dims = {"num_heads": cfg.num_heads, "kv_heads": cfg.kv_heads,
            "d_ff": cfg.d_ff, "d_model": cfg.d_model,
            "padded_vocab": cfg.padded_vocab,
            "attn_out (num_heads*head_dim)": cfg.num_heads * hd}
    for dim_name, val in dims.items():
        if val % tp:
            raise ValueError(
                f"tp={tp} does not divide {role} {dim_name}={val}")


@dataclasses.dataclass
class _Session:
    """Pool-resident decode state for one request."""
    uid: int
    slot: int
    pending: int                 # last emitted token, not yet in cache


class CachedSpecDecEngine:
    """Multi-request speculative decoding with persistent KV caches.
    Dense-family target and drafter (the paper-scale pair); all six
    verification strategies route through the shared block verifier."""

    def __init__(self, target: tuple, drafter: tuple, cfg: SpecDecConfig,
                 pool_slots: int = 1,
                 pool_pages: Optional[int] = None):
        self.t_params, self.t_cfg = target
        self.d_params, self.d_cfg = drafter
        assert self.t_cfg.family == "dense" and self.d_cfg.family == "dense"
        # One drafter model and one draft temperature: the cached draft
        # sweep scores every lane with cfg.temps[0], so heterogeneous
        # temps would silently diverge from the reference engine's
        # per-column path instead of staying bit-identical — refuse them.
        assert len(set(cfg.temps)) == 1, (
            "CachedSpecDecEngine requires homogeneous draft temperatures; "
            "use the reference SpecDecEngine for the diverse-drafts setup")
        self.cfg = cfg
        self.vocab = self.t_cfg.vocab_size
        # Serving tensor parallelism (DESIGN.md §15): tp > 1 builds the
        # 1-D ("model",) mesh up front (fail fast on device count /
        # divisibility) and runs the fused round and the admission
        # prefill under shard_map with LOCAL model configs.  The host-
        # driven kv path keeps the full configs; the scheduler serves
        # tp > 1 through kv_fused only.
        self.mesh = None
        self._tp_axis = None
        self._t_cfg_local, self._d_cfg_local = self.t_cfg, self.d_cfg
        if cfg.tp > 1:
            from repro.launch.mesh import make_tp_mesh
            validate_tp_divisibility(self.t_cfg, cfg.tp, "target")
            validate_tp_divisibility(self.d_cfg, cfg.tp, "drafter")
            self.mesh = make_tp_mesh(cfg.tp)
            self._tp_axis = "model"
            self._t_cfg_local = tp_local_config(self.t_cfg, cfg.tp)
            self._d_cfg_local = tp_local_config(self.d_cfg, cfg.tp)
        self.pool_slots = pool_slots
        # Physical page budget for a paged pool (DESIGN.md §12): None
        # auto-grows (starts at contiguous-equivalent capacity, doubles
        # on demand); an int is a HARD budget — reservation past it
        # raises PagePoolExhausted, and the v2 scheduler uses the
        # ``page_state``/``request_pages`` accounting below to evict
        # before ever hitting it.  Ignored for contiguous pools.
        self.pool_pages = pool_pages
        self.pool: Optional[CachePool] = None
        self._sessions: dict = {}
        # Quantized serving (DESIGN.md §11): W8A8 target weights are used
        # ONLY by the verify matmuls — admission prefill keeps the f32
        # tree (prompt KV quality sets the whole session's context) and
        # the drafter stays f32 (it is already the small model).  The
        # KV arenas quantize pool-wide via CachePool(quant=True).
        self._t_verify_params = self.t_params
        if cfg.quant:
            from repro.serving.quant import quantize_params
            self._t_verify_params = quantize_params(self.t_params)
        self._d_step = jax.jit(
            lambda p, t, c, pos: decode_step_slots(
                p, self.d_cfg, t, c, pos, use_kernel=cfg.decode_kernel,
                interpret=cfg.pallas_interpret))
        self._t_verify = jax.jit(
            lambda p, t, c, pos: verify_step_slots(p, self.t_cfg, t, c, pos))
        # Fused round program (built lazily once the pool geometry is
        # known; rebuilt when buf_len grows — the paged program closes
        # over the view length, DESIGN.md §8/§12).
        self._fused_round = None
        self._fused_round_buf = None
        # Paged model-call jits, keyed by (kind, buf_len): the gathered
        # view length is a compile-time shape, so each buffer growth
        # compiles a fresh entry (exactly when the contiguous path
        # would retrace on its grown arena shapes).
        self._paged_jits: dict = {}
        # Persistent contiguous view for the paged kv_fused path (§12):
        # the fused round runs the SAME contiguous program in both
        # modes, operating on this gathered working set; page storage
        # is cold state, synced per-slot only at events (suspend,
        # resume, admission, mode switch).  ``_view_dirty`` tracks
        # slots whose view rows are newer than their pages.
        self._fused_view: Optional[dict] = None
        self._view_dirty: set = set()
        # Bucketed admission (DESIGN.md §9): stacked arena prefill, one
        # compile per (model, bucket), shared by per-request ``admit``
        # (a one-request wave).  The input arena is donated like the
        # fused round's (§8 donation contract).
        self._slot_prefill = {
            "target": self._build_slot_prefill(self.t_params,
                                               self._t_cfg_local),
            "drafter": self._build_slot_prefill(self.d_params,
                                                self._d_cfg_local),
        }
        # Serving instrumentation (read by the scheduler / benchmarks).
        self.num_target_forwards = 0
        self.num_draft_forwards = 0
        # Prefill model dispatches spent on admission: 2 per chunk per
        # request on the per-request path, <= 2 x buckets per chunk
        # round per wave when batched.
        self.num_prefill_dispatches = 0
        # Device->host transfers spent materializing draft tokens (one
        # per draft step per round, shared across all live requests).
        self.num_draft_syncs = 0

    def _build_slot_prefill(self, params, mcfg):
        """Jitted admission prefill for one model.  Under serving TP it
        runs sharded like the fused round (DESIGN.md §15): the weights
        stay in their output-dim shards and the arena in its KV-head
        shards, joined by exact all-gathers, so admission never gathers
        a whole model onto one device."""
        cfg = self.cfg

        def fn(p, t, c, pos, w):
            return prefill_slots(p, mcfg, t, c, pos, w,
                                 use_kernel=cfg.prefill_kernel,
                                 interpret=cfg.pallas_interpret,
                                 tp_axis=self._tp_axis)

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P
            from repro.sharding.rules import (serve_cache_pspec,
                                              serve_params_pspecs)
            from repro.specdec.distributed import tp_fused_round
            kv = {"k": serve_cache_pspec(5), "v": serve_cache_pspec(5)}
            fn = tp_fused_round(
                fn, self.mesh,
                (serve_params_pspecs(params, self.mesh), P(), kv, P(), P()),
                kv)
        return jax.jit(fn, donate_argnums=(2,))

    # -- pool / session lifecycle ------------------------------------------
    def _ensure_pool(self, buf_len: int) -> CachePool:
        if self.pool is None:
            cfgs = {"target": self.t_cfg, "drafter": self.d_cfg}
            if self.cfg.paged:
                self.pool = PagedCachePool(
                    cfgs, num_slots=self.pool_slots,
                    rows_per_slot=self.cfg.num_drafts, buf_len=buf_len,
                    quant=self.cfg.quant, page_size=self.cfg.page_size,
                    num_pages=self.pool_pages)
            else:
                self.pool = CachePool(
                    cfgs, num_slots=self.pool_slots,
                    rows_per_slot=self.cfg.num_drafts, buf_len=buf_len,
                    quant=self.cfg.quant, mesh=self.mesh)
        else:
            if buf_len > self.pool.buf_len:
                # Growth re-traces the fused program AND reshapes the
                # paged view; commit the view first (the scatter must
                # run against the pre-growth table width).
                self._view_commit()
            self.pool.ensure_buf(buf_len)
        return self.pool

    def _paged_jit(self, kind: str):
        """Jitted paged model call for the pool's CURRENT buf_len.
        ``kind``: "d_step" | "t_verify" | "prefill_target" |
        "prefill_drafter"."""
        bl = self.pool.buf_len
        key = (kind, bl)
        if key in self._paged_jits:
            return self._paged_jits[key]
        cfg = self.cfg
        if kind == "d_step":
            fn = jax.jit(
                lambda p, t, pg, tb, pos: decode_step_slots_paged(
                    p, self.d_cfg, t, pg, tb, pos, buf_len=bl,
                    use_kernel=cfg.decode_kernel,
                    interpret=cfg.pallas_interpret))
        elif kind == "t_verify":
            fn = jax.jit(
                lambda p, t, pg, tb, pos: verify_step_slots_paged(
                    p, self.t_cfg, t, pg, tb, pos, buf_len=bl))
        else:
            mcfg = self.t_cfg if kind == "prefill_target" else self.d_cfg
            fn = jax.jit(
                lambda p, t, pg, tb, pos, w: prefill_slots_paged(
                    p, mcfg, t, pg, tb, pos, w, buf_len=bl,
                    use_kernel=cfg.prefill_kernel,
                    interpret=cfg.pallas_interpret),
                donate_argnums=(2,))
        self._paged_jits[key] = fn
        return fn

    # -- paged fused view (§12): pages as cold storage ---------------------
    # The paged kv_fused path never pays per-round gather/scatter.  The
    # first fused round gathers ONE contiguous working set; every later
    # round runs the contiguous program on it (donation-chained, zero
    # paging cost).  Page storage only has to be current when something
    # other than the fused round reads it — a suspend detaching a
    # slot's chains, the host-driven kv path, or buffer growth — so
    # sync is per-slot and event-rate, not per-round.

    def _view_sync(self, slots) -> None:
        """Scatter the listed slots' view rows into page storage (rows
        of every other slot are masked out of the table, so their pages
        are bit-untouched).  One slot at a time: the row-subset shape
        is then always (rows_per_slot, n_lp), so the whole event path
        compiles exactly one scatter program per model — a wave-sized
        subset would compile one program PER WAVE SIZE, and a mid-run
        compile is a ~0.5s stall on the serving clock."""
        if self._fused_view is None:
            return
        pool = self.pool
        for slot in sorted(set(slots) & self._view_dirty):
            rows = pool.rows_of(slot)
            tbl = jnp.asarray(pool.page_table[rows])
            for name in ("target", "drafter"):
                sub = {kk: leaf[:, rows]
                       for kk, leaf in self._fused_view[name].items()}
                pool.update(name, paged_kv.scatter_arena_jit(
                    pool.pages[name], tbl, sub))
            self._view_dirty.discard(slot)

    def _view_refresh(self, slots) -> None:
        """Gather the listed slots' rows from page storage into the
        view (after an admission prefill or a resumed handle's attach
        wrote pages behind the view's back).  Per-slot for the same
        one-compiled-shape reason as ``_view_sync``."""
        if self._fused_view is None:
            return
        pool = self.pool
        for slot in sorted(set(slots)):
            rows = pool.rows_of(slot)
            tbl = jnp.asarray(pool.page_table[rows])
            for name in ("target", "drafter"):
                sub = paged_kv.gather_arena_jit(pool.pages[name], tbl,
                                                buf_len=pool.buf_len)
                self._fused_view[name] = {
                    kk: self._fused_view[name][kk].at[:, rows].set(sub[kk])
                    for kk in sub}
            self._view_dirty.discard(slot)

    def _view_commit(self) -> None:
        """Write every dirty slot back to pages and drop the view —
        the full sync a mode switch or buffer growth needs."""
        if self._fused_view is not None:
            self._view_sync(set(self._view_dirty))
            self._fused_view = None
        self._view_dirty.clear()

    # -- page accounting (the v2 scheduler's capacity oracle, §12) ---------
    def has_session(self, uid) -> bool:
        return uid in self._sessions

    def evict(self, uid) -> None:
        """Evict a live session mid-generation: drop the session and
        return its slot (and, paged, its pages) to the pool.  The caller
        re-admits later with the full ``prompt + output`` prefix —
        bit-identical resumption, because re-prefilled KV is bitwise
        equal to decode-built KV and per-request randomness depends only
        on (uid, blocks), never on which round a block ran in."""
        self.release(uid)

    def can_suspend(self) -> bool:
        """Whether preemption can keep KV resident (paged pools only —
        a contiguous slot's KV dies with the slot)."""
        return bool(self.cfg.paged)

    def suspend(self, uid) -> dict:
        """Preempt WITHOUT forfeiting KV: pop the session and detach
        its page chains.  The returned handle owns the pages; the slot
        frees for another request, and ``resume`` re-binds the chains
        to any free slot with a host table rewrite — no prefill, no
        recompute.  Bit-identity is trivial here: the resumed state is
        the SAME device bytes the request left behind."""
        sess = self._sessions.pop(uid)
        # The handle's pages must hold the slot's CURRENT KV; under the
        # fused view they may be stale (pages are cold storage), so
        # flush this one slot's rows first — the only per-suspend cost.
        self._view_sync({sess.slot})
        handle = self.pool.detach(sess.slot)
        handle["pending"] = sess.pending
        return handle

    def resume(self, uid, handle: dict) -> int:
        """Re-admit a suspended request from its handle."""
        assert uid not in self._sessions
        slot = self.pool.alloc()
        self.pool.attach(slot, handle)
        self._view_refresh({slot})
        self._sessions[uid] = _Session(uid=uid, slot=slot,
                                       pending=int(handle["pending"]))
        return slot

    def handle_pages(self, handle: dict) -> int:
        """Physical pages a suspend handle holds."""
        return int(handle["chain_len"]) * self.pool.rows_per_slot

    def drop_handle(self, handle: dict) -> None:
        """Demote a suspended request to hard-evicted: forfeit its
        pages (it re-admits via re-prefill like any evicted request)."""
        self.pool.release_handle(handle)

    # -- fault recovery + degradation ladder (DESIGN.md §13) ---------------
    def discard_round_state(self, scrub: bool = False) -> None:
        """Drop every piece of round-scoped device state after a
        guarded fault, leaving the pool in the host-authoritative state
        a fresh admission wave expects: the fused view (which may hold
        an aborted round's in-flight arenas) and the lazily-mirrored
        device positions/page table.  Callers displace every session
        first — the scheduler evicts or suspends all live requests
        before discarding, so nothing references the dropped state.

        ``scrub=True`` additionally zeroes the KV storage itself — the
        NaN-poisoning recovery.  Finite garbage in dead regions is
        masked out of every attention read, but NaN garbage is not
        (``0 * NaN = NaN`` in the masked weight sum), so arenas that
        may hold poisoned bytes are rebuilt rather than reused."""
        assert not self._sessions, \
            "discard_round_state with live sessions; displace them first"
        self._fused_view = None
        self._view_dirty.clear()
        if self.pool is not None:
            self.pool.drop_device_mirrors()
            if scrub:
                self.pool.scrub()

    def set_verifier_backend(self, backend: str) -> None:
        """Degradation-ladder rung: swap the block-verification backend
        in place (pallas -> xla in practice).  Token-invisible — the
        backends are exact-equality oracles of one another
        (tests/test_block_verify.py asserts array_equal across them).
        The fused round program closes over the config, so it rebuilds
        lazily on the next round."""
        if backend == self.cfg.verifier_backend:
            return
        self.cfg = dataclasses.replace(self.cfg, verifier_backend=backend)
        self._fused_round = None

    def dequantize_verify(self) -> None:
        """Degradation-ladder rung quant -> f32: swap the W8A8 verify
        weights back to the f32 tree.  The KV arenas keep their int8
        STORAGE format (rebuilding the pool mid-serve would drop every
        live session); only the verify matmuls change precision.  Note
        this rung is acceptance-equivalent, not bit-identical — the
        chaos bit-identity gate runs unquantized configs."""
        self._t_verify_params = self.t_params
        self._verify_dequantized = True

    def requantize_verify(self) -> None:
        """Healing-probe reverse of ``dequantize_verify`` (DESIGN.md
        §14): re-derive the W8A8 verify tree from the f32 params and
        put the quantized verify matmuls back.  No-op on an unquantized
        config — there is nothing to climb back to."""
        if not self.cfg.quant or not getattr(self, "_verify_dequantized",
                                             False):
            return
        from repro.serving.quant import quantize_params
        self._t_verify_params = quantize_params(self.t_params)
        self._verify_dequantized = False

    # -- durable state (DESIGN.md §14) --------------------------------------
    def export_state(self) -> dict:
        """The engine facts a snapshot needs: enough to (a) re-apply
        mode flags the degradation ladder flipped and (b) REFUSE a
        restore onto an incompatible engine (different quant/paged/
        strategy config would break the bit-identity contract the
        journal's replay leans on).  No device state — KV rebuilds from
        prompts, programs rebuild lazily."""
        cfg = self.cfg
        return {
            "strategy": cfg.strategy,
            "num_drafts": int(cfg.num_drafts),
            "draft_len": int(cfg.draft_len),
            "vocab": int(self.vocab),
            "quant": bool(cfg.quant),
            "paged": bool(cfg.paged),
            "page_size": int(cfg.page_size),
            "verifier_backend": cfg.verifier_backend,
            "verify_dequantized": bool(getattr(self, "_verify_dequantized",
                                               False)),
        }

    _RESTORE_COMPAT = ("strategy", "num_drafts", "draft_len", "vocab",
                       "quant", "paged", "page_size")

    def restore_state(self, state: dict) -> None:
        """Re-apply exported mode flags onto a FRESH engine and verify
        config compatibility.  Mismatched identity fields raise — a
        journal written under quant=int8 replays different tokens on an
        f32 engine, which is exactly the silent divergence the §14
        restore contract forbids."""
        mine = self.export_state()
        for f in self._RESTORE_COMPAT:
            if f in state and state[f] != mine[f]:
                raise ValueError(
                    f"restore onto incompatible engine: {f}="
                    f"{mine[f]!r} but the snapshot was written with "
                    f"{f}={state[f]!r}")
        backend = state.get("verifier_backend")
        if backend and backend != self.cfg.verifier_backend:
            self.set_verifier_backend(backend)
        if state.get("verify_dequantized"):
            self.dequantize_verify()

    def residency(self) -> dict:
        """Arena-residency summary for the round journal (§14): live
        sessions plus the pool's slot/page occupancy.  Observability
        only — restore re-prefills from prompts, it never trusts this."""
        out = {"sessions": len(self._sessions)}
        if self.pool is not None:
            out.update(self.pool.residency())
        return out

    def page_state(self) -> Optional[dict]:
        """{free, total, fixed} physical-page accounting, or None when
        the engine is not paged.  Before the pool exists the whole
        budget is free."""
        if not self.cfg.paged:
            return None
        if self.pool is not None:
            return {"free": self.pool.free_pages,
                    "total": self.pool.num_pages,
                    "fixed": self.pool.fixed_budget}
        if self.pool_pages is None:
            return {"free": None, "total": None, "fixed": False}
        return {"free": self.pool_pages, "total": self.pool_pages,
                "fixed": True}

    def request_pages(self, prefix_len: int) -> int:
        """Pages a request at prefix length ``prefix_len`` holds AFTER
        its next speculative round: every round reserves through
        ``pos + L + 1`` positions across its K lanes, so this is the
        number the scheduler must budget to admit (or keep) it."""
        per_row = -(-(prefix_len + self.cfg.draft_len + 1)
                    // self.cfg.page_size)
        return per_row * self.cfg.num_drafts

    def held_pages(self, uid) -> int:
        if self.pool is None or uid not in self._sessions:
            return 0
        return self.pool.held_pages(self._sessions[uid].slot)

    def admit(self, uid: int, prompt: np.ndarray, buf_len: int) -> int:
        """Per-request admission (the reference path): a one-request
        wave of ``admit_batch``.  Both admission paths therefore run the
        same arena-wide, bucketed ``prefill_slots`` program and write
        bit-identical caches by construction — a separately shaped
        prefill would leave that identity to the backend's matmul
        choices (XLA:CPU routes small dots to a different kernel, whose
        rounding differs).  Costs 2 dispatches per chunk per request."""
        self.admit_batch([(uid, prompt)], buf_len)
        return self._sessions[uid].slot

    def admit_batch(self, pairs, buf_len: int) -> None:
        """Bucketed batched admission (DESIGN.md §9): admit every
        ``(uid, prompt)`` in ``pairs`` with prompt KV written straight
        into the pool arenas on device.

        The wave's prefills drain into power-of-two length buckets
        (``_bucket_plan``); each (chunk round, bucket) group is ONE
        stacked ``prefill_slots`` dispatch per model over the whole
        arena — rows outside the group are write-masked — so a wave
        costs at most ``2 x buckets`` dispatches per chunk round instead
        of ``2 x requests``, and the compiled shape set is the bucket
        set.  Chunk c+1 of a prompt attends chunk c's KV already in the
        arena, which is what makes repeated calls equal one long
        prefill."""
        pairs = [(uid, np.asarray(p, np.int32)) for uid, p in pairs]
        if not pairs:
            return
        pool = self._ensure_pool(buf_len)
        paged = isinstance(pool, PagedCachePool)
        rows_n = pool.num_slots * self.cfg.num_drafts
        max_bucket = _max_bucket(pool.buf_len)
        plans = []
        for uid, prompt in pairs:
            assert uid not in self._sessions
            assert len(prompt) >= 1
            slot = pool.alloc()
            self._sessions[uid] = _Session(uid=uid, slot=slot,
                                           pending=int(prompt[-1]))
            if paged:
                # Reserve the whole prompt's chain up front (host-side
                # table bookkeeping only) so every chunk's scattered
                # writes land in mapped pages.
                pool.reserve(slot, len(prompt) - 1)
            plans.append((uid, slot, prompt[:-1],
                          _bucket_plan(len(prompt) - 1, max_bucket)))
        params = {"target": self.t_params, "drafter": self.d_params}
        # Paged + fused view live (§12): prefill straight INTO the view
        # with the contiguous ``prefill_slots`` program — the admitted
        # slots become dirty (pages get their content only if they
        # later suspend), and the wave pays zero gather/refresh.
        # Without a view (first wave, or the host-driven kv path) the
        # prefills scatter through the page table as before.
        use_view = paged and self._fused_view is not None
        for c in range(max(len(p[3]) for p in plans)):
            groups = {}
            for uid, slot, toks, chunks in plans:
                if c < len(chunks):
                    groups.setdefault(chunks[c][2], []).append(
                        (uid, slot, toks, chunks[c]))
            for bucket in sorted(groups):
                tok = np.zeros((rows_n, bucket), np.int32)
                pos = np.zeros((rows_n,), np.int32)
                write = np.zeros((rows_n,), bool)
                for _, slot, toks, (off, ln, _) in groups[bucket]:
                    rr = pool.rows_of(slot)
                    tok[rr, :ln] = toks[off:off + ln]
                    pos[rr] = off
                    write[rr] = True
                tok_d, pos_d, write_d = (jnp.asarray(tok), jnp.asarray(pos),
                                         jnp.asarray(write))
                # The dispatch computes rows_n x bucket arena cells; the
                # prompt tokens it writes count once per request.
                uids = [g[0] for g in groups[bucket]]
                tokens = sum(g[3][1] for g in groups[bucket])
                for name in ("target", "drafter"):
                    # Install each chunk's output arena immediately —
                    # the input buffer is donated, so pool.caches must
                    # never be left pointing at it (a mid-wave failure
                    # would otherwise corrupt the pool).
                    with span("engine.prefill", model=name, bucket=bucket,
                              rows=rows_n, tokens=tokens, uids=uids):
                        if use_view:
                            self._fused_view[name] = \
                                self._slot_prefill[name](
                                    params[name], tok_d,
                                    self._fused_view[name], pos_d, write_d)
                        elif paged:
                            pool.update(name, self._paged_jit(
                                "prefill_" + name)(
                                    params[name], tok_d, pool.pages[name],
                                    pool.pt_device(), pos_d, write_d))
                        else:
                            pool.update(name, self._slot_prefill[name](
                                params[name], tok_d, pool.caches[name],
                                pos_d, write_d))
                    self.num_prefill_dispatches += 1
        for _, slot, toks, _ in plans:
            pool.set_pos(slot, len(toks))
        if use_view:
            self._view_dirty.update(p[1] for p in plans)
        elif paged:
            # The wave's prefills wrote PAGES behind an absent view;
            # nothing to pull (the next fused round's entry gather or
            # the kv path's ops read pages directly).
            pass

    def release(self, uid: int) -> None:
        sess = self._sessions.pop(uid)
        self._view_dirty.discard(sess.slot)
        self.pool.release(sess.slot)

    # -- the batched cached block ------------------------------------------
    def _block_randomness(self, sub: jax.Array):
        # Shared with the reference engine so both see the same uniform
        # sheet (the RNG contract of DESIGN.md §3.2).
        return block_randomness(sub, self.cfg.draft_len,
                                self.cfg.num_drafts, self.vocab)

    def _block_cached(self, subs: Sequence[jax.Array],
                      uids: Sequence[int]) -> list:
        """Advance every listed session one speculative block: one drafter
        decode sweep (x L) and ONE stacked verify_step over the whole
        arena, then per-request fused verification + arena rollback."""
        cfg = self.cfg
        pool = self.pool
        K, Lr, N = cfg.num_drafts, cfg.draft_len, self.vocab
        S = pool.num_slots
        sessions = [self._sessions[u] for u in uids]
        r_n = len(sessions)
        need_probs = cfg.strategy in RS_STRATEGIES

        rand = [self._block_randomness(s) for s in subs]
        log_u_all = jnp.stack([lu for lu, _ in rand])     # (R, L+1, K, N)

        live_rows = np.concatenate([pool.rows_of(s.slot) for s in sessions])
        base_pos = pool.pos.copy()                        # (S,) host
        row_pos0 = pool.row_positions()                   # (S*K,) host
        # The verify chunk writes positions [pos, pos + L]; the arenas are
        # non-ring, so running past the buffer must fail loudly here
        # rather than silently wrap/clamp the KV writes.  Callers size
        # buf_len as len(prompt) + max_new + L + 2 (scheduler contract).
        hi = max(base_pos[s.slot] for s in sessions) + Lr + 1
        assert hi <= pool.buf_len, (
            f"speculative block would write through position {hi - 1} but "
            f"the cache arena holds {pool.buf_len}; pass a larger buf_len")
        paged = isinstance(pool, PagedCachePool)
        table = None
        if paged:
            # The host-driven path's ops read/write page storage
            # directly; if fused rounds left a newer view, commit it
            # (mixing modes on one engine stays bit-exact).
            self._view_commit()
            # Extend every advancing slot's chain through the round's
            # write horizon (verify writes [pos, pos + L], catch-up
            # writes at pos + L) before any device work is dispatched.
            for sess in sessions:
                pool.reserve(sess.slot, int(base_pos[sess.slot]) + Lr + 1)
            table = pool.pt_device()

        # --- drafts: L arena decode sweeps, live rows advance -------------
        cur = np.zeros((S * K, 1), np.int32)
        for sess in sessions:
            cur[pool.rows_of(sess.slot)] = sess.pending
        d_tokens = np.zeros((r_n, K, Lr), np.int32)
        prob_steps = []
        d_cache = pool.pages["drafter"] if paged else pool.caches["drafter"]
        draft_syncs = 0
        for j in range(Lr):
            if paged:
                logits, d_cache = self._paged_jit("d_step")(
                    self.d_params, jnp.asarray(cur), d_cache, table,
                    jnp.asarray(row_pos0 + j))
            else:
                logits, d_cache = self._d_step(
                    self.d_params, jnp.asarray(cur), d_cache,
                    jnp.asarray(row_pos0 + j))
            self.num_draft_forwards += 1
            live = logits[jnp.asarray(live_rows)]
            p_all = probs_from_logits(live, cfg.temps[0], cfg.top_k, N)
            tok = V.draft_token_from_uniforms(
                log_u_all[:, j].reshape(r_n * K, N), p_all)
            tk = np.asarray(tok).reshape(r_n, K)   # 1 transfer / draft step
            draft_syncs += 1
            d_tokens[:, :, j] = tk
            cur = np.zeros((S * K, 1), np.int32)
            for r, sess in enumerate(sessions):
                cur[pool.rows_of(sess.slot), 0] = tk[r]
            if need_probs:
                prob_steps.append(p_all)
        pool.update("drafter", d_cache)
        d_probs = None
        if need_probs:
            d_probs = jnp.stack(prob_steps).reshape(
                Lr, r_n, K, N).transpose(1, 2, 0, 3)

        # --- target: ONE stacked verify chunk over the arena --------------
        chunk = np.zeros((S * K, Lr + 1), np.int32)
        for r, sess in enumerate(sessions):
            chunk[pool.rows_of(sess.slot)] = np.concatenate(
                [np.full((K, 1), sess.pending, np.int32), d_tokens[r]],
                axis=1)
        if paged:
            t_logits, t_cache = self._paged_jit("t_verify")(
                self._t_verify_params, jnp.asarray(chunk),
                pool.pages["target"], table, jnp.asarray(row_pos0))
        else:
            t_logits, t_cache = self._t_verify(
                self._t_verify_params, jnp.asarray(chunk),
                pool.caches["target"], jnp.asarray(row_pos0))
        self.num_target_forwards += 1
        pool.update("target", t_cache)
        q = probs_from_logits(t_logits[jnp.asarray(live_rows)],
                              cfg.target_temp, cfg.top_k, N)
        q = q.reshape(r_n, K, Lr + 1, N)

        # --- fused block verification (Algorithm 2), per request ----------
        outs = []
        row_src = np.arange(S * K)
        full_slots = {}          # slot -> Y_L, for a == L catch-up
        for r, sess in enumerate(sessions):
            hb = run_block_verify(
                log_u_all[r], d_tokens[r],
                None if d_probs is None else d_probs[r], q[r], rand[r][1],
                strategy=cfg.strategy, backend=cfg.verifier_backend,
                interpret=cfg.pallas_interpret)
            a = hb.num_accepted
            # hb.active is already host-side — the fused verifier's single
            # device_get covers it, so selecting the surviving row costs
            # no extra sync (the accounting rule of DESIGN.md §7.3).
            k_star = _select_rollback_row(hb.active, a)
            rows = pool.rows_of(sess.slot)
            row_src[rows] = rows[0] + k_star
            pool.set_pos(sess.slot, base_pos[sess.slot] + 1 + a)
            if a == Lr:
                # Drafter consumed [pending, d_1..d_{L-1}]: on full
                # acceptance its cache is one token short — feed Y_L at
                # position base_pos + L in the post-rollback sweep below.
                full_slots[sess.slot] = hb.new_tokens[Lr - 1]
            sess.pending = hb.new_tokens[-1]
            outs.append(BlockOutcome(new_tokens=hb.new_tokens,
                                     accepted=a,
                                     verify_syncs=hb.host_syncs,
                                     active=hb.active))

        # --- arena rollback: one gather replicates surviving rows ---------
        pool.rollback_rows(row_src)

        if full_slots:
            # One extra drafter sweep catches up fully-accepted slots
            # (write Y_L at base_pos + L).  Every other row decodes a
            # dummy token at its POST-rollback position — exactly where
            # the next block's first sweep writes that row's pending
            # token, so the dummy KV is overwritten before anything can
            # attend to it (free-slot rows are fully overwritten by the
            # admission prefill scatter).
            extra_tokens = np.zeros((S * K, 1), np.int32)
            extra_pos = pool.row_positions()          # post-rollback pos
            for slot, y_l in full_slots.items():
                rows = pool.rows_of(slot)
                extra_tokens[rows, 0] = y_l
                extra_pos[rows] = base_pos[slot] + Lr
            if paged:
                _, d_cache = self._paged_jit("d_step")(
                    self.d_params, jnp.asarray(extra_tokens),
                    pool.pages["drafter"], table,
                    jnp.asarray(extra_pos, np.int32))
            else:
                _, d_cache = self._d_step(
                    self.d_params, jnp.asarray(extra_tokens),
                    pool.caches["drafter"], jnp.asarray(extra_pos, np.int32))
            self.num_draft_forwards += 1
            pool.update("drafter", d_cache)

        self.num_draft_syncs += draft_syncs
        return outs

    # -- the fused single-dispatch round (DESIGN.md §8) ---------------------
    def _build_fused_round(self):
        """Compile the whole speculative round into one jitted program.

        Geometry (S slots x K lanes, L steps) is closed over, so the
        program has fixed shapes regardless of how many requests are
        live — liveness is a data-level (S,) mask, and free slots ride
        along as dead rows exactly as they do in the host-driven round.
        Cache arenas and device positions are DONATED (where the backend
        supports it): callers must install the returned buffers via
        ``CachePool.adopt_round_device`` (then ``refresh_pos_host`` once
        the packed result lands) and never touch the inputs again.

        With ``cfg.tp > 1`` the same round body runs under shard_map on
        the engine's ("model",) mesh (DESIGN.md §15): weights and KV
        arenas enter sharded per ``specdec.distributed.tp_round_specs``,
        the body computes with LOCAL head/ffn/vocab shards plus exact
        all-gathers, and the packed result comes out replicated — still
        exactly one device->host fetch per round.
        """
        cfg = self.cfg
        if cfg.verifier_backend == "legacy":
            raise ValueError(
                "fused rounds need a device verifier backend ('xla' or "
                "'pallas'); the 'legacy' host loop cannot run in-program")
        round_core = build_round_core(
            cfg, self._t_cfg_local, self._d_cfg_local, self.vocab,
            self.pool.num_slots, tp_axis=self._tp_axis)
        # Buffer donation (the §8 donation contract), on every backend.
        donate = (2, 3, 4)
        if cfg.tp == 1:
            return jax.jit(round_core, donate_argnums=donate)
        from repro.specdec.distributed import tp_fused_round, tp_round_specs
        in_specs, out_specs = tp_round_specs(
            self._t_verify_params, self.d_params,
            self.pool.caches["target"], self.pool.caches["drafter"],
            self.mesh)
        return jax.jit(
            tp_fused_round(round_core, self.mesh, in_specs, out_specs),
            donate_argnums=donate)

    def _block_fused(self, subs: Sequence[jax.Array],
                     uids: Sequence[int], admits: Sequence = ()) -> list:
        """Advance every listed session one speculative round as ONE
        device dispatch; the round's only device->host transfer is the
        packed (tokens, accepted, active, pos) fetch.

        ``admits`` are ``(uid, prompt)`` pairs admitted INSIDE the
        round's overlap window (DESIGN.md §9): their bucketed prefill
        dispatches are issued against the round's output arenas after
        the round is in flight but BEFORE the host blocks on the packed
        fetch, so admission costs no extra host round-trip and the
        prompts prefill while the round computes."""
        cfg, pool = self.cfg, self.pool
        K, L, S = cfg.num_drafts, cfg.draft_len, pool.num_slots
        with span("engine.round", uids=list(uids)) as at:
            sessions = [self._sessions[u] for u in uids]
            # Same loud non-ring overflow guard as the host-driven round.
            hi = max(pool.pos[s.slot] for s in sessions) + L + 1
            assert hi <= pool.buf_len, (
                f"speculative block would write through position {hi - 1} "
                f"but the cache arena holds {pool.buf_len}; pass a larger "
                "buf_len")
            paged = isinstance(pool, PagedCachePool)
            if paged:
                # Host-side table bookkeeping before dispatch: each
                # advancing slot's chain must cover the round's write
                # horizon.
                for sess in sessions:
                    pool.reserve(sess.slot,
                                 int(pool.pos[sess.slot]) + L + 1)

            live = np.zeros(S, bool)
            pending = np.zeros(S, np.int32)
            # Free slots still need a syntactically valid key for the
            # in-program randomness; their draws are masked garbage.
            sub_rows = [jax.random.PRNGKey(0)] * S
            for sess, sub in zip(sessions, subs):
                live[sess.slot] = True
                pending[sess.slot] = sess.pending
                sub_rows[sess.slot] = sub

            # The program closes over the view length (paged) and is
            # keyed to pool geometry; rebuild when the buffer grows.  (The
            # contiguous program re-traces on grown arena shapes anyway —
            # rebuilding matches cost, old shapes never recur.)
            if self._fused_round is None \
                    or self._fused_round_buf != pool.buf_len:
                self._fused_round = self._build_fused_round()
                self._fused_round_buf = pool.buf_len
            if paged:
                # First fused round (or first after a mode switch /
                # buffer growth dropped the view): gather the working set
                # ONCE.  Every later round chains on the previous round's
                # output arenas — the same donation flow as the contiguous
                # path.
                if self._fused_view is None:
                    pt = pool.pt_device()
                    self._fused_view = {
                        name: paged_kv.gather_arena_jit(
                            pool.pages[name], pt, buf_len=pool.buf_len)
                        for name in ("target", "drafter")}
                    self._view_dirty.clear()
                arenas = self._fused_view
            else:
                arenas = pool.caches
            # Reserved against used arena bytes, from shapes and dtypes
            # (no device sync): the round sweeps every row and position.
            reserved, per_position = _arena_bytes(arenas)
            at["arena_bytes"] = reserved
            at["live_bytes"] = per_position * K * int(
                sum(pool.pos[s.slot] for s in sessions))
            t_kv, d_kv, pos_dev, packed = self._fused_round(
                self._t_verify_params, self.d_params,
                arenas["target"], arenas["drafter"],
                pool.pos_device(), jnp.asarray(pending), jnp.asarray(live),
                jnp.stack(sub_rows))
            self.num_draft_forwards += L + 1
            self.num_target_forwards += 1

            # Install the round's device outputs; the in-flight gap then
            # dispatches this wave's admission prefills (they consume the
            # round's output arenas, so device execution stays ordered).
            if paged:
                self._fused_view = {"target": t_kv, "drafter": d_kv}
                self._view_dirty.update(s.slot for s in sessions)
                pool.adopt_pos_device(pos_dev)
            else:
                pool.adopt_round_device({"target": t_kv, "drafter": d_kv},
                                        pos_dev)
        if admits:
            self.admit_batch(admits, pool.buf_len)

        with span("engine.fetch"):
            host = jax.device_get(packed)      # the round's ONE transfer
        with span("engine.check"):
            pool.refresh_pos_host(host["pos"], [s.slot for s in sessions])
            # Guard the raw fetch (DESIGN.md §13): token range/finiteness,
            # accepted bounds, and the rollback invariant — a NaN-poisoned
            # logit row makes the race argmax emit garbage ids, and this
            # is the last point before that garbage becomes session state.
            check_packed(host, [(s.uid, s.slot) for s in sessions],
                         vocab=self.vocab, draft_len=L)
            outs = []
            for i, sess in enumerate(sessions):
                s = sess.slot
                acc = int(host["accepted"][s])
                active = np.asarray(host["active"][s])
                toks = [int(t) for t in host["tokens"][s][:acc + 1]]
                sess.pending = toks[-1]
                # The packed fetch is one transfer for the WHOLE round;
                # attribute it to the round's first outcome so aggregate
                # accounting reads host_syncs == rounds (§7.3).
                outs.append(BlockOutcome(new_tokens=toks, accepted=acc,
                                         verify_syncs=1 if i == 0 else 0,
                                         active=active))
        return outs

    # -- scheduler contract -------------------------------------------------
    def _admit_wave(self, pairs, buf_len: int,
                    admission: Optional[str] = None) -> None:
        """Admit unseen sessions: one bucketed wave (``admit_batch``, the
        default) or, with ``admission="per_request"``, one ``admit``
        wave per request (the scheduler passes its policy per call)."""
        if admission in (None, "bucketed"):
            self.admit_batch(pairs, buf_len)
        else:
            for uid, prompt in pairs:
                self.admit(uid, prompt, buf_len)

    def round_with_admission(self, subs: Sequence[jax.Array],
                             uids: Sequence[int], admits: Sequence,
                             buf_len: int,
                             tails: Optional[Sequence[int]] = None) -> list:
        """One kv_fused serving round with overlapped admission (§9):
        grow the pool for the whole wave, dispatch the fused round for
        ``uids`` (the already-admitted sessions), dispatch the bucketed
        admission prefills for ``admits`` while the round runs, and only
        then block on the round's packed fetch.  Admitted sessions
        produce no tokens this round — they join the live set next
        round.  ``tails`` (the caller's last emitted token per uid)
        enforces the prefix-tail == pending contract that the
        prefix-carrying ``gen_blocks`` path checks.  Returns
        ``BlockOutcome``s for ``uids`` only."""
        self._ensure_pool(buf_len)
        if tails is not None:
            for uid, tail in zip(uids, tails):
                sess = self._sessions[uid]
                assert int(tail) == sess.pending, (
                    f"uid {uid}: prefix tail {int(tail)} != cached "
                    f"pending {sess.pending}")
        if not uids:
            self._admit_wave(admits, buf_len, admission="bucketed")
            return []
        return self._block_fused(subs, uids, admits=admits)

    def gen_blocks(self, subs: Sequence[jax.Array],
                   prefixes: Sequence[np.ndarray], buf_len: int,
                   uids: Optional[Sequence[int]] = None,
                   fused: bool = False,
                   admission: Optional[str] = None) -> list:
        """Advance R requests by one speculative block each (the reference
        engine's scheduler contract, DESIGN.md §1).  With ``uids`` the
        engine serves from persistent slots: unseen uids are admitted
        as one bucketed wave (their prefixes prefill straight into the
        pool arenas, §9; ``admission="per_request"`` keeps the reference
        path), known uids continue from their cached state and
        ``prefixes[i]`` only validates the contract (its last token
        must equal the session's pending token).  Without uids, each
        call runs against ephemeral slots.  ``fused=True`` runs the
        round as one device dispatch (§8) — same tokens, 0 draft syncs,
        1 host sync per round."""
        block = self._block_fused if fused else self._block_cached
        if uids is None:
            ephemeral = [object() for _ in prefixes]
            try:
                self._admit_wave(list(zip(ephemeral, prefixes)), buf_len,
                                 admission)
                outs = block(subs, ephemeral)
            finally:
                for uid in ephemeral:
                    if uid in self._sessions:
                        self.release(uid)
            return outs
        self._ensure_pool(buf_len)
        new = []
        for uid, pre in zip(uids, prefixes):
            pre = np.asarray(pre, np.int32)
            if uid not in self._sessions:
                new.append((uid, pre))
            else:
                sess = self._sessions[uid]
                assert int(pre[-1]) == sess.pending, (
                    f"uid {uid}: prefix tail {int(pre[-1])} != cached "
                    f"pending {sess.pending}")
        self._admit_wave(new, buf_len, admission)
        return block(subs, uids)

    def gen_block(self, key: jax.Array, prefix: np.ndarray, buf_len: int,
                  uid=None, fused: bool = False):
        """Single-request speculative block (the R=1 case of gen_blocks)."""
        uids = None if uid is None else [uid]
        return self.gen_blocks([key], [np.asarray(prefix, np.int32)],
                               buf_len, uids=uids, fused=fused)[0]

    # -- public API ---------------------------------------------------------
    def generate(self, key: jax.Array, prompt: np.ndarray,
                 max_new: Optional[int] = None,
                 fused: bool = False) -> GenerationStats:
        cfg = self.cfg
        max_new = max_new or cfg.max_new_tokens
        prompt = np.asarray(prompt, np.int32)
        buf = len(prompt) + max_new + cfg.draft_len + 2
        uid = object()   # private session, never collides with scheduler ids
        self._admit_wave([(uid, prompt)], buf)
        block = self._block_fused if fused else self._block_cached
        out = []
        blocks = 0
        accepted_total = 0
        syncs = 0
        try:
            while len(out) < max_new:
                # Same key derivation as the reference engine so both
                # engines see identical shared uniforms (exact-match
                # testable).
                key, sub = jax.random.split(key)
                o = block([sub], [uid])[0]
                out.extend(o.new_tokens)
                accepted_total += o.accepted
                syncs += o.verify_syncs
                blocks += 1
        finally:
            self.release(uid)
        return GenerationStats(output=np.asarray(out[:max_new], np.int32),
                               blocks=blocks, accepted_drafts=accepted_total,
                               host_syncs=syncs)
