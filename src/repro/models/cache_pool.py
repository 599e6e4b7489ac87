"""Slot-based KV-cache arena for multi-request cached serving.

One pool holds the persistent decode state for up to ``num_slots`` live
requests at once, for every model that participates in a serving step
(speculative decoding needs two: target and drafter).  Each request owns
one *slot* = ``rows_per_slot`` consecutive batch rows of a shared
``(layers, num_slots * rows_per_slot, kv_heads, buf_len, head_dim)``
cache — for spec-dec the rows are the K draft lanes.  All live requests
then advance in ONE ``decode_step_slots`` / ``verify_step_slots`` call
over the whole arena; free slots ride along as dead rows (their garbage
is never attended by other rows and is fully overwritten at the next
admission's prefill scatter).

Lifecycle contract (DESIGN.md §7):

  * ``alloc``/``release`` at request admission/completion; allocation is
    lowest-free-slot first, so a given request trace maps to slots
    deterministically;
  * per-slot positions are tracked HOST-side (``pool.pos``) — reading a
    position never costs a device sync, and the model-call API takes
    positions as an argument instead of carrying them in the cache dict;
  * per-slot rollback is row replication: after block verification the
    surviving draft row's cache is broadcast across the slot's rows (one
    arena-wide gather for all slots at once, ``rollback_rows``);
  * ``ensure_buf`` grows every arena to a longer buffer (zero-padded on
    the time axis) when a larger request is admitted; buffer length only
    ever grows, mirroring the scheduler's monotone buffer policy.

int8 arenas (DESIGN.md §11): ``quant=True`` stores each arena as four
leaves — int8 ``k``/``v`` plus f32 per-KV-vector scales ``k_s``/``v_s``
with a trailing singleton axis, so every arena op here (row gather /
scatter on axis 1, time growth on axis 3) applies uniformly to all
leaves.  The slots model calls quantize on write and dequantize inside
the attention reads, admission prefill included.

Positions live in TWO places (DESIGN.md §8): the host mirror
(``pool.pos``) is authoritative for admission/allocation and sizing
decisions, and a lazily materialized device copy (``pos_device()``)
feeds the fused round program, which advances positions in-program and
hands back the updated array (``adopt_round_device``).  Host-side
lifecycle writes (alloc/release/prefill) update the device copy
PER SLOT (``_touch_pos`` — one ``.at[slot].set`` element write), so
admitting or releasing one request never re-uploads every live slot's
positions; the fused round refreshes the host mirror for the slots it
advanced from its packed result (``refresh_pos_host``), so the two
views never drift.
"""

from __future__ import annotations

import heapq
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import paged as P
from repro.models.config import ModelConfig
from repro.models.registry import init_cache


@jax.jit
def _gather_rows(leaf, idx):
    return jnp.take(leaf, idx, axis=1)


@jax.jit
def _grow_time(new_leaf, old_leaf):
    t_old = old_leaf.shape[3]
    return jax.lax.dynamic_update_slice_in_dim(
        new_leaf, old_leaf, 0, axis=3) if t_old else new_leaf


class CachePool:
    """Multi-model slot arena; see module docstring for the contract."""

    def __init__(self, cfgs: Dict[str, ModelConfig], num_slots: int,
                 rows_per_slot: int, buf_len: int, quant: bool = False,
                 mesh=None):
        assert num_slots >= 1 and rows_per_slot >= 1
        for cfg in cfgs.values():
            assert not cfg.sliding_window, \
                "CachePool: non-ring (full-attention) caches only"
        self.cfgs = dict(cfgs)
        self.num_slots = num_slots
        self.rows_per_slot = rows_per_slot
        self.buf_len = buf_len
        self.quant = quant
        self.mesh = mesh
        self.caches = {name: self._init_arena(cfg, buf_len)
                       for name, cfg in self.cfgs.items()}
        # Host-side per-slot decode position (== tokens whose KV is live).
        self.pos = np.zeros(num_slots, np.int64)
        # Device copy of ``pos`` for the fused round program; rebuilt
        # lazily after any host-side position write (DESIGN.md §8).
        self._pos_dev = None
        self._free = list(range(num_slots))

    def _init_arena(self, cfg: ModelConfig, buf_len: int) -> dict:
        c = jax.eval_shape(lambda: init_cache(
            cfg, self.num_slots * self.rows_per_slot, buf_len))
        kv = c["k"].shape
        # Positions live host-side.  Quant arenas: int8 leaves plus
        # per-KV-vector f32 scales (trailing-1 axis).
        leaves = ({"k": (kv, jnp.int8), "v": (kv, jnp.int8),
                   "k_s": (kv[:-1] + (1,), jnp.float32),
                   "v_s": (kv[:-1] + (1,), jnp.float32)} if self.quant
                  else {"k": (kv, c["k"].dtype), "v": (kv, c["v"].dtype)})
        # Made in place on a serving-TP mesh: no device ever holds the
        # whole arena.
        return {kk: jnp.zeros(shape, dtype, device=self._sharding(shape))
                for kk, (shape, dtype) in leaves.items()}

    def _sharding(self, shape):
        """Head-sharding of an arena leaf over a serving-TP mesh
        (DESIGN.md §15): axis 2 of every ``(layers, rows, kv_heads, T,
        head_dim)`` leaf lands on the mesh's "model" axis, matching the
        column-sharded wk/wv so each device owns exactly the KV its heads
        read/write.  Correctness never depends on placement (the fused
        round's shard_map re-shards its inputs); pinning it at
        init/growth keeps the steady state free of re-shard transfers.
        None off-mesh."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding
        from repro.sharding.rules import serve_cache_pspec
        tp = int(self.mesh.shape["model"])
        spec = serve_cache_pspec(len(shape)) if shape[2] % tp == 0 \
            else serve_cache_pspec(0)
        return NamedSharding(self.mesh, spec)

    def _place(self, arena: dict) -> dict:
        """Pin every leaf of ``arena`` to its ``_sharding`` (no-op
        off-mesh)."""
        if self.mesh is None:
            return arena
        return {kk: jax.device_put(leaf, self._sharding(leaf.shape))
                for kk, leaf in arena.items()}

    # -- slot lifecycle ----------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError(
                f"CachePool: all {self.num_slots} slots in use")
        slot = min(self._free)
        self._free.remove(slot)
        self.pos[slot] = 0
        self._touch_pos(slot)
        return slot

    def release(self, slot: int) -> None:
        assert 0 <= slot < self.num_slots and slot not in self._free
        self.pos[slot] = 0
        self._touch_pos(slot)
        self._free.append(slot)

    def set_pos(self, slot: int, pos: int) -> None:
        """Record a slot's new decode position (host mirror + per-slot
        device touch) — the host-driven round's position write."""
        self.pos[slot] = int(pos)
        self._touch_pos(slot)

    def rows_of(self, slot: int) -> np.ndarray:
        r = self.rows_per_slot
        return np.arange(slot * r, (slot + 1) * r)

    # -- buffer growth -----------------------------------------------------
    def ensure_buf(self, buf_len: int) -> None:
        """Grow every arena's time axis to at least ``buf_len``.  Existing
        KV content (all live positions) is preserved; new tail is zero."""
        if buf_len <= self.buf_len:
            return
        for name, cfg in self.cfgs.items():
            fresh = self._init_arena(cfg, buf_len)
            old = self.caches[name]
            self.caches[name] = self._place(
                {kk: _grow_time(fresh[kk], old[kk]) for kk in fresh})
        self.buf_len = buf_len

    # -- cache content ops -------------------------------------------------
    def update(self, name: str, cache: dict) -> None:
        """Adopt the arena returned by a slots model call."""
        self.caches[name] = {kk: cache[kk] for kk in self.caches[name]}

    def rollback_rows(self, row_src: np.ndarray) -> None:
        """Arena-wide row replication: row i of every cache becomes row
        ``row_src[i]``.  Callers build ``row_src`` so each rolled-back
        slot's rows all point at its surviving row and every other row
        points at itself."""
        assert row_src.shape == (self.num_slots * self.rows_per_slot,)
        idx = jnp.asarray(row_src, jnp.int32)
        for name, arena in self.caches.items():
            self.caches[name] = {kk: _gather_rows(arena[kk], idx)
                                 for kk in arena}

    # -- fused-round device state (DESIGN.md §8) ---------------------------
    def _touch_pos(self, slot: int) -> None:
        """Per-slot device-position update after a host lifecycle write:
        one ``.at[slot].set`` element write instead of invalidating (and
        re-uploading) the whole position array.  No-op while the device
        copy has never been materialized."""
        if self._pos_dev is not None:
            self._pos_dev = self._pos_dev.at[slot].set(
                jnp.int32(int(self.pos[slot])))

    def pos_device(self) -> jax.Array:
        """(num_slots,) i32 device positions for the fused round program.
        Materialized from the host mirror once; afterwards the array
        handed back by the previous round (plus per-slot lifecycle
        touches) is reused, so the steady-state round uploads nothing."""
        if self._pos_dev is None:
            self._pos_dev = jnp.asarray(self.pos, jnp.int32)
        return self._pos_dev

    def adopt_round_device(self, caches: Dict[str, dict],
                           pos_dev: jax.Array) -> None:
        """Adopt a fused round program's DEVICE outputs: the per-model
        {k, v} arenas (the donated input buffers are dead — callers must
        never touch them again) and the advanced device positions.
        Deliberately host-async: callers may dispatch more device work
        (admission prefills, §9) against the adopted arrays before the
        round's packed result is fetched; the host mirror stays stale
        for the advanced slots until ``refresh_pos_host``."""
        assert set(caches) == set(self.caches)
        for name, c in caches.items():
            self.caches[name] = {kk: c[kk] for kk in self.caches[name]}
        self._pos_dev = pos_dev

    def adopt_pos_device(self, pos_dev: jax.Array) -> None:
        """Adopt ONLY a fused round's advanced device positions.  Used
        when the round's KV lives in a caller-held contiguous view
        rather than in pool storage (the paged kv_fused path, §12):
        positions still flow through the pool's device mirror, storage
        syncs separately at view-commit events."""
        self._pos_dev = pos_dev

    def refresh_pos_host(self, pos_host: np.ndarray, slots) -> None:
        """Refresh the host position mirror for ``slots`` from a fused
        round's packed result.  Only the slots the round advanced are
        written — slots admitted while the round ran already hold their
        post-prefill positions host-side, and the round's packed ``pos``
        (snapshotted at dispatch) would clobber them."""
        for s in slots:
            self.pos[s] = int(pos_host[s])

    def row_positions(self, default: int = 0) -> np.ndarray:
        """(num_slots * rows_per_slot,) per-row positions for the slots
        model calls; free slots get ``default``."""
        per_slot = self.pos.copy()
        for s in self._free:
            per_slot[s] = default
        return np.repeat(per_slot, self.rows_per_slot).astype(np.int32)

    # -- durable-state observability (DESIGN.md §14) -----------------------
    def residency(self) -> dict:
        """Arena residency for the round journal: slot occupancy, the
        pinned buffer length, and per-slot live positions.  Pure host
        bookkeeping (no device sync) — journal appends must not add a
        fetch to the round hot path."""
        return {"slots_used": self.num_slots - len(self._free),
                "slots_total": self.num_slots,
                "buf_len": int(self.buf_len),
                "pos": [int(p) for p in self.pos]}

    # -- fault recovery (DESIGN.md §13) ------------------------------------
    def drop_device_mirrors(self) -> None:
        """Invalidate the lazily-materialized device mirrors after a
        guarded fault discarded a round mid-flight.  The host views
        (``pos``, and the page table in the paged pool) are
        authoritative and re-upload on next use, so device state that
        adopted an aborted round's in-flight outputs can never leak
        into the replay."""
        self._pos_dev = None

    def scrub(self) -> None:
        """Zero every arena — the NaN-poisoning recovery (DESIGN.md
        §13).  Finite garbage in dead arena regions is masked out of
        every read (the §7/§12 dead-row argument), but NaN/Inf garbage
        is NOT: a masked attention weight of 0.0 against a NaN value
        still contributes ``0 * NaN = NaN`` to the output sum, so
        possibly-poisoned storage must be rebuilt, not reused.  Callers
        displace every session first — all slots must be free."""
        assert len(self._free) == self.num_slots, \
            "scrub with occupied slots; displace sessions first"
        self.caches = {name: self._init_arena(cfg, self.buf_len)
                       for name, cfg in self.cfgs.items()}
        self.pos[:] = 0
        self._pos_dev = None


@jax.jit
def _grow_pages_leaf(new_leaf, old_leaf):
    return jax.lax.dynamic_update_slice_in_dim(new_leaf, old_leaf, 0, axis=1)


class PagePoolExhausted(RuntimeError):
    """A fixed-budget paged pool ran out of physical pages.  The
    scheduler's v2 policy treats this as its eviction signal boundary —
    it reserves conservatively ahead of every round, so hitting this
    means the caller's accounting is wrong, not that eviction is due."""


class PagedCachePool(CachePool):
    """Paged slot arena (DESIGN.md §12): same lifecycle contract and
    model-facing semantics as ``CachePool``, but each model's KV lives
    in fixed-size physical pages ``(layers, num_pages + 1, kv_heads,
    page_size, head_dim)`` behind ONE page table ``(rows, n_lp)`` shared
    by every model (positions are shared, so all models' chains advance
    in lockstep; physical page index ``p`` names page ``p`` in every
    model's storage at once).  Physical page 0 is a permanent zero page
    and table entry 0 means unmapped — see models/paged.py for the
    gather/scatter semantics that make dead rows and reused (garbage)
    pages token-invisible.

    Differences from the contiguous pool:

      * ``ensure_buf`` is a table WIDENING (append unmapped columns) —
        no storage copy, no whole-pool zero-pad regrowth;
      * storage is reserved per slot as its chain grows (``reserve``;
        admission reserves for the prompt, engines reserve
        ``pos + L + 1`` before each round), so a free slot holds zero
        pages and a fixed ``num_pages`` budget can oversubscribe slots
        (more queued requests than physical capacity) — exhausting a
        fixed budget raises ``PagePoolExhausted``; with ``num_pages=
        None`` the pool starts at full contiguous-equivalent capacity
        and doubles on demand;
      * model calls run the ``*_slots_paged`` entry points (pages +
        device table) instead of taking ``pool.caches`` — this class
        deliberately does NOT define ``caches``, so contiguous-only
        code paths fail loudly;
      * rollback replicates chain CONTENT page-by-page through the
        table (``models/paged.replicate_rows``) — rows keep their own
        physical pages.
    """

    def __init__(self, cfgs: Dict[str, ModelConfig], num_slots: int,
                 rows_per_slot: int, buf_len: int, quant: bool = False,
                 page_size: int = 64, num_pages: Optional[int] = None,
                 mesh=None):
        assert num_slots >= 1 and rows_per_slot >= 1 and page_size >= 1
        for cfg in cfgs.values():
            assert not cfg.sliding_window, \
                "PagedCachePool: non-ring (full-attention) caches only"
        self.cfgs = dict(cfgs)
        self.num_slots = num_slots
        self.rows_per_slot = rows_per_slot
        self.buf_len = buf_len
        self.quant = quant
        self.mesh = mesh
        self.page_size = page_size
        self.n_lp = P.n_logical_pages(buf_len, page_size)
        rows = num_slots * rows_per_slot
        self.fixed_budget = num_pages is not None
        self.num_pages = num_pages if self.fixed_budget \
            else rows * self.n_lp
        assert self.num_pages >= 1
        self.pages = {name: self._init_pages(cfg, self.num_pages)
                      for name, cfg in self.cfgs.items()}
        # Shared page table: host-authoritative, device mirror lazy
        # (same two-view discipline as positions, DESIGN.md §8).
        self.page_table = np.zeros((rows, self.n_lp), np.int32)
        self._pt_dev = None
        self._free_pages = list(range(1, self.num_pages + 1))
        heapq.heapify(self._free_pages)       # lowest-free-page first
        self._chain_len = np.zeros(num_slots, np.int64)
        self.pos = np.zeros(num_slots, np.int64)
        self._pos_dev = None
        self._free = list(range(num_slots))

    def _init_pages(self, cfg: ModelConfig, num_pages: int) -> dict:
        c = init_cache(cfg, 1, self.page_size)
        shape = (c["k"].shape[0], num_pages + 1) + c["k"].shape[2:]
        pages = {"k": jnp.zeros(shape, c["k"].dtype),
                 "v": jnp.zeros(shape, c["v"].dtype)}
        if self.quant:
            sshape = shape[:-1] + (1,)
            pages = {"k": jnp.zeros(shape, jnp.int8),
                     "v": jnp.zeros(shape, jnp.int8),
                     "k_s": jnp.zeros(sshape, jnp.float32),
                     "v_s": jnp.zeros(sshape, jnp.float32)}
        # Page storage shares the arena layout's heads axis (index 2),
        # so the contiguous pool's placement applies unchanged.
        return self._place(pages)

    # -- page allocation ---------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    def chain_pages(self, n_tokens: int) -> int:
        """Pages ONE row needs to cover ``n_tokens`` positions."""
        return P.n_logical_pages(max(int(n_tokens), 0), self.page_size)

    def held_pages(self, slot: int) -> int:
        """Physical pages currently owned by ``slot`` (all its rows)."""
        return int(self._chain_len[slot]) * self.rows_per_slot

    def reserve(self, slot: int, n_tokens: int) -> None:
        """Extend ``slot``'s chains (every row in lockstep) to cover
        ``n_tokens`` logical positions.  Never shrinks.  Raises
        ``PagePoolExhausted`` on a fixed budget (before mutating
        anything); auto-grow pools double their storage instead."""
        need_lp = self.chain_pages(n_tokens)
        assert need_lp <= self.n_lp, (
            f"reserve({n_tokens}) needs {need_lp} logical pages but the "
            f"table holds {self.n_lp}; grow buf_len first (ensure_buf)")
        have = int(self._chain_len[slot])
        if need_lp <= have:
            return
        want = (need_lp - have) * self.rows_per_slot
        if want > len(self._free_pages):
            if self.fixed_budget:
                raise PagePoolExhausted(
                    f"slot {slot} needs {want} pages, "
                    f"{len(self._free_pages)}/{self.num_pages} free")
            self._grow_pages(want - len(self._free_pages))
        r0 = slot * self.rows_per_slot
        for lp in range(have, need_lp):
            for r in range(r0, r0 + self.rows_per_slot):
                self.page_table[r, lp] = heapq.heappop(self._free_pages)
        self._chain_len[slot] = need_lp
        self._touch_table(slot)

    def _grow_pages(self, min_extra: int) -> None:
        """Auto-grow storage: at least double (amortized O(1) copies),
        at least ``min_extra`` new pages.  Page indices are stable, so
        the table is untouched."""
        new_total = max(self.num_pages * 2, self.num_pages + min_extra)
        for name, cfg in self.cfgs.items():
            fresh = self._init_pages(cfg, new_total)
            old = self.pages[name]
            self.pages[name] = self._place(
                {kk: _grow_pages_leaf(fresh[kk], old[kk]) for kk in fresh})
        self._free_pages.extend(range(self.num_pages + 1, new_total + 1))
        heapq.heapify(self._free_pages)
        self.num_pages = new_total

    def release(self, slot: int) -> None:
        """Free the slot AND its pages.  Clearing the slot's table rows
        is what keeps its dead rows harmless: their in-round garbage
        writes redirect through unmapped entries and DROP, so a freed
        page reallocated to another request can never be corrupted by
        the releasing slot riding along in a later round."""
        r0 = slot * self.rows_per_slot
        r1 = r0 + self.rows_per_slot
        for pg in self.page_table[r0:r1].reshape(-1):
            if pg > 0:
                heapq.heappush(self._free_pages, int(pg))
        self.page_table[r0:r1] = 0
        self._chain_len[slot] = 0
        self._touch_table(slot)
        super().release(slot)

    # -- suspend / resume (DESIGN.md §12): pages without a slot ------------
    def detach(self, slot: int) -> dict:
        """Suspend a slot's request: free the SLOT but keep its PAGES.
        Returns a handle owning the chains; ``attach`` later re-binds
        them to any free slot — a host table rewrite, no KV copy and no
        recompute — and ``release_handle`` forfeits them.  Detached
        pages are in neither the free heap (no other slot can claim
        them) nor the table (no round can write them): the bytes the
        handle owns are exactly the bytes the request left behind."""
        r0 = slot * self.rows_per_slot
        r1 = r0 + self.rows_per_slot
        handle = {"chains": self.page_table[r0:r1].copy(),
                  "chain_len": int(self._chain_len[slot]),
                  "pos": int(self.pos[slot])}
        self.page_table[r0:r1] = 0
        self._chain_len[slot] = 0
        self._touch_table(slot)
        super().release(slot)
        return handle

    def attach(self, slot: int, handle: dict) -> None:
        """Re-bind a detached handle's chains to ``slot``.  The table
        may have WIDENED since detach (``ensure_buf``); the extra
        columns stay unmapped, same as any short chain."""
        r0 = slot * self.rows_per_slot
        r1 = r0 + self.rows_per_slot
        chains = handle["chains"]
        assert chains.shape[0] == self.rows_per_slot
        assert chains.shape[1] <= self.n_lp
        assert not self.page_table[r0:r1].any()
        self.page_table[r0:r1, :chains.shape[1]] = chains
        self._chain_len[slot] = int(handle["chain_len"])
        self._touch_table(slot)
        self.set_pos(slot, int(handle["pos"]))

    def release_handle(self, handle: dict) -> None:
        """Forfeit a suspended request's pages (demotion to a hard
        eviction — re-admission goes back through re-prefill)."""
        for pg in handle["chains"].reshape(-1):
            if pg > 0:
                heapq.heappush(self._free_pages, int(pg))
        handle["chains"] = np.zeros_like(handle["chains"])
        handle["chain_len"] = 0

    # -- device table mirror -----------------------------------------------
    def _touch_table(self, slot: int) -> None:
        """Per-slot device-table update after a host-side chain change
        (reserve/release) — one row-range write, not a full re-upload."""
        if self._pt_dev is not None:
            r0 = slot * self.rows_per_slot
            r1 = r0 + self.rows_per_slot
            self._pt_dev = self._pt_dev.at[r0:r1].set(
                jnp.asarray(self.page_table[r0:r1]))

    def pt_device(self) -> jax.Array:
        """(rows, n_lp) i32 device page table for the paged model calls;
        lazily materialized from the host mirror, then maintained by
        per-slot touches."""
        if self._pt_dev is None:
            self._pt_dev = jnp.asarray(self.page_table)
        return self._pt_dev

    # -- buffer growth: table widening, NOT a storage copy -----------------
    def ensure_buf(self, buf_len: int) -> None:
        if buf_len <= self.buf_len:
            return
        new_lp = P.n_logical_pages(buf_len, self.page_size)
        if new_lp > self.n_lp:
            rows = self.num_slots * self.rows_per_slot
            pad = np.zeros((rows, new_lp - self.n_lp), np.int32)
            self.page_table = np.concatenate([self.page_table, pad], axis=1)
            self.n_lp = new_lp
            self._pt_dev = None        # shape changed; re-upload lazily
        self.buf_len = buf_len

    # -- cache content ops -------------------------------------------------
    def update(self, name: str, pages: dict) -> None:
        """Adopt the pages returned by a ``*_slots_paged`` model call."""
        self.pages[name] = {kk: pages[kk] for kk in self.pages[name]}

    def rollback_rows(self, row_src: np.ndarray) -> None:
        assert row_src.shape == (self.num_slots * self.rows_per_slot,)
        idx = jnp.asarray(row_src, jnp.int32)
        pt = self.pt_device()
        for name in self.pages:
            self.pages[name] = P.replicate_rows_jit(
                self.pages[name], pt, idx)

    def adopt_round_device(self, pages: Dict[str, dict],
                           pos_dev: jax.Array) -> None:
        """Adopt a paged fused round's DEVICE outputs (per-model page
        storage + advanced positions); same host-async contract as the
        contiguous pool's ``adopt_round_device``."""
        assert set(pages) == set(self.pages)
        for name, pg in pages.items():
            self.pages[name] = {kk: pg[kk] for kk in self.pages[name]}
        self._pos_dev = pos_dev

    def materialize(self, name: str) -> dict:
        """Gather one model's full contiguous arena view (tests and
        debugging only — the serving paths never materialize this)."""
        return P.gather_arena_jit(self.pages[name], self.pt_device(),
                                  buf_len=self.buf_len)

    # -- durable-state observability (DESIGN.md §14) -----------------------
    def residency(self) -> dict:
        """Contiguous residency plus the page ledger: pages in use (held
        by slots AND by detached suspend handles — both are spoken for)
        and the host table's mapped-entry count."""
        out = super().residency()
        out.update({"pages_used": self.num_pages - len(self._free_pages),
                    "pages_total": self.num_pages,
                    "table_mapped": P.table_occupancy(self.page_table)})
        return out

    # -- fault recovery (DESIGN.md §13) ------------------------------------
    def drop_device_mirrors(self) -> None:
        super().drop_device_mirrors()
        self._pt_dev = None

    def scrub(self) -> None:
        """Zero page storage (see ``CachePool.scrub``).  All slots must
        be free AND all pages returned — a suspend handle's detached
        pages are invisible to the pool, so callers strip outstanding
        handles first (their bytes may be poisoned too)."""
        assert len(self._free) == self.num_slots, \
            "scrub with occupied slots; displace sessions first"
        assert len(self._free_pages) == self.num_pages, \
            "scrub with pages still held; strip suspend handles first"
        assert not self.page_table.any()
        self.pages = {name: self._init_pages(cfg, self.num_pages)
                      for name, cfg in self.cfgs.items()}
        self.pos[:] = 0
        self._pos_dev = None
        self._pt_dev = None
