"""Batched request scheduler for speculative-decoding serving.

A minimal continuous-batching-lite scheduler: requests join a queue, up
to ``max_batch`` live requests advance one speculative block per round
(each with its own RNG stream), finished requests leave and queued ones
join at round boundaries.  Tracks the serving metrics a deployment would
export: time-to-first-block, tokens/s, block efficiency, acceptance
rate, host-sync counts.

All execution modes share one policy (admission order, RNG derivation,
buffer sizing), so their outputs are bit-identical:

  * sequential (``batched=False``): one engine block per live request per
    round — R target forwards per round;
  * batched (``batched=True``): all live requests' draft buffers stack
    into (R*K, T) model calls via ``SpecDecEngine.gen_blocks`` — ONE
    target forward per round regardless of R;
  * kv (``cache_mode="kv"``): a ``CachedSpecDecEngine`` keeps every live
    request's target and drafter caches resident in a slot-based cache
    pool across rounds (admit on first block, release on completion) —
    one drafter decode sweep plus ONE stacked ``verify_step`` per round,
    no per-block re-prefill (DESIGN.md §7).  The first two modes
    re-score the whole prefix every block, O(T^2) per request;
  * kv_fused (``cache_mode="kv_fused"``): same engine and pool, but the
    whole round — drafter sweep, stacked verify, Algorithm-2
    verification, rollback, catch-up — runs as ONE jitted device
    program (DESIGN.md §8): no per-draft-step host transfer
    (``draft_syncs == 0``) and exactly one host sync per round.

RNG streams are derived per request as
``fold_in(fold_in(key, uid), blocks)`` — NESTED folds, because the
flat ``fold_in(key, uid * 1000 + blocks)`` encoding collides across
requests once a request reaches 1000 blocks (uid 1 block 1000 == uid 2
block 0), silently coupling two requests' draws.  ``run()`` feeds the
SAME key to every round, so a request's stream depends only on
(uid, blocks), never on WHICH round a block lands in — that round-
independence is what lets kv_fused defer a newly admitted request's
first block to the round after its overlapped prefill (DESIGN.md §9)
while staying bit-identical to the modes that run it immediately.
(The former per-round ``fold_in(key, round_idx)`` would have tied
every block's randomness to the admission policy.)

Admission (``admission="bucketed"``, the default) drains the queue
into the engine's bucketed batched-prefill waves; under kv_fused the
wave's prefills are dispatched while the current round runs and the
admitted requests join the live set next round.  ``per_request`` admits
one request per wave (the TTFT baseline in the bursty-admission bench).

Buffer lengths grow monotonically to the largest live requirement
(queued requests count from their admission round), so a request's
compiled shapes — and therefore its sampled tokens — never depend on
which mode ran it (trailing-buffer content does not affect causal
logits, but buffer LENGTH changes compiled reduction shapes, so it is
pinned scheduler-side).
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Callable, Optional

import jax
import numpy as np

from repro.models.cache_pool import PagePoolExhausted
from repro.serving.admission import (
    CircuitBreaker,
    Overloaded,
    TokenRateLimiter,
    pick_shed_victim,
)
from repro.serving.faults import (
    FAULT_KINDS,
    FaultPlan,
    InjectedFault,
    poison_outcome,
)
from repro.serving.guard import (
    GuardViolation,
    InvalidRequest,
    RoundWatchdog,
    WatchdogTimeout,
    validate_outcome,
    validate_prompt,
)
from repro.serving.journal import RoundJournal, read_journal
from repro.serving.snapshot import (
    capture_server,
    journal_path,
    read_snapshot,
    snapshot_path,
    unpack_request,
    write_snapshot,
)
from repro.specdec.engine import SpecDecConfig, SpecDecEngine


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray
    max_new: int
    # v2 policy inputs (DESIGN.md §12): higher priority admits first and
    # is never evicted for a lower-priority candidate; ``on_token``
    # streams tokens as their round commits instead of at completion.
    priority: int = 0
    on_token: Optional[Callable] = None
    # runtime state
    output: list = dataclasses.field(default_factory=list)
    blocks: int = 0
    accepted: int = 0
    t_submit: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    # Honest eviction accounting: ``t_submit`` is never reset, so TTFT
    # and wall_s keep covering time spent evicted; ``evicted_s`` breaks
    # out how much of that wall a request spent OUT of the live set
    # after having been admitted at least once, and ``token_times``
    # (one wall-clock stamp per emitted token, shared with the
    # ``on_token`` callback order) makes inter-token gaps — including
    # the gap spanning an eviction — directly measurable.
    evictions: int = 0
    evicted_s: float = 0.0
    token_times: list = dataclasses.field(default_factory=list)
    tokens_since_admit: int = 0
    t_admit: Optional[float] = None
    _t_evict: Optional[float] = None
    # Suspend handle (paged engines): a preempted request keeps its KV
    # pages here and resumes by table re-attach — no re-prefill.  Page
    # pressure may strip the handle (``drop_handle``), demoting it to
    # an ordinary evicted request that re-prefills on re-admission.
    _kv_handle: Optional[dict] = None
    # Fault accounting (DESIGN.md §13): ``retries`` counts rounds this
    # request was displaced from by an ATTRIBUTED fault — a separate
    # counter from ``evictions`` so fault replay never perturbs the v2
    # admission rank.  Past the retry budget the request quarantines:
    # ``error`` is set and it moves to ``server.failed``.
    retries: int = 0
    error: Optional[str] = None
    # Overload protection (DESIGN.md §14): absolute wall-clock deadline.
    # An expired request is shed from the QUEUE at admission time (live
    # requests run to completion — their slot is already paid for).
    t_deadline: Optional[float] = None

    @property
    def done(self) -> bool:
        return len(self.output) >= self.max_new

    @property
    def block_efficiency(self) -> float:
        return len(self.output) / max(self.blocks, 1)

    @property
    def ttft_ms(self) -> Optional[float]:
        """Time-to-first-token: submission to first emitted tokens."""
        if self.t_first is None:
            return None
        return (self.t_first - self.t_submit) * 1e3

    @property
    def wall_s(self) -> Optional[float]:
        """Submission to completion — eviction time included."""
        if self.t_done is None:
            return None
        return self.t_done - self.t_submit

    @property
    def itl_ms(self) -> list:
        """Inter-token latencies (ms) between consecutive emitted
        tokens.  Tokens committed by the same round share a timestamp
        (gap 0); the gap that spans an eviction/re-admission cycle
        carries the full evicted time — nothing vanishes."""
        t = self.token_times
        return [(b - a) * 1e3 for a, b in zip(t, t[1:])]


@dataclasses.dataclass
class ServerMetrics:
    completed: int = 0
    total_tokens: int = 0
    total_blocks: int = 0
    rounds: int = 0
    target_forwards: int = 0
    host_syncs: int = 0          # verification device->host transfers
    draft_syncs: int = 0         # draft-token materialization transfers
    evictions: int = 0           # capacity evictions (v2 policy)
    preemptions: int = 0         # max-token fairness preemptions (v2)
    # Wall time is accumulated per ``step()`` call, so ``tokens_per_s``
    # is meaningful whether callers drive ``run()`` or ``step()``
    # directly (``run()`` previously set it; direct ``step()`` callers
    # divided by the 1e-9 floor and reported nonsense).
    wall_s: float = 0.0
    # Fault tolerance (DESIGN.md §13).  Every guarded fault increments
    # exactly one ``faults[kind]`` entry AND ``retries`` (one discarded
    # round each), so ``retries == faults_total`` is a consistency
    # invariant the chaos bench gates on.
    faults: dict = dataclasses.field(default_factory=dict)
    retries: int = 0             # rounds discarded and replayed
    quarantined: int = 0         # requests failed past the retry budget
    watchdog_trips: int = 0      # rounds that overran the timeout
    watchdog_accepts: int = 0    # slow-but-valid rounds kept (anti-livelock)
    callback_errors: int = 0     # on_token callbacks that raised
    degradations: list = dataclasses.field(default_factory=list)
    # Overload protection (DESIGN.md §14).
    rejected: int = 0            # submits refused (queue full / rate / drain)
    shed: int = 0                # queued requests dropped for a higher-
    #                              priority submit under backpressure
    deadline_expired: int = 0    # queued requests past their deadline
    heals: int = 0               # ladder rungs climbed back (circuit breaker)

    @property
    def faults_total(self) -> int:
        return sum(self.faults.values())

    @property
    def tokens_per_s(self) -> float:
        return self.total_tokens / max(self.wall_s, 1e-9)

    @property
    def mean_block_efficiency(self) -> float:
        return self.total_tokens / max(self.total_blocks, 1)


CACHE_MODES = ("reprefill", "kv", "kv_fused")
ADMISSION_MODES = ("bucketed", "per_request")
POLICIES = ("fifo", "v2")


class SpecDecServer:
    """Round-robin block scheduler over a shared engine.

    ``cache_mode="reprefill"`` drives a reference ``SpecDecEngine``
    (stateless; full-prefix re-score per block, sequential or batched);
    ``cache_mode="kv"`` drives a ``CachedSpecDecEngine`` whose cache
    pool must have at least ``max_batch`` slots — requests are admitted
    to a slot at their first block and released on completion, and every
    round is one batched arena step (``batched`` is implied);
    ``cache_mode="kv_fused"`` is the same serving policy with the round
    executed as one fused device program (DESIGN.md §8).

    ``admission`` picks the cached-engine prefill path: "bucketed"
    (default — batched bucketed waves straight into pool slots,
    overlapped with the running round under kv_fused, DESIGN.md §9) or
    "per_request" (one request per wave; the TTFT baseline in the
    bursty-admission bench).  The policy is passed through to the
    engine per call, never written onto it.

    ``policy`` selects the admission/eviction policy (DESIGN.md §12):

      * "fifo" (default): the original behaviour — queue drains in
        submission order up to ``max_batch``, a live request holds its
        slot until completion, no eviction.
      * "v2": continuous batching with eviction and fairness.  Queued
        requests admit in (priority desc, evictions asc, submit order)
        — the evictions term rotates preempted requests behind waiting
        peers of equal priority.  A candidate that cannot fit (batch
        full, or — under a fixed paged KV budget — its worst-case page
        commitment would oversubscribe the pool) may DISPLACE strictly
        lower-priority live requests.  On a paged engine displacement
        SUSPENDS: the victim's KV pages detach into a handle (the slot
        frees, the pages stay resident and unwritable) and re-admission
        is a host table re-attach — no recompute, so preemption costs
        ~nothing.  Page pressure can strip a suspended handle (worst-
        ranked first), demoting the holder to a hard eviction that
        re-admits via chunked re-prefill of prompt+output; non-paged
        engines always take that path.  Both are token-invisible:
        per-request randomness is (uid, blocks)-keyed, resumed pages
        are the same bytes, and re-prefilled KV is bitwise equal to
        the decode-built KV it replaces.  ``preempt_tokens=N``
        additionally preempts any live request that has emitted ≥ N
        tokens since its last admission while others wait — bounding
        tail TTFT under a few long-running requests.

    ``min_buf_len`` pins the starting decode-buffer length.  Buffer
    length changes compiled reduction shapes (module docstring), and
    under v2 WHICH requests are live — and therefore the natural buffer
    growth schedule — depends on wall-clock arrival order; pinning the
    buffer to the trace's maximum requirement makes outputs bit-
    comparable across policies and load patterns.
    """

    def __init__(self, engine, max_batch: int = 8,
                 batched: bool = False, cache_mode: str = "reprefill",
                 admission: str = "bucketed", policy: str = "fifo",
                 preempt_tokens: Optional[int] = None,
                 min_buf_len: int = 0,
                 fault_plan: Optional[FaultPlan] = None,
                 retry_budget: Optional[int] = None,
                 round_timeout_ms: Optional[float] = None,
                 degrade_after: Optional[int] = None,
                 journal_dir: Optional[str] = None,
                 snapshot_every: Optional[int] = None,
                 journal_fsync: bool = True,
                 max_queue: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 rate_limit: Optional[float] = None,
                 heal_after: Optional[int] = None):
        if cache_mode not in CACHE_MODES:
            raise ValueError(f"unknown cache_mode {cache_mode!r}")
        if admission not in ADMISSION_MODES:
            raise ValueError(f"unknown admission mode {admission!r}")
        if policy not in POLICIES:
            raise ValueError(f"unknown policy {policy!r}")
        if policy == "v2" and cache_mode not in ("kv", "kv_fused"):
            raise ValueError(
                "policy='v2' needs cache_mode 'kv' or 'kv_fused' — "
                "eviction releases engine sessions")
        if preempt_tokens is not None:
            if policy != "v2":
                raise ValueError("preempt_tokens needs policy='v2'")
            if preempt_tokens < 1:
                raise ValueError("preempt_tokens must be >= 1")
        if getattr(getattr(engine, "cfg", None), "tp", 1) > 1 \
                and cache_mode != "kv_fused":
            raise ValueError(
                "tp > 1 serves through the sharded fused round "
                "(DESIGN.md §15) — use cache_mode='kv_fused'")
        if cache_mode in ("kv", "kv_fused"):
            if not hasattr(engine, "admit"):
                raise TypeError(
                    f"cache_mode={cache_mode!r} needs a CachedSpecDecEngine")
            if engine.pool_slots < max_batch:
                raise ValueError(
                    f"engine pool has {engine.pool_slots} slots < "
                    f"max_batch={max_batch}")
        self.engine = engine
        self.max_batch = max_batch
        self.batched = batched
        self.cache_mode = cache_mode
        self.admission = admission
        self.policy = policy
        self.preempt_tokens = preempt_tokens
        self.queue: deque = deque()
        self.live: list = []
        self._uid = 0
        self._buf_len = max(0, int(min_buf_len))
        self.metrics = ServerMetrics()
        # Fault tolerance (DESIGN.md §13).  ``guarded`` turns on round
        # recovery; it is implied by passing ANY fault-layer knob, so a
        # server with none of them behaves byte-for-byte like before
        # (faults propagate, the fifo page-exhaustion test stays loud).
        if retry_budget is not None and retry_budget < 0:
            raise ValueError("retry_budget must be >= 0")
        if round_timeout_ms is not None and round_timeout_ms <= 0:
            raise ValueError("round_timeout_ms must be > 0")
        if degrade_after is not None and degrade_after < 1:
            raise ValueError("degrade_after must be >= 1")
        if heal_after is not None and heal_after < 1:
            raise ValueError("heal_after must be >= 1")
        self.fault_plan = fault_plan
        self.guarded = (fault_plan is not None or retry_budget is not None
                        or round_timeout_ms is not None
                        or degrade_after is not None
                        or heal_after is not None)
        self.retry_budget = 2 if retry_budget is None else int(retry_budget)
        self.round_timeout_ms = round_timeout_ms
        self.degrade_after = degrade_after
        # Requests that FAILED (quarantine, callback error, shed,
        # deadline) — disjoint from the completed list ``run()`` returns.
        self.failed: list = []
        self._consec_faults = 0
        self._consec_wd = 0
        # Overload protection (DESIGN.md §14).
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError("deadline_ms must be > 0")
        if rate_limit is not None and rate_limit <= 0:
            raise ValueError("rate_limit must be > 0")
        self.max_queue = max_queue
        self.deadline_ms = deadline_ms
        self.draining = False
        self._limiter = (TokenRateLimiter(rate_limit)
                         if rate_limit is not None else None)
        # Circuit breaker + healing probe for the PR 8 ladder: rungs
        # are no longer permanently sticky when ``heal_after`` is set —
        # after that many consecutive clean committed rounds the server
        # probes the rung back, and a fault during the probation window
        # re-degrades with an exponentially backed-off threshold.
        self._breaker = (CircuitBreaker(heal_after)
                         if heal_after is not None else None)
        self._ladder_taken: list = []
        self._pre_degrade_batched = self.batched
        # Completed requests accumulate here across ``run()``/``step()``
        # calls — the snapshot captures them so a restored server's
        # full per-uid token record is intact.
        self.done: list = []
        # Durable state (DESIGN.md §14): write-ahead round journal +
        # periodic snapshots.  A journal that already holds committed
        # records belongs to a previous server life — appending a fresh
        # uid-space onto it would corrupt replay, so the constructor
        # refuses it; use ``SpecDecServer.restore`` (or clear the dir).
        self.journal_dir = journal_dir
        if snapshot_every is not None:
            if journal_dir is None:
                raise ValueError("snapshot_every needs journal_dir")
            if snapshot_every < 1:
                raise ValueError("snapshot_every must be >= 1")
        self.snapshot_every = snapshot_every
        self._journal: Optional[RoundJournal] = None
        self._rounds_since_snap = 0
        if journal_dir is not None:
            os.makedirs(journal_dir, exist_ok=True)
            jpath = journal_path(journal_dir)
            records, valid_end = read_journal(jpath)
            if records:
                raise ValueError(
                    f"journal at {jpath} already holds {len(records)} "
                    "records — use SpecDecServer.restore() to resume it")
            self._journal = RoundJournal(jpath, fsync=journal_fsync,
                                         truncate_to=valid_end)

    def submit(self, prompt: np.ndarray, max_new: int = 32, *,
               priority: int = 0, on_token: Optional[Callable] = None,
               deadline_ms: Optional[float] = None) -> int:
        """Queue a request.  ``priority`` orders v2 admission (ignored
        under fifo); ``on_token(uid, token)`` is called once per emitted
        token, at the round commit that produced it, in emission
        order.  Malformed inputs (empty prompt, non-integer dtype,
        out-of-vocab ids, ``max_new < 1``) raise ``InvalidRequest``
        HERE, at the API boundary, instead of surfacing as a cryptic
        device-side failure rounds later.

        Overload protection (DESIGN.md §14) also lives HERE, at the
        boundary: a draining server refuses with
        ``Overloaded(reason="draining")``; a token-rate limiter refuses
        with "rate_limited" (charging the request's worst-case token
        work, prompt + max_new); a full queue (``max_queue``) sheds the
        lowest-priority never-advanced queued request to make room for
        a strictly higher-priority submit, else refuses with
        "queue_full".  ``deadline_ms`` (default: the server's
        ``deadline_ms``) sets a wall-clock deadline after which the
        request is shed from the queue instead of admitted."""
        if self.draining:
            self.metrics.rejected += 1
            raise Overloaded("server is draining", reason="draining",
                             draining=True)
        prompt = validate_prompt(prompt, max_new,
                                 getattr(self.engine, "vocab", None))
        if self._limiter is not None \
                and not self._limiter.try_acquire(len(prompt) + int(max_new)):
            self.metrics.rejected += 1
            raise Overloaded(
                f"token rate limit exceeded ({self._limiter.rate}/s)",
                reason="rate_limited")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            victim = pick_shed_victim(self.queue, priority)
            if victim is None:
                self.metrics.rejected += 1
                raise Overloaded(
                    f"queue full ({self.max_queue}) and no lower-priority "
                    "request to shed", reason="queue_full")
            self.queue.remove(victim)
            victim.error = "shed: displaced by a higher-priority submit " \
                           "under backpressure"
            self.failed.append(victim)
            self.metrics.shed += 1
            self._journal_fail(victim, "shed")
        self._uid += 1
        if deadline_ms is None:
            deadline_ms = self.deadline_ms
        now = time.time()
        req = Request(uid=self._uid, prompt=prompt,
                      max_new=int(max_new), priority=priority,
                      on_token=on_token, t_submit=now,
                      t_deadline=(now + deadline_ms / 1e3
                                  if deadline_ms is not None else None))
        # WRITE-AHEAD: the submit record is durable before the request
        # is visible to admission, so a crash can never run a request
        # that replay does not know about.
        if self._journal is not None:
            self._journal.append({
                "t": "submit", "uid": req.uid,
                "prompt": [int(x) for x in prompt],
                "max_new": req.max_new, "priority": req.priority,
                "deadline": req.t_deadline, "ts": req.t_submit})
        self.queue.append(req)
        return req.uid

    # ---- admission / eviction policy ---------------------------------

    @staticmethod
    def _order(req: Request, evictions: Optional[int] = None):
        """v2 queue order: priority first, then rotate evicted/preempted
        requests behind same-priority waiters, then earliest deadline
        first (EDF — deadline-free requests sort last, so deadline-free
        traffic keeps the exact pre-§14 order), then submission order.
        ``evictions`` overrides the request's own count (the rank it
        would take after one more displacement)."""
        if evictions is None:
            evictions = req.evictions
        return (-req.priority, evictions,
                req.t_deadline if req.t_deadline is not None else np.inf,
                req.t_submit, req.uid)

    def _mark_admitted(self, req: Request, now: float) -> None:
        if req._t_evict is not None:
            req.evicted_s += now - req._t_evict
            req._t_evict = None
        req.t_admit = now
        req.tokens_since_admit = 0

    def _evict(self, req: Request, now: float) -> None:
        """Displace ``req`` from the live set and requeue it.  On a
        paged engine this SUSPENDS: the request's KV pages detach into
        a handle (``Request._kv_handle``) and re-admission is a table
        re-attach — no recompute.  Otherwise (or after the handle is
        stripped under page pressure) the session is released outright
        and re-admission re-prefills prompt+output, which rebuilds KV
        bitwise equal to the state just dropped — either way the
        displacement is token-invisible (DESIGN.md §12)."""
        self.live.remove(req)
        if self.engine.has_session(req.uid):
            if getattr(self.engine, "can_suspend", lambda: False)():
                req._kv_handle = self.engine.suspend(req.uid)
            else:
                self.engine.evict(req.uid)
        req.evictions += 1
        req._t_evict = now
        self.queue.append(req)

    def _lifetime_pages(self, req: Request) -> int:
        """Worst-case page commitment: the pages ``req`` will hold once
        fully decoded.  Admission against lifetime commitments (not
        current holdings) guarantees mid-round ``reserve`` can never
        exhaust a fixed page budget."""
        return self.engine.request_pages(len(req.prompt) + req.max_new)

    def _pick_victim(self, below_priority: int, protect: set):
        """Lowest-priority live request strictly below
        ``below_priority`` (never admitted this step), shortest prefix
        first — the cheapest re-prefill loses its slot."""
        cands = [r for r in self.live
                 if r.priority < below_priority and id(r) not in protect]
        if not cands:
            return None
        return min(cands, key=lambda r: (r.priority,
                                         len(r.prompt) + len(r.output),
                                         r.uid))

    def _admit_v2(self, now: float) -> list:
        page_state = self.engine.page_state()
        fixed = bool(page_state and page_state.get("fixed"))
        newly: list = []
        protect: set = set()
        while self.queue:
            cand = min(self.queue, key=self._order)
            blocked_by_pages = False
            if fixed:
                # Pages spoken for: live requests count their LIFETIME
                # commitment (they grow every round, worst case to full
                # decode); suspended queue entries count their handle's
                # actual holdings (detached chains never grow — growth
                # re-enters through this same check at resume, when the
                # resumed request's lifetime is charged as ``need``).
                committed = sum(self._lifetime_pages(r) for r in self.live)
                committed += sum(self.engine.handle_pages(q._kv_handle)
                                 for q in self.queue
                                 if q._kv_handle is not None and q is not cand)
                need = self._lifetime_pages(cand)
                if need > page_state["total"]:
                    raise ValueError(
                        f"request uid={cand.uid} needs {need} pages but "
                        f"the pool only has {page_state['total']}")
                blocked_by_pages = committed + need > page_state["total"]
            if len(self.live) >= self.max_batch or blocked_by_pages:
                # Page pressure reclaims from suspended holders first:
                # stripping the worst-ranked handle behind ``cand``
                # frees pages without touching the live set (the holder
                # re-admits later via re-prefill).  Handles ranked
                # AHEAD of cand are never stripped — those requests
                # resume before cand anyway.
                if blocked_by_pages and len(self.live) < self.max_batch:
                    holders = [q for q in self.queue
                               if q._kv_handle is not None and q is not cand
                               and self._order(q) > self._order(cand)]
                    if holders:
                        worst = max(holders, key=self._order)
                        self.engine.drop_handle(worst._kv_handle)
                        worst._kv_handle = None
                        self.metrics.evictions += 1
                        continue
                victim = self._pick_victim(cand.priority, protect)
                if victim is None:
                    break
                self._evict(victim, now)
                self.metrics.evictions += 1
                continue
            self.queue.remove(cand)
            self.live.append(cand)
            protect.add(id(cand))
            self._mark_admitted(cand, now)
            if cand._kv_handle is not None:
                # Resume from the suspend handle: session re-binds to a
                # free slot host-side, KV already resident — the request
                # advances THIS round (no prefill to overlap), which is
                # token-invisible because randomness is (uid, blocks)-
                # keyed, never round-keyed.
                self.engine.resume(cand.uid, cand._kv_handle)
                cand._kv_handle = None
            else:
                newly.append(cand)
        return newly

    def _preempt(self, now: float) -> None:
        """Fairness rotation: while requests wait in the queue, evict
        live requests that have emitted ``preempt_tokens`` or more
        tokens since their last admission.  Their incremented eviction
        count sorts them behind same-priority waiters, so slots rotate
        instead of ping-ponging."""
        if not self.preempt_tokens or not self.queue:
            return
        for req in list(self.live):
            if req.done or req.tokens_since_admit < self.preempt_tokens:
                continue
            # Only preempt when some waiter would actually outrank the
            # displaced request in the admission order — otherwise the
            # eviction is pure churn: the same request re-admits
            # immediately and pays a re-prefill for nothing (a high-
            # priority request is never preempted for low-priority
            # waiters).
            displaced = self._order(req, evictions=req.evictions + 1)
            if not any(self._order(q) < displaced for q in self.queue):
                continue
            self._evict(req, now)
            self.metrics.preemptions += 1

    def _expire_deadlines(self, now: float) -> None:
        """Shed queued requests past their deadline (both policies).
        Live requests are exempt — their slot is already paid for and
        they may still finish; the deadline bounds time spent WAITING."""
        for req in [r for r in self.queue
                    if r.t_deadline is not None and now > r.t_deadline]:
            self.queue.remove(req)
            if req._kv_handle is not None:
                self.engine.drop_handle(req._kv_handle)
                req._kv_handle = None
            req.error = "deadline exceeded before admission"
            self.failed.append(req)
            self.metrics.deadline_expired += 1
            self._journal_fail(req, "deadline")

    def _admit(self) -> list:
        """Move queued requests into the live set; returns the newly
        admitted requests."""
        now = time.time()
        self._expire_deadlines(now)
        if self.policy == "v2":
            self._preempt(now)
            return self._admit_v2(now)
        newly = []
        while self.queue and len(self.live) < self.max_batch:
            req = self.queue.popleft()
            self.live.append(req)
            newly.append(req)
            self._mark_admitted(req, now)
        return newly

    def _required_buf(self, req: Request) -> int:
        return len(req.prompt) + req.max_new + self.engine.cfg.draft_len + 2

    # Faults the guarded scheduler recovers from; anything else stays
    # loud.  GuardViolation subclasses AssertionError, but a PLAIN
    # AssertionError (an engine contract bug) is never recoverable.
    _RECOVERABLE = (InjectedFault, PagePoolExhausted, GuardViolation,
                    WatchdogTimeout, MemoryError)

    def _is_recoverable(self, e: BaseException) -> bool:
        if isinstance(e, self._RECOVERABLE):
            return True
        # Real allocator failures surface as XLA RESOURCE_EXHAUSTED.
        return isinstance(e, RuntimeError) and "RESOURCE_EXHAUSTED" in str(e)

    def _vocab(self) -> Optional[int]:
        return getattr(self.engine, "vocab", None)

    def step(self, key: jax.Array) -> list:
        """Advance every live request by one speculative block.  Returns
        requests that finished this round.

        Under kv_fused with bucketed admission, requests admitted THIS
        step only prefill (overlapped with the round advancing the
        previously admitted requests, DESIGN.md §9) and start emitting
        tokens next step.  Round-alignment differences between modes
        are token-invisible because per-request randomness depends only
        on (uid, blocks) — callers comparing admission policies must
        pass the same ``key`` every step, as ``run()`` does.

        On a guarded server (DESIGN.md §13) a recoverable fault makes
        the step return [] after displacing the round's requests; the
        next step replays them bit-identically — ``blocks`` only
        advances at commit, so the re-derived (uid, blocks) stream is
        the same sheet the discarded round drew."""
        t0 = time.perf_counter()
        try:
            newly = self._admit()
            if not self.live:
                return []
            self._buf_len = max([self._buf_len]
                                + [self._required_buf(r)
                                   for r in self.live])
            overlap = (self.cache_mode == "kv_fused"
                       and self.admission == "bucketed")
            new_ids = {id(r) for r in newly}
            advancing = [r for r in self.live if id(r) not in new_ids] \
                if overlap else self.live
            # Nested folds: a flat uid * C + blocks encoding collides
            # across requests once blocks reaches C (module docstring).
            subs = [jax.random.fold_in(jax.random.fold_in(key, r.uid),
                                       r.blocks)
                    for r in advancing]
            fw0 = self.engine.num_target_forwards
            ds0 = getattr(self.engine, "num_draft_syncs", 0)
            try:
                outs = self._dispatch(subs, advancing, newly, overlap)
            except Exception as fault:
                if not (self.guarded and self._is_recoverable(fault)):
                    raise
                self._recover(fault, newly)
                return []
            if advancing:
                self.metrics.rounds += 1
            self.metrics.target_forwards += \
                self.engine.num_target_forwards - fw0
            self.metrics.draft_syncs += (
                getattr(self.engine, "num_draft_syncs", 0) - ds0)
            finished = self._commit(advancing, outs)
            self._consec_faults = 0
            # Healing probe (DESIGN.md §14): after enough consecutive
            # clean committed rounds the circuit breaker half-opens and
            # the server climbs the most recent ladder rung back.
            if (self._breaker is not None and advancing
                    and self._ladder_taken
                    and self._breaker.record_commit()):
                self._heal()
            return finished
        finally:
            self.metrics.wall_s += time.perf_counter() - t0

    def _engine_round(self, subs, advancing, newly, overlap) -> list:
        """One engine round — the three execution branches."""
        if overlap:
            # The overlap path skips full-prefix assembly (the engine
            # serves from cached state) but still hands over each
            # request's last emitted token so the engine can enforce
            # the prefix-tail == pending contract loudly.
            tails = [int(r.output[-1]) if r.output else int(r.prompt[-1])
                     for r in advancing]
            # Admission prefixes carry prompt+output: a re-admitted
            # (evicted) request re-prefills everything it has emitted
            # so far, rebuilding KV bitwise equal to the state it lost.
            # For fresh requests output is empty and this is the prompt.
            return self.engine.round_with_admission(
                subs, [r.uid for r in advancing],
                [(r.uid, np.concatenate([r.prompt,
                                         np.asarray(r.output, np.int32)]))
                 for r in newly], self._buf_len,
                tails=tails)
        prefixes = [np.concatenate([r.prompt,
                                    np.asarray(r.output, np.int32)])
                    for r in advancing]
        if self.cache_mode in ("kv", "kv_fused"):
            return self.engine.gen_blocks(
                subs, prefixes, self._buf_len,
                uids=[r.uid for r in advancing],
                fused=self.cache_mode == "kv_fused",
                admission=self.admission)
        if self.batched:
            return self.engine.gen_blocks(subs, prefixes, self._buf_len)
        return [self.engine.gen_block(sub, prefix, self._buf_len)
                for sub, prefix in zip(subs, prefixes)]

    def _dispatch(self, subs, advancing, newly, overlap) -> list:
        """Run one engine round under the fault layer (DESIGN.md §13):
        pre-call injections fire before the engine is touched, the
        watchdog times the blocking call, post-call injections and the
        outcome guard run on the results.  Injection draws are keyed by
        (kind, uid, blocks, retries) — fully deterministic, and a
        replay re-draws at the same rate because the attributed
        request's retry counter advanced."""
        plan = self.fault_plan
        post = []
        if plan is not None:
            for req in advancing:
                for kind in FAULT_KINDS:
                    if not plan.fires(kind, req.uid, req.blocks,
                                      req.retries):
                        continue
                    if kind in ("pool_exhausted", "oom"):
                        # Pre-call: the engine never runs, session
                        # state stays clean (suspend-capable recovery).
                        raise InjectedFault(kind, uid=req.uid, phase="pre")
                    post.append((kind, req))
        wd = RoundWatchdog(self.round_timeout_ms)
        with wd:
            outs = self._engine_round(subs, advancing, newly, overlap)
            for kind, req in post:
                if kind == "slow_round":
                    time.sleep(plan.slow_ms / 1e3)
        # The valve only engages on rounds that ADVANCE requests: an
        # admission-only round (overlap mode right after displacement)
        # must neither raise — discarding it re-does the same prefill —
        # nor reset the consecutive-trip counter, which would starve
        # the advancing rounds of ever reaching the accept valve.
        if wd.tripped and advancing:
            self.metrics.watchdog_trips += 1
            self._consec_wd += 1
            if self._consec_wd > max(1, self.retry_budget):
                # Anti-livelock valve: the round's results are VALID,
                # just late.  On a genuinely slow machine, discarding
                # forever would wedge the drain loop — accept the slow
                # round instead and record that we did.
                self.metrics.watchdog_accepts += 1
                self._consec_wd = 0
            else:
                slow = next((r for k, r in post if k == "slow_round"),
                            None)
                if slow is not None:
                    raise InjectedFault("slow_round", uid=slow.uid,
                                        phase="post")
                raise WatchdogTimeout(
                    f"round exceeded {self.round_timeout_ms}ms")
        elif advancing:
            self._consec_wd = 0
        for kind, req in post:
            if kind == "kernel_dispatch":
                raise InjectedFault(kind, uid=req.uid, phase="post")
        poisoned_uids = set()
        if post:
            idx = {id(r): i for i, r in enumerate(advancing)}
            for kind, req in post:
                if kind == "nan_logits":
                    outs[idx[id(req)]] = poison_outcome(
                        outs[idx[id(req)]], self._vocab(), req.uid)
                    poisoned_uids.add(req.uid)
        if self.guarded:
            lr = self.engine.cfg.draft_len
            for req, out in zip(advancing, outs):
                try:
                    validate_outcome(out, req.uid, self._vocab(), lr)
                except GuardViolation:
                    if req.uid not in poisoned_uids:
                        raise
                    # The guard caught OUR injection: attribute it to
                    # the injected class (recovery scrubs either way —
                    # both are poisoning kinds), so the fault counters
                    # separate injected NaN rounds from genuine
                    # corruption ("guard").
                    raise InjectedFault("nan_logits", uid=req.uid,
                                        phase="post")
        return outs

    def _commit(self, advancing, outs) -> list:
        """Commit a validated round: journal it (write-ahead — the
        record is fsync'd before any token reaches a streaming
        callback, so a crash can never deliver a token that replay
        forgets), emit tokens, retire finished requests, isolate
        callback failures."""
        finished, cb_failed = [], []
        t_commit = time.time()
        emits = []
        for req, out in zip(advancing, outs):
            # Emit only up to max_new: the block may overshoot on its
            # last round, and streamed tokens / timestamps must match
            # the final (trimmed) output exactly.
            emit = list(out.new_tokens)[:req.max_new - len(req.output)]
            emits.append(emit)
            req.output.extend(emit)
            req.blocks += 1
            # A committed round is progress: quarantine is for
            # PERSISTENT failure, so the budget counts CONSECUTIVE
            # attributed faults, not lifetime ones — a long request
            # under steady background chaos must not accumulate its
            # way into quarantine.
            req.retries = 0
            req.accepted += out.accepted
            req.tokens_since_admit += len(emit)
            self.metrics.host_syncs += out.verify_syncs
            if req.t_first is None:
                req.t_first = t_commit
        self._journal_commit(advancing, emits)
        for req, emit in zip(advancing, emits):
            for tok in emit:
                req.token_times.append(t_commit)
                if req.on_token is not None:
                    try:
                        req.on_token(req.uid, int(tok))
                    except Exception as e:
                        # User callback code: a raising callback fails
                        # only ITS request — never the drain loop.
                        req.on_token = None
                        req.error = f"on_token callback raised: {e!r}"
                        cb_failed.append(req)
                        self.metrics.callback_errors += 1
            if req.error is None and req.done:
                req.t_done = t_commit
                finished.append(req)
        for req in cb_failed:
            # The failed request's slot (and pages) release; committed
            # tokens stay on the record for the postmortem.
            self.live.remove(req)
            if hasattr(self.engine, "has_session") \
                    and self.engine.has_session(req.uid):
                self.engine.release(req.uid)
            self.failed.append(req)
            self._journal_fail(req, "callback")
        for req in finished:
            self.live.remove(req)
            if self.cache_mode in ("kv", "kv_fused"):
                self.engine.release(req.uid)
            self.metrics.completed += 1
            self.metrics.total_tokens += len(req.output)
            self.metrics.total_blocks += req.blocks
        self.done.extend(finished)
        if advancing:
            self._maybe_snapshot()
        return finished

    # ---- fault recovery (DESIGN.md §13) ------------------------------

    def _recover(self, fault, newly) -> None:
        """Guarded-fault recovery: displace every request the round
        touched, discard round-scoped device state, attribute the
        fault, and (optionally) step the degradation ladder.  Replay is
        exact for free: per-request randomness is (uid, blocks)-keyed
        and ``blocks`` only advances at commit, so the re-executed
        round draws the very sheet the discarded round drew, and
        re-prefilled KV is bitwise equal to the decode-built KV it
        replaces."""
        now = time.time()
        kind = getattr(fault, "kind", None)
        if kind is None:
            kind = "pool_exhausted" \
                if isinstance(fault, PagePoolExhausted) else "oom"
        phase = getattr(fault, "phase",
                        "pre" if isinstance(fault, PagePoolExhausted)
                        else "post")
        poisoned = kind in ("nan_logits", "guard")
        uid = getattr(fault, "uid", None)
        self.metrics.faults[kind] = self.metrics.faults.get(kind, 0) + 1
        self.metrics.retries += 1
        self._consec_faults += 1

        # Displace everyone.  Post-phase faults advanced session state
        # (pending / device positions) past what the host committed, so
        # those sessions hard-evict and replay from prompt+output;
        # pre-phase faults left sessions clean, so a paged v2 engine
        # SUSPENDS instead (pages stay resident — this is how a real
        # ``PagePoolExhausted`` converts into displacement: suspend the
        # holders, let v2 admission strip handles under pressure, hard-
        # evict last).  Poisoned rounds always hard-evict — suspended
        # pages would keep possibly-NaN bytes alive across the scrub.
        can_suspend = (self.policy == "v2" and not poisoned
                       and phase == "pre"
                       and getattr(self.engine, "can_suspend",
                                   lambda: False)())
        new_ids = {id(r) for r in newly}
        displaced = list(self.live)
        self.live.clear()
        for req in displaced:
            if hasattr(self.engine, "has_session") \
                    and self.engine.has_session(req.uid):
                if can_suspend and id(req) not in new_ids:
                    req._kv_handle = self.engine.suspend(req.uid)
                else:
                    self.engine.evict(req.uid)
            req._t_evict = now
        # Requeue at the FRONT in original order; ``evictions`` stays
        # untouched — fault displacement is not a policy rotation, and
        # bumping it would perturb the v2 admission rank (and with it
        # the token-invisible replay schedule).
        self.queue.extendleft(reversed(displaced))
        if poisoned:
            # The scrub rebuilds KV storage; a suspended handle's
            # detached pages may hold poisoned bytes, so forfeit them
            # first (the holders re-prefill — exact, by the same
            # bit-identity argument as eviction).
            for q in self.queue:
                if q._kv_handle is not None:
                    self.engine.drop_handle(q._kv_handle)
                    q._kv_handle = None
        if hasattr(self.engine, "discard_round_state"):
            self.engine.discard_round_state(scrub=poisoned)

        if uid is not None:
            req = next((r for r in displaced if r.uid == uid), None)
            if req is not None:
                req.retries += 1
                if req.retries > self.retry_budget:
                    self._quarantine(
                        req, f"retry budget ({self.retry_budget}) "
                             f"exhausted by repeated {kind} faults")
        stepped = False
        if self._breaker is not None and self._breaker.record_fault():
            # Fault during the probation window of a healing probe: the
            # rung we just climbed back is still broken.  Re-take it
            # immediately (the breaker has already doubled the clean-
            # round threshold for the next probe).
            stepped = self._degrade()
            if stepped:
                self._consec_faults = 0
        if not stepped and self.degrade_after \
                and self._consec_faults >= self.degrade_after:
            stepped = self._degrade()
            if stepped:
                self._consec_faults = 0
        if not stepped and uid is None \
                and self._consec_faults > max(1, self.retry_budget):
            # An unattributed fault recurring with no ladder rung left:
            # re-raise rather than retry forever.
            raise fault

    def _quarantine(self, req: Request, reason: str) -> None:
        """Permanently fail a request: out of the queue, suspend handle
        forfeited, error recorded.  Its engine session is already gone
        (recovery displaced it before attribution)."""
        if req in self.queue:
            self.queue.remove(req)
        if req._kv_handle is not None:
            self.engine.drop_handle(req._kv_handle)
            req._kv_handle = None
        req.error = f"quarantined: {reason}"
        self.failed.append(req)
        self.metrics.quarantined += 1
        self._journal_fail(req, "quarantine")

    def _ladder_next(self) -> Optional[str]:
        """The next degradation rung, or None at the bottom.  Rungs
        step from the most-optimized execution mode toward the
        stateless reference, and every rung except dequant is
        bit-identical (DESIGN.md §13):

          pallas verifier -> xla   (exact-equality oracles)
          quant verify -> f32      (acceptance-equivalent)
          kv_fused -> kv           (same tokens, host-driven round)
          kv -> reprefill          (same tokens, stateless reference)
        """
        cfg = getattr(self.engine, "cfg", None)
        if cfg is not None and cfg.verifier_backend == "pallas" \
                and hasattr(self.engine, "set_verifier_backend"):
            return "verifier:pallas->xla"
        if cfg is not None and getattr(cfg, "quant", False) \
                and hasattr(self.engine, "dequantize_verify") \
                and not getattr(self.engine, "_verify_dequantized", False):
            return "verify:quant->f32"
        if self.cache_mode == "kv_fused":
            return "cache:kv_fused->kv"
        if self.cache_mode == "kv":
            return "cache:kv->reprefill"
        return None

    def _apply_rung(self, step: str) -> None:
        """Apply one ladder rung to the engine/server (shared by the
        live ``_degrade`` path and journal replay on restore)."""
        if step == "verifier:pallas->xla":
            self.engine.set_verifier_backend("xla")
        elif step == "verify:quant->f32":
            self.engine.dequantize_verify()
        elif step == "cache:kv_fused->kv":
            self.cache_mode = "kv"
        else:  # cache:kv->reprefill
            # The reference path is stateless: no sessions, no resume —
            # strip any suspended handle (the holders re-prefill) and
            # stack the reference rounds into batched forwards.
            for q in self.queue:
                if q._kv_handle is not None:
                    self.engine.drop_handle(q._kv_handle)
                    q._kv_handle = None
            self._pre_degrade_batched = self.batched
            self.cache_mode = "reprefill"
            self.batched = True
        self._ladder_taken.append(step)

    def _revert_rung(self, step: str) -> None:
        """Inverse of ``_apply_rung`` — climb one rung back up.  Every
        transition is token-invisible by the same arguments as the way
        down: the (uid, blocks) RNG key and the re-prefill/re-admission
        bit-identity contract (sessions for uids the faster mode has
        not seen are admitted on its first round)."""
        if step == "verifier:pallas->xla":
            self.engine.set_verifier_backend("pallas")
        elif step == "verify:quant->f32":
            self.engine.requantize_verify()
        elif step == "cache:kv_fused->kv":
            self.cache_mode = "kv_fused"
        else:  # cache:kv->reprefill
            self.cache_mode = "kv"
            self.batched = self._pre_degrade_batched
        self._ladder_taken.pop()

    def _degrade(self) -> bool:
        """Step one rung down the degradation ladder; returns whether a
        step was taken.  Without a circuit breaker (``heal_after``)
        transitions are sticky — the ladder never climbs back mid-serve
        (a flapping mode would re-trigger whatever broke the faster
        one); with one, ``_heal`` probes rungs back after sustained
        clean rounds.  Every transition is recorded in
        ``metrics.degradations``."""
        step = self._ladder_next()
        if step is None:
            return False
        self._apply_rung(step)
        self.metrics.degradations.append(
            {"round": self.metrics.rounds, "step": step})
        if self._journal is not None:
            self._journal.append({"t": "degrade", "step": step})
        return True

    def _heal(self) -> None:
        """Circuit-breaker healing probe (DESIGN.md §14): climb the most
        recently taken ladder rung back and open a probation window —
        a fault before it closes re-degrades with an exponentially
        backed-off threshold (``_recover``)."""
        step = self._ladder_taken[-1]
        self._revert_rung(step)
        self.metrics.heals += 1
        self.metrics.degradations.append(
            {"round": self.metrics.rounds, "step": f"heal:{step}"})
        if self._journal is not None:
            self._journal.append({"t": "heal", "step": step})
        if self._breaker is not None:
            self._breaker.record_promote()

    def run(self, key: jax.Array) -> list:
        """Drain the queue; returns all completed requests in finish order.
        Wall time accrues inside ``step()`` (shared with direct-step
        callers), so this loop adds no timing of its own.  The SAME key
        feeds every round — per-request streams are (uid, blocks)-keyed
        (module docstring), so which round a block lands in never
        changes its randomness."""
        done = []
        while self.queue or self.live:
            done.extend(self.step(key))
        return done

    # ---- durable state (DESIGN.md §14) -------------------------------

    def _journal_fail(self, req: Request, kind: str) -> None:
        """Record a permanent per-request failure (shed / deadline /
        quarantine / callback) so replay reproduces the failed set."""
        if self._journal is not None:
            self._journal.append({"t": "fail", "uid": req.uid,
                                  "kind": kind, "error": req.error})

    def _journal_commit(self, advancing, emits) -> None:
        """One fsync'd record per committed round: absolute (blocks,
        accepted) plus the emitted token delta per request, the pinned
        buffer length (compiled-shape input — replay must re-pin it),
        finished uids, and the engine's arena residency."""
        if self._journal is None or not advancing:
            return
        rec = {
            "t": "commit", "round": self.metrics.rounds,
            "buf": self._buf_len,
            "reqs": [[r.uid, r.blocks, [int(t) for t in e],
                      int(r.accepted)]
                     for r, e in zip(advancing, emits)],
            "fin": [r.uid for r in advancing
                    if r.error is None and r.done],
        }
        if hasattr(self.engine, "residency"):
            rec["res"] = self.engine.residency()
        self._journal.append(rec)

    def snapshot(self) -> str:
        """Write a point-in-time snapshot NOW (also runs on the
        ``snapshot_every`` cadence).  Host state only — no logits, no
        KV: determinism makes recovery logits-free (DESIGN.md §14)."""
        if self._journal is None:
            raise ValueError("snapshot() needs journal_dir")
        spath = snapshot_path(self.journal_dir)
        write_snapshot(spath, capture_server(self, self._journal.offset))
        self._rounds_since_snap = 0
        return spath

    def _maybe_snapshot(self) -> None:
        if self._journal is None or not self.snapshot_every:
            return
        self._rounds_since_snap += 1
        if self._rounds_since_snap >= self.snapshot_every:
            self.snapshot()

    def begin_drain(self) -> None:
        """Stop accepting submits — every subsequent ``submit`` raises
        ``Overloaded(draining=True)``.  Queued and live work continues
        to completion through ``step()``/``run()``."""
        self.draining = True

    def shutdown(self, key: Optional[jax.Array] = None) -> Optional[str]:
        """Graceful shutdown: begin draining, optionally finish all
        remaining work (pass the round ``key``), then append the drain
        marker, write a final snapshot, and close the journal.  Returns
        the snapshot path (None when journaling is off).  Without a
        ``key`` the un-drained work is NOT lost — it is exactly what
        the final snapshot records, and ``restore`` resumes it."""
        self.begin_drain()
        if key is not None:
            while self.queue or self.live:
                self.step(key)
        if self._journal is None:
            return None
        self._journal.append({"t": "drain"})
        spath = self.snapshot()
        self._journal.close()
        return spath

    @classmethod
    def restore(cls, journal_dir: str, engine, *,
                journal_fsync: bool = True,
                snapshot_every: Optional[int] = None, **kw):
        """Rebuild a server from its journal directory and resume every
        request bit-identically (DESIGN.md §14).

        Recovery is LOGITS-FREE: snapshot + journal hold only host
        state (prompts, committed tokens, block counts, ladder rungs) —
        no logits and no KV ever hit disk.  Determinism does the rest:
        per-request randomness is (uid, blocks)-keyed and ``blocks``
        advances only at commit, so re-admission re-prefills
        prompt+output into KV bitwise equal to what the dead process
        held, and the next block draws the exact stream it would have
        drawn.  A torn final record (kill -9 mid-append) is dropped by
        the CRC-framed reader and the journal resumes from the last
        valid byte — the torn round never committed host-side either,
        so nothing is lost or duplicated.

        ``kw`` must carry the same serving configuration the original
        server was constructed with (cache_mode, policy, ...); ladder
        state recorded in the snapshot/journal is re-applied on top.
        ``on_token`` callbacks and suspend handles are process-local
        and do not survive (suspended requests re-prefill — token-
        invisible, like any hard eviction)."""
        jpath = journal_path(journal_dir)
        spath = snapshot_path(journal_dir)
        if not os.path.exists(jpath):
            raise FileNotFoundError(f"no journal at {jpath}")
        snap = read_snapshot(spath)
        records, valid_end = read_journal(jpath)
        if snap is not None:
            start = int(snap["journal_offset"])
            if start > valid_end:
                raise ValueError(
                    f"snapshot journal_offset {start} beyond the valid "
                    f"journal ({valid_end} bytes) — inconsistent pair")
            replay, _ = read_journal(jpath, start=start)
        else:
            replay = records

        kw.pop("journal_dir", None)
        srv = cls(engine, **kw)
        reqs: dict = {}                   # uid -> [Request, state]
        if snap is not None:
            srv._uid = int(snap["uid"])
            srv._buf_len = max(srv._buf_len, int(snap["buf_len"]))
            srv.cache_mode = str(snap["cache_mode"])
            srv.batched = bool(snap["batched"])
            srv._ladder_taken = [str(s) for s in snap["ladder"]]
            if snap.get("engine") and hasattr(engine, "restore_state"):
                engine.restore_state(snap["engine"])
            m = snap["metrics"]
            for k in ("rounds", "evictions", "preemptions", "retries",
                      "quarantined", "watchdog_trips", "watchdog_accepts",
                      "callback_errors", "shed", "rejected",
                      "deadline_expired", "heals"):
                setattr(srv.metrics, k, int(m[k]))
            srv.metrics.wall_s = float(m["wall_s"])
            srv.metrics.faults = {str(k): int(v)
                                  for k, v in m["faults"].items()}
            srv.metrics.degradations = [dict(d)
                                        for d in m["degradations"]]
            for d in snap["requests"]:
                req = unpack_request(d)
                # Live requests restore as queued: the engine session
                # died with the process, re-admission rebuilds it.
                state = "queued" if d["state"] == "live" else d["state"]
                reqs[req.uid] = [req, state]

        for rec in replay:
            t = rec["t"]
            if t == "submit":
                req = Request(
                    uid=int(rec["uid"]),
                    prompt=np.asarray(rec["prompt"], np.int32),
                    max_new=int(rec["max_new"]),
                    priority=int(rec["priority"]),
                    t_deadline=rec.get("deadline"),
                    t_submit=float(rec.get("ts", 0.0)))
                reqs[req.uid] = [req, "queued"]
                srv._uid = max(srv._uid, req.uid)
            elif t == "commit":
                srv._buf_len = max(srv._buf_len, int(rec["buf"]))
                srv.metrics.rounds = int(rec["round"])
                for uid, blocks, toks, accepted in rec["reqs"]:
                    req = reqs[uid][0]
                    req.output.extend(int(x) for x in toks)
                    req.blocks = int(blocks)
                    req.accepted = int(accepted)
                for uid in rec["fin"]:
                    reqs[uid][1] = "done"
            elif t == "fail":
                entry = reqs.get(rec["uid"])
                if entry is not None:
                    entry[0].error = rec.get("error")
                    entry[1] = "failed"
                kind = rec.get("kind")
                if kind == "shed":
                    srv.metrics.shed += 1
                elif kind == "deadline":
                    srv.metrics.deadline_expired += 1
                elif kind == "quarantine":
                    srv.metrics.quarantined += 1
                elif kind == "callback":
                    srv.metrics.callback_errors += 1
            elif t == "degrade":
                srv._apply_rung(str(rec["step"]))
                srv.metrics.degradations.append(
                    {"round": srv.metrics.rounds,
                     "step": str(rec["step"])})
            elif t == "heal":
                srv._revert_rung(str(rec["step"]))
                srv.metrics.heals += 1
                srv.metrics.degradations.append(
                    {"round": srv.metrics.rounds,
                     "step": f"heal:{rec['step']}"})
            # "drain" markers carry no state

        # Completion metrics recomputed from final states (never
        # layered onto snapshot counters — a request can cross the
        # snapshot boundary mid-flight).
        srv.metrics.completed = 0
        srv.metrics.total_tokens = 0
        srv.metrics.total_blocks = 0
        for req, state in sorted(reqs.values(), key=lambda e: e[0].uid):
            if state == "done":
                srv.done.append(req)
                srv.metrics.completed += 1
                srv.metrics.total_tokens += len(req.output)
                srv.metrics.total_blocks += req.blocks
            elif state == "failed":
                srv.failed.append(req)
            else:
                srv.queue.append(req)

        srv.journal_dir = journal_dir
        if snapshot_every is not None and snapshot_every < 1:
            raise ValueError("snapshot_every must be >= 1")
        srv.snapshot_every = snapshot_every
        srv._journal = RoundJournal(jpath, fsync=journal_fsync,
                                    truncate_to=valid_end)
        return srv
