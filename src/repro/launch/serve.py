"""Serving launcher: GLS multi-draft speculative decoding over a
target/drafter pair, driven by the batched request scheduler.

  python -m repro.launch.serve --steps 120 --requests 4 \
      --strategy gls --drafts 8 --cache-mode kv

``--cache-mode reprefill`` drives the reference engine (full-prefix
re-score per block; add ``--batched`` to stack live requests into one
target forward per round); ``--cache-mode kv`` serves from persistent
KV caches in a multi-request slot pool (DESIGN.md §7) — same tokens,
no re-prefill; ``--cache-mode kv_fused`` additionally runs each whole
round as ONE jitted device program (DESIGN.md §8) — same tokens again,
zero draft syncs, one host sync per round.  ``--paged`` swaps the slot
arena for the paged KV arena and ``--policy v2 --preempt-tokens N``
turns on eviction/re-admission + rotation preemption (DESIGN.md §12)
— same tokens in every combination.

``--journal-dir DIR`` turns on crash-safe serving (DESIGN.md §14):
one fsync'd journal record per committed round plus periodic
snapshots (``--snapshot-every N``); after a crash (or a SIGTERM
drain), ``--restore --journal-dir DIR`` resumes every request
bit-identically.  ``--deadline-ms/--max-queue/--rate-limit`` arm the
overload valves and ``--heal-after`` the degradation-ladder circuit
breaker.

``--target ARCH --drafter ARCH`` serves two architectures of
``repro.configs`` at their published widths in float32, with random
weights drawn from ``--init-seed`` (under ``--tp N`` each weight is
created already sharded) and seeded random prompts of 16-128 tokens:

  python -m repro.launch.serve --target smollm-360m \
      --drafter smollm-135m --cache-mode kv_fused --backend pallas \
      --requests 8 --max-new 32 --max-batch 8

The fused round has fixed shapes, so its device work does not depend
on the weights.  Without ``--target`` the launcher trains (or loads
from ``checkpoints/``) the small vocab-128 benchmark pair on the
synthetic corpus first (CPU-scale demonstration of the full path).

``build_server`` is the construction ``main`` uses — engine, then
server — and ``chip_smoke.py`` drives the same function."""

from __future__ import annotations

import argparse
import functools

import jax
import numpy as np


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", default=None,
                    help="serve this repro.configs architecture at its "
                         "published widths with seeded random weights "
                         "(needs --drafter; default: the trained "
                         "vocab-128 benchmark pair)")
    ap.add_argument("--drafter", default=None,
                    help="drafter architecture for --target (same "
                         "vocabulary)")
    ap.add_argument("--init-seed", type=int, default=0,
                    help="seed of the random weights and prompts of "
                         "--target/--drafter")
    ap.add_argument("--strategy", default="gls",
                    choices=("gls", "gls_strong", "specinfer", "spectr",
                             "single", "daliri"))
    ap.add_argument("--drafts", type=int, default=8)
    ap.add_argument("--draft-len", type=int, default=4)
    ap.add_argument("--steps", type=int, default=120,
                    help="training steps when no checkpoint given")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--backend", default="xla",
                    choices=("legacy", "xla", "pallas"),
                    help="block-verification backend (pallas routes the "
                         "K-way race through the gls_race kernel)")
    ap.add_argument("--cache-mode", default="reprefill",
                    choices=("reprefill", "kv", "kv_fused"),
                    help="reprefill: reference engine, full-prefix "
                         "re-score; kv: persistent KV caches in a "
                         "multi-request slot pool; kv_fused: kv with "
                         "the whole round fused into one device program")
    ap.add_argument("--batched", action="store_true",
                    help="stack live requests into one target forward "
                         "per round (reprefill mode; kv always batches)")
    ap.add_argument("--admission", default="bucketed",
                    choices=("bucketed", "per_request"),
                    help="bucketed: batched admission — prompts prefill "
                         "straight into pool slots, one stacked dispatch "
                         "per length bucket per model, overlapped with "
                         "the running round under kv_fused (DESIGN.md "
                         "§9); per_request: the 2-dispatches-per-request "
                         "reference path")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV arena (DESIGN.md §12): fixed-size "
                         "time pages behind a device page table — the "
                         "queue can oversubscribe physical capacity "
                         "and preemption parks pages instead of "
                         "discarding KV (kv/kv_fused only)")
    ap.add_argument("--policy", default="fifo", choices=("fifo", "v2"),
                    help="v2: priority-ordered admission with "
                         "eviction/re-admission and preemption "
                         "(kv/kv_fused only)")
    ap.add_argument("--tp", type=int, default=1,
                    help="serving tensor parallelism (DESIGN.md §15): "
                         "run the fused round under shard_map on a "
                         "(tp,)-device 'model' mesh — weights output-dim "
                         "sharded, KV arenas head-sharded, all-gathers "
                         "only (kv_fused only; CPU sessions need "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N exported before launch)")
    ap.add_argument("--preempt-tokens", type=int, default=None,
                    help="per-request rotation quantum: suspend a "
                         "request after this many new tokens when "
                         "others are waiting (policy v2)")
    ap.add_argument("--prefill-kernel", action="store_true",
                    help="route admission prefill chunks through the "
                         "flash-attention Pallas kernel (numerically "
                         "equivalent, not bit-equal)")
    ap.add_argument("--fault-rate", type=float, default=None,
                    help="chaos mode (DESIGN.md §13): inject every "
                         "fault class at this per-request-per-round "
                         "rate; survivors replay bit-identically")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="deterministic injection seed for --fault-rate")
    ap.add_argument("--retry-budget", type=int, default=None,
                    help="per-request fault retries before quarantine "
                         "(default 2; passing it arms the guard layer)")
    ap.add_argument("--round-timeout-ms", type=float, default=None,
                    help="per-round wall-clock watchdog budget")
    ap.add_argument("--degrade-after", type=int, default=None,
                    help="consecutive faults before stepping down the "
                         "degradation ladder (pallas->xla, quant->f32, "
                         "kv_fused->kv->reprefill)")
    ap.add_argument("--journal-dir", default=None,
                    help="crash-safe serving (DESIGN.md §14): write one "
                         "fsync'd journal record per committed round "
                         "into this directory; restart with --restore "
                         "to resume every request bit-identically")
    ap.add_argument("--snapshot-every", type=int, default=None,
                    help="write a snapshot every N committed rounds "
                         "(bounds journal replay cost; needs "
                         "--journal-dir)")
    ap.add_argument("--restore", action="store_true",
                    help="resume from --journal-dir instead of starting "
                         "fresh (snapshot + journal replay)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request wall-clock deadline: queued "
                         "requests past it are shed, and v2 admission "
                         "orders same-priority requests EDF")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="queue-depth backpressure: past this many "
                         "waiting requests, submits shed a lower-"
                         "priority waiter or are rejected (Overloaded)")
    ap.add_argument("--rate-limit", type=float, default=None,
                    help="token-rate limiter: sustained admission "
                         "budget in (prompt+max_new) tokens/s")
    ap.add_argument("--heal-after", type=int, default=None,
                    help="circuit breaker: probe the most recent "
                         "degradation rung back after this many "
                         "consecutive clean rounds")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    ap = make_parser()
    args = ap.parse_args(argv)
    if (args.target is None) != (args.drafter is None):
        ap.error("--target and --drafter go together")
    if args.cache_mode == "kv_fused" and args.backend == "legacy":
        ap.error("--cache-mode kv_fused needs a device verifier backend "
                 "(xla or pallas)")
    if (args.paged or args.policy == "v2") and \
            args.cache_mode not in ("kv", "kv_fused"):
        ap.error("--paged / --policy v2 need --cache-mode kv or kv_fused")
    if args.tp > 1 and args.cache_mode != "kv_fused":
        ap.error("--tp serves through the sharded fused round; it needs "
                 "--cache-mode kv_fused")
    if args.tp > 1 and args.paged:
        ap.error("--tp requires the contiguous arena (drop --paged)")
    if (args.snapshot_every is not None or args.restore) \
            and args.journal_dir is None:
        ap.error("--snapshot-every / --restore need --journal-dir")
    return args


def init_served_params(cfg, key: jax.Array, mesh=None):
    """Random weights for ``cfg``, made on the device by one jitted
    ``init_params``.  With a serving-TP ``mesh`` every leaf is created
    already in its ``serve_param_spec`` sharding, so no device ever
    holds the whole model."""
    from repro.models import init_params
    init = functools.partial(init_params, cfg=cfg)
    if mesh is None:
        return jax.jit(init)(key)
    from jax.sharding import NamedSharding
    from repro.sharding.rules import serve_params_pspecs
    specs = serve_params_pspecs(jax.eval_shape(init, key), mesh)
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs)
    return jax.jit(init, out_shardings=shardings)(key)


def random_pair(t_cfg, d_cfg, seed: int = 0, tp: int = 1):
    """((target_params, t_cfg), (drafter_params, d_cfg)) with seeded
    random weights, sharded over the first ``tp`` devices when tp > 1."""
    mesh = None
    if tp > 1:
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(tp)
    kt, kd = jax.random.split(jax.random.PRNGKey(seed))
    return ((init_served_params(t_cfg, kt, mesh), t_cfg),
            (init_served_params(d_cfg, kd, mesh), d_cfg))


def random_prompts(n: int, vocab: int, seed: int = 0, min_len: int = 16,
                   max_len: int = 128) -> list:
    """``n`` seeded prompts of uniform random tokens in [1, vocab), with
    lengths uniform in [min_len, max_len]."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(min_len, max_len + 1, size=n)
    return [rng.integers(1, vocab, size=int(m)).astype(np.int32)
            for m in lens]


def load_pair(args):
    """The served (target, drafter) pair ``args`` names."""
    if args.target is not None:
        from repro.configs import get_config
        return random_pair(get_config(args.target).replace(dtype="float32"),
                           get_config(args.drafter).replace(dtype="float32"),
                           seed=args.init_seed, tp=args.tp)
    return _lm_pair().get_pair(steps=args.steps, log=print)


def _lm_pair():
    """The benchmark pair module (``benchmarks/`` sits beside ``src/``)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
    from benchmarks import lm_pair
    return lm_pair


def build_server(args, pair):
    """Engine and server for parsed ``args`` (``parse_args``) over
    ``pair`` (``load_pair``): the ``SpecDecServer`` →
    ``CachedSpecDecEngine`` (or reference ``SpecDecEngine``) stack
    ``main`` serves from."""
    from repro.serving import FaultPlan
    from repro.specdec import (
        CachedSpecDecEngine,
        SpecDecConfig,
        SpecDecEngine,
        SpecDecServer,
    )

    target, drafter = pair
    k = 1 if args.strategy in ("single", "daliri") else args.drafts
    cfg = SpecDecConfig(num_drafts=k, draft_len=args.draft_len,
                        strategy=args.strategy, top_k=50,
                        max_new_tokens=args.max_new,
                        verifier_backend=args.backend,
                        prefill_kernel=args.prefill_kernel,
                        paged=args.paged, tp=args.tp)
    if args.cache_mode in ("kv", "kv_fused"):
        eng = CachedSpecDecEngine(target, drafter, cfg,
                                  pool_slots=args.max_batch)
    else:
        eng = SpecDecEngine(target, [drafter], cfg)
    plan = None
    if args.fault_rate is not None:
        slow_ms = (args.round_timeout_ms * 2.0
                   if args.round_timeout_ms else 100.0)
        plan = FaultPlan.uniform(args.fault_rate, seed=args.fault_seed,
                                 slow_ms=slow_ms)
    server_kw = dict(max_batch=args.max_batch,
                     batched=args.batched,
                     cache_mode=args.cache_mode,
                     admission=args.admission,
                     policy=args.policy,
                     preempt_tokens=args.preempt_tokens,
                     fault_plan=plan,
                     retry_budget=args.retry_budget,
                     round_timeout_ms=args.round_timeout_ms,
                     degrade_after=args.degrade_after,
                     max_queue=args.max_queue,
                     deadline_ms=args.deadline_ms,
                     rate_limit=args.rate_limit,
                     heal_after=args.heal_after)
    if args.restore:
        return SpecDecServer.restore(
            args.journal_dir, eng, snapshot_every=args.snapshot_every,
            **server_kw)
    return SpecDecServer(eng, journal_dir=args.journal_dir,
                         snapshot_every=args.snapshot_every, **server_kw)


def main(argv=None):
    args = parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    pair = load_pair(args)
    server = build_server(args, pair)
    eng = server.engine
    k = eng.cfg.num_drafts
    if args.restore:
        print(f"restored from {args.journal_dir}: "
              f"{len(server.queue)} resumable, {len(server.done)} done, "
              f"{len(server.failed)} failed")
    else:
        prompts = (random_prompts(args.requests, pair[0][1].vocab_size,
                                  args.init_seed)
                   if args.target is not None
                   else _lm_pair().bench_prompts(args.requests))
        for p in prompts:
            server.submit(p, max_new=args.max_new)

    # Graceful shutdown (DESIGN.md §14): SIGTERM/SIGINT finish the
    # in-flight round, flush the journal, write a final snapshot, and
    # exit 0 — a later --restore resumes the un-drained work.
    import signal
    stop = {"flag": False}

    def _on_signal(signum, frame):
        stop["flag"] = True
        server.begin_drain()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    key = jax.random.PRNGKey(0)
    done = list(server.done)
    while (server.queue or server.live) and not stop["flag"]:
        done.extend(server.step(key))
    if server.journal_dir is not None:
        spath = server.shutdown()
        if stop["flag"]:
            print(f"drained on signal: snapshot at {spath} "
                  f"({len(server.queue) + len(server.live)} requests "
                  "resumable via --restore)")
    m = server.metrics
    be = float(np.mean([r.block_efficiency for r in done])) if done else 0.0
    ttft = float(np.mean([r.ttft_ms for r in done])) if done else 0.0
    pd = getattr(eng, "num_prefill_dispatches", 0)
    print(f"strategy={args.strategy} K={k} L={args.draft_len} "
          f"backend={args.backend} cache_mode={args.cache_mode} "
          f"admission={args.admission} "
          f"BE={be:.2f} tok/s={m.tokens_per_s:.1f} "
          f"mean-ttft={ttft:.1f}ms prefill-dispatches={pd} "
          f"rounds={m.rounds} target-forwards={m.target_forwards} "
          f"verify-syncs={m.host_syncs} draft-syncs={m.draft_syncs} "
          f"evictions={m.evictions} preemptions={m.preemptions} "
          f"over {len(done)} requests")
    if server.guarded:
        print(f"faults={dict(m.faults)} retries={m.retries} "
              f"quarantined={m.quarantined} "
              f"watchdog-trips={m.watchdog_trips} "
              f"watchdog-accepts={m.watchdog_accepts} "
              f"degradations={[d['step'] for d in m.degradations]} "
              f"heals={m.heals} failed={len(server.failed)}")
    if server.journal_dir is not None or m.rejected or m.shed \
            or m.deadline_expired:
        ja = server._journal.appends if server._journal else 0
        print(f"journal-appends={ja} rejected={m.rejected} "
              f"shed={m.shed} deadline-expired={m.deadline_expired}")


if __name__ == "__main__":
    main()
