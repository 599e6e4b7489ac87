"""Serving-path benchmark: fused verification backends, the batched
scheduler, and bursty admission.

Three comparisons the serving refactor is accountable for:

  * verifier backends — "legacy" (per-token host loop, 2 syncs/token) vs
    "xla" (one jitted block) vs "pallas" (block race through the
    kernels/gls_race row kernel): tokens/s and verification host-sync
    counts on the same trained pair;
  * scheduler paths — sequential (R target forwards per round, full-
    prefix re-score) vs batched (ONE (R*K, T) re-score forward per
    round) vs kv (persistent KV caches in a multi-request slot pool —
    one drafter decode sweep plus ONE stacked verify_step per round, no
    re-prefill) vs kv_fused (the whole round as ONE jitted device
    program, DESIGN.md §8 — 0 draft syncs, 1 host sync per round):
    tokens/s at R=4 live requests, forwards per round, sync counts, and
    output-equality checks (all paths must be bit-identical to the
    sequential reference mode).  CI gates on
    ``kv_fused_speedup_vs_kv >= 1`` — a fused round slower than the
    host-driven round is a regression;
  * admission paths (DESIGN.md §9) — a bursty wave of queued requests
    with MIXED prompt lengths admitted ``per_request`` (one request per
    admission wave: 2 arena-wide prefill dispatches per request, not
    overlapped with the round) vs ``bucketed`` (the whole queue in one
    wave, one stacked dispatch per bucket per model, overlapped with the
    running kv_fused round): per-request ``ttft_ms``, mean-TTFT ratio,
    prefill dispatch counts, and a bit-identity check.  Both policies
    run the same bucketed prefill program, so neither compiles per
    prompt length; the warm corpus uses DIFFERENT prompt lengths in
    the same buckets, so the measured pass compiles nothing.  What the
    TTFT ratio measures is wave batching and overlap alone, on the
    host's clock (reported, not gated).

Two §11 additions ride along in the payload:

  * ``quant`` — kv_fused tokens/s with f32 arenas vs the int8 KV arena
    + W8A8 verify path, and per-strategy acceptance-rate deltas across
    all six strategies (the quant ship gate: CI fails on a delta beyond
    statistical tolerance, NOT on logit drift);
  * ``race_dispatches`` — trace-time race-kernel dispatch counts per
    fused round, per strategy (kernels/gls_race/ops.py counters).

``collect()`` returns the JSON payload CI archives as BENCH_specdec.json.
"""

from __future__ import annotations

import os

import jax
import numpy as np

from benchmarks.bench_table1_iid_drafts import collect as table1_collect
from benchmarks.common import emit
from benchmarks.lm_pair import bench_prompts, get_pair
from repro.specdec import (
    RACE_STRATEGIES,
    RS_STRATEGIES,
    CachedSpecDecEngine,
    SpecDecConfig,
    SpecDecEngine,
    SpecDecServer,
)

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

L = 4
MAX_NEW = 32
SCHED_BATCH = 4   # R: live requests per round in the scheduler bench

# Bursty-admission scenario: >= 8 queued requests, mixed prompt lengths
# straddling the admission buckets.  Warm lengths deliberately differ
# from measured lengths while hitting the same buckets.
ADMIT_BATCH = 8
ADMIT_LENS_WARM = (5, 23, 14, 37, 9, 18, 29, 47, 7, 26, 12, 41)
ADMIT_LENS_MEAS = (6, 24, 15, 38, 10, 19, 30, 46, 8, 27, 13, 40)


def _mixed_prompts(lens):
    base = bench_prompts(len(lens), length=max(lens) + 1)
    return [p[:n] for p, n in zip(base, lens)]


def _bench_admission(target, drafter, *, max_new=MAX_NEW):
    """Bursty-admission TTFT: per_request vs bucketed admission under
    cache_mode="kv_fused".  Returns per-request ttft_ms, means, prefill
    dispatch counts, and the bit-identity verdict."""
    sd = SpecDecConfig(num_drafts=4, draft_len=L, strategy="gls",
                       top_k=50, max_new_tokens=max_new)
    out = {}
    outputs = {}
    for admission in ("per_request", "bucketed"):
        eng = CachedSpecDecEngine(target, drafter, sd,
                                  pool_slots=ADMIT_BATCH)

        def serve(corpus):
            srv = SpecDecServer(eng, max_batch=ADMIT_BATCH,
                                cache_mode="kv_fused", admission=admission)
            for p in corpus:
                srv.submit(p, max_new=max_new)
            done = srv.run(jax.random.PRNGKey(11))
            return srv, done

        # Warm pass: compiles the fused round and the bucket set's
        # prefill shapes; the measured lengths are fresh but fall in
        # the same buckets, so neither policy compiles while measured.
        serve(_mixed_prompts(ADMIT_LENS_WARM))
        pd0 = eng.num_prefill_dispatches
        srv, done = serve(_mixed_prompts(ADMIT_LENS_MEAS))
        ttfts = {r.uid: r.ttft_ms for r in done}
        out[admission] = {
            "mean_ttft_ms": float(np.mean(list(ttfts.values()))),
            "max_ttft_ms": float(np.max(list(ttfts.values()))),
            "ttft_ms": {str(u): float(v) for u, v in sorted(ttfts.items())},
            "tokens_per_s": srv.metrics.tokens_per_s,
            "prefill_dispatches": eng.num_prefill_dispatches - pd0,
        }
        outputs[admission] = {r.uid: list(r.output) for r in done}
    out["queued_requests"] = len(ADMIT_LENS_MEAS)
    out["prompt_lens"] = list(ADMIT_LENS_MEAS)
    out["bit_identical"] = outputs["bucketed"] == outputs["per_request"]
    out["ttft_improvement"] = (
        out["per_request"]["mean_ttft_ms"]
        / max(out["bucketed"]["mean_ttft_ms"], 1e-9))
    return out


def _bench_backends(*, k=8, max_new=MAX_NEW, n_prompts=3):
    rows = []
    for backend in ("legacy", "xla", "pallas"):
        rows.extend(table1_collect(
            ks=(k,), strategies=("gls",), backend=backend,
            max_new=max_new, n_prompts=n_prompts))
    return rows


def _bench_scheduler(target, drafter, *, n_requests=8, max_new=MAX_NEW):
    corpus = bench_prompts(n_requests, length=12)
    sd = SpecDecConfig(num_drafts=4, draft_len=L, strategy="gls",
                       top_k=50, max_new_tokens=max_new)
    out = {}
    outputs = {}
    for mode in ("sequential", "batched", "kv", "kv_fused"):
        if mode in ("kv", "kv_fused"):
            eng = CachedSpecDecEngine(target, drafter, sd,
                                      pool_slots=SCHED_BATCH)
        else:
            eng = SpecDecEngine(target, [drafter], sd)

        def make_server():
            return SpecDecServer(eng, max_batch=SCHED_BATCH,
                                 batched=mode == "batched",
                                 cache_mode=mode if mode.startswith("kv")
                                 else "reprefill")

        # Warmup pass compiles this mode's forwards so the measured run
        # reports steady-state tokens/s, not jit tracing time.
        warm = make_server()
        for p in corpus[:SCHED_BATCH]:
            warm.submit(p, max_new=max_new)
        warm.run(jax.random.PRNGKey(3))

        server = make_server()
        for p in corpus:
            server.submit(p, max_new=max_new)
        done = server.run(jax.random.PRNGKey(7))
        m = server.metrics
        out[mode] = {
            "tokens_per_s": m.tokens_per_s,
            "mean_block_efficiency": m.mean_block_efficiency,
            "rounds": m.rounds,
            "target_forwards": m.target_forwards,
            "host_syncs": m.host_syncs,
            "draft_syncs": m.draft_syncs,
        }
        outputs[mode] = {r.uid: list(r.output) for r in done}
    out["live_requests"] = SCHED_BATCH
    out["bit_identical"] = {
        mode: outputs["sequential"] == outputs[mode]
        for mode in ("batched", "kv", "kv_fused")}
    out["kv_speedup_vs_reprefill"] = (
        out["kv"]["tokens_per_s"] / max(out["sequential"]["tokens_per_s"],
                                        1e-9))
    out["kv_fused_speedup_vs_kv"] = (
        out["kv_fused"]["tokens_per_s"] / max(out["kv"]["tokens_per_s"],
                                              1e-9))
    return out


def _bench_quant(target, drafter, *, n_requests=8, max_new=MAX_NEW):
    """Quantized serving (DESIGN.md §11): kv_fused tokens/s with the f32
    arenas vs the int8 KV arena + W8A8 verify path, plus the gate that
    decides whether quant ships — per-strategy acceptance-rate deltas
    (quantization moves logits by design; acceptance is the coupling
    statistic the paper cares about)."""
    corpus = bench_prompts(n_requests, length=12)
    out = {}
    for tag, quant in (("f32", False), ("int8", True)):
        sd = SpecDecConfig(num_drafts=4, draft_len=L, strategy="gls",
                           top_k=50, max_new_tokens=max_new, quant=quant)
        eng = CachedSpecDecEngine(target, drafter, sd,
                                  pool_slots=SCHED_BATCH)

        def make_server():
            return SpecDecServer(eng, max_batch=SCHED_BATCH,
                                 cache_mode="kv_fused")

        warm = make_server()
        for p in corpus[:SCHED_BATCH]:
            warm.submit(p, max_new=max_new)
        warm.run(jax.random.PRNGKey(3))
        server = make_server()
        for p in corpus:
            server.submit(p, max_new=max_new)
        server.run(jax.random.PRNGKey(7))
        out[tag] = {"tokens_per_s": server.metrics.tokens_per_s}
    out["quant_speedup"] = (out["int8"]["tokens_per_s"]
                            / max(out["f32"]["tokens_per_s"], 1e-9))

    accept = {}
    for strategy in RACE_STRATEGIES + RS_STRATEGIES:
        rates = {}
        for tag, quant in (("f32", False), ("int8", True)):
            sd = SpecDecConfig(num_drafts=4, draft_len=L,
                               strategy=strategy, top_k=50,
                               max_new_tokens=max_new, quant=quant)
            eng = CachedSpecDecEngine(target, drafter, sd, pool_slots=1)
            acc = blocks = 0
            for seed in (5, 6):   # shared keys across tags: the residual
                st = eng.generate(jax.random.PRNGKey(seed), corpus[0],
                                  max_new=max_new, fused=True)
                acc += st.accepted_drafts
                blocks += st.blocks
            rates[tag] = acc / (blocks * L)
        accept[strategy] = {**rates,
                            "delta": rates["int8"] - rates["f32"]}
    out["acceptance"] = accept
    out["max_acceptance_delta"] = float(
        max(abs(v["delta"]) for v in accept.values()))
    return out


def _tp_payload(max_new: int = MAX_NEW, n_requests: int = 4):
    """tp=1 vs tp=2 kv_fused serving rows.  Needs a process with two or
    more jax devices (`_bench_tp`): the sharded round spans devices, and
    device count is locked at first init."""
    target, drafter = get_pair()
    corpus = bench_prompts(n_requests, length=12)
    out = {}
    outputs = {}
    for tp in (1, 2):
        sd = SpecDecConfig(num_drafts=4, draft_len=L, strategy="gls",
                           top_k=50, max_new_tokens=max_new, tp=tp)
        eng = CachedSpecDecEngine(target, drafter, sd,
                                  pool_slots=SCHED_BATCH)

        def make_server():
            return SpecDecServer(eng, max_batch=SCHED_BATCH,
                                 cache_mode="kv_fused")

        warm = make_server()
        for p in corpus[:SCHED_BATCH]:
            warm.submit(p, max_new=max_new)
        warm.run(jax.random.PRNGKey(3))
        server = make_server()
        for p in corpus:
            server.submit(p, max_new=max_new)
        done = server.run(jax.random.PRNGKey(7))
        m = server.metrics
        out[f"tp{tp}"] = {
            "tokens_per_s": m.tokens_per_s,
            "rounds": m.rounds,
            "target_forwards": m.target_forwards,
            "host_syncs": m.host_syncs,
            "draft_syncs": m.draft_syncs,
        }
        outputs[tp] = {r.uid: list(r.output) for r in done}
    out["bit_identical"] = outputs[1] == outputs[2]
    out["devices"] = jax.device_count()
    return out


def _bench_tp(*, max_new=MAX_NEW):
    """Sharded fused round (DESIGN.md §15): tokens/s and dispatch counts
    at tp=2 next to tp=1, plus the bit-identity verdict — REPORTED, not
    gated (the equivalence gate lives in tests/test_sharded_round.py).

    With two or more devices in this process it runs here.  A process
    that holds one accelerator cannot hand it to a child, so there it
    raises.  On the CPU backend with one device it runs in a child
    process with 8 simulated host devices (CPU only: the collectives are
    memcpys, so the tokens/s delta is pure sharding overhead).  A failed
    run raises."""
    if jax.device_count() >= 2:
        return _tp_payload(max_new=max_new)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "the tp bench needs two or more devices in this process; a "
            "child process cannot share this process's accelerator")
    import json as _json
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        sys.path.insert(0, ".")
        import json
        from benchmarks.bench_serving_backends import _tp_payload
        print("TP_BENCH_JSON " + json.dumps(_tp_payload(max_new={max_new})))
    """)
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # the script pins its own device count
    proc = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                          capture_output=True, text=True, timeout=580,
                          env=env)
    for line in proc.stdout.splitlines():
        if line.startswith("TP_BENCH_JSON "):
            return _json.loads(line[len("TP_BENCH_JSON "):])
    raise RuntimeError("tp bench child process failed:\n"
                       + (proc.stderr[-2000:] or proc.stdout[-2000:]))


def _race_dispatch_counts(target, drafter, *, max_new=16):
    """Per-round race-kernel dispatch structure per strategy: trace-time
    counters from kernels/gls_race/ops.py over one fused-engine
    generation (each engine retraces its own round program, so the
    counts are the round's embedded dispatches).  The pallas verifier
    backend is pinned — it is the one that routes through the race ops;
    RS strategies embed no race dispatch on any backend, which the
    empty counters document."""
    from repro.kernels.gls_race import ops
    prompt = bench_prompts(1, length=12)[0]
    counts = {}
    for strategy in RACE_STRATEGIES + RS_STRATEGIES:
        sd = SpecDecConfig(num_drafts=4, draft_len=L, strategy=strategy,
                           top_k=50, max_new_tokens=max_new,
                           verifier_backend="pallas")
        eng = CachedSpecDecEngine(target, drafter, sd, pool_slots=1)
        ops.reset_dispatch_counts()
        st = eng.generate(jax.random.PRNGKey(9), prompt,
                          max_new=max_new, fused=True)
        counts[strategy] = {"per_round": dict(ops.dispatch_counts),
                            "rounds": st.blocks}
    return counts


def collect(fast: bool = True):
    """BENCH_specdec.json payload: BE + tokens/s for gls vs specinfer vs
    spectr at K in {2, 8}, backend deltas, scheduler path deltas."""
    target, drafter = get_pair()   # trains once; later calls hit the cache
    max_new = MAX_NEW if fast else 48
    strat_rows = table1_collect(ks=(2, 8),
                                strategies=("gls", "specinfer", "spectr"),
                                max_new=max_new)
    strategies = {}
    for r in strat_rows:
        strategies.setdefault(r["strategy"], {})[f"K{r['K']}"] = {
            "block_efficiency": r["block_efficiency"],
            "tokens_per_s": r["tokens_per_s"],
        }
    return {
        "draft_len": L,
        "max_new_tokens": max_new,
        "strategies": strategies,
        "verifier_backends": _bench_backends(max_new=max_new),
        "scheduler": _bench_scheduler(target, drafter, max_new=max_new),
        "admission": _bench_admission(target, drafter, max_new=max_new),
        "quant": _bench_quant(target, drafter, max_new=max_new),
        "race_dispatches": _race_dispatch_counts(target, drafter),
        "tp": _bench_tp(max_new=max_new),
    }


def run(fast: bool = False):
    payload = collect(fast=fast)
    for r in payload["verifier_backends"]:
        emit(f"serve_backend_{r['backend']}_gls_K{r['K']}",
             r["us_per_prompt"],
             f"tok_s={r['tokens_per_s']:.1f};host_syncs={r['host_syncs']};"
             f"BE={r['block_efficiency']:.3f}")
    sched = payload["scheduler"]
    for mode in ("sequential", "batched", "kv", "kv_fused"):
        m = sched[mode]
        emit(f"scheduler_{mode}", 0.0,
             f"tok_s={m['tokens_per_s']:.1f};rounds={m['rounds']};"
             f"target_forwards={m['target_forwards']};"
             f"host_syncs={m['host_syncs']};"
             f"draft_syncs={m['draft_syncs']}")
    emit("scheduler_paths_bit_identical", 0.0,
         str(sched["bit_identical"]))
    emit("scheduler_kv_speedup_vs_reprefill", 0.0,
         f"{sched['kv_speedup_vs_reprefill']:.2f}x")
    emit("scheduler_kv_fused_speedup_vs_kv", 0.0,
         f"{sched['kv_fused_speedup_vs_kv']:.2f}x")
    adm = payload["admission"]
    for pol in ("per_request", "bucketed"):
        a = adm[pol]
        emit(f"admission_{pol}", a["mean_ttft_ms"] * 1e3,
             f"mean_ttft_ms={a['mean_ttft_ms']:.1f};"
             f"max_ttft_ms={a['max_ttft_ms']:.1f};"
             f"tok_s={a['tokens_per_s']:.1f};"
             f"prefill_dispatches={a['prefill_dispatches']}")
    emit("admission_bit_identical", 0.0, str(adm["bit_identical"]))
    emit("admission_ttft_improvement", 0.0,
         f"{adm['ttft_improvement']:.2f}x")
    qn = payload["quant"]
    emit("serving_quant_kv_fused", 0.0,
         f"f32_tok_s={qn['f32']['tokens_per_s']:.1f};"
         f"int8_tok_s={qn['int8']['tokens_per_s']:.1f};"
         f"speedup={qn['quant_speedup']:.2f}x;"
         f"max_accept_delta={qn['max_acceptance_delta']:.3f}")
    for strategy, rd in payload["race_dispatches"].items():
        emit(f"race_dispatches_{strategy}", 0.0,
             f"per_round={rd['per_round']};rounds={rd['rounds']}")
    tpb = payload["tp"]
    if "tp2" in tpb:
        for tag in ("tp1", "tp2"):
            r = tpb[tag]
            emit(f"serving_{tag}_simulated_mesh", 0.0,
                 f"tok_s={r['tokens_per_s']:.1f};rounds={r['rounds']};"
                 f"target_forwards={r['target_forwards']};"
                 f"host_syncs={r['host_syncs']};"
                 f"draft_syncs={r['draft_syncs']}")
        emit("serving_tp_bit_identical", 0.0, str(tpb["bit_identical"]))
    else:
        emit("serving_tp_bench", 0.0,
             f"error={tpb.get('error', 'unknown')[:120]}")
    return payload


if __name__ == "__main__":
    run()
