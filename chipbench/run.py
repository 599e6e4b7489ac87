"""The chip benchmark of the served speculative-decoding path.

    python3 chipbench/run.py --workload smollm-chat-open --seed 7 \
        --seconds 40 --trace 0

Run from the repository root on a machine with a TPU.  The cell names a
configuration (``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<mix>.json``) in ``BENCHMARK.json``.  A run:

1. refuses any platform but TPU, and fewer chips than the cell asks for
   (exit 3, no result);
2. builds the server through ``repro.launch.serve`` (``random_pair``,
   then ``build_server``) with weights from the seed, and pins the
   decode buffer to the mix's longest request so that no shape changes
   later (``SpecDecServer(min_buf_len=...)`` over the built engine);
3. warms up: serves prompts at every admission-bucket edge of the mix's
   length range, which compiles (or loads from JAX's persistent cache
   in ``<repo>/.jax_cache``) the fused round and every prefill bucket;
4. measures ``--seconds`` of the mix (``chipbench/traffic.py``),
   counting compilations inside the window (there should be none);
5. with ``--trace 1``, profiles a steady sub-window and reduces the
   trace (``chipbench/reduce.py``) to the cell's per-layer metrics,
   each read by ``chipbench/metrics/<name>.py``;
6. frees the server and checks every token of the finished requests
   (a seeded sample where there are more than the mix's
   ``check_requests``) against the plain reference
   (``chipbench/reference.py``): ``correct`` holds the share of checked
   tokens whose gap a logit error of the configuration's
   ``logit_tolerance`` does not explain to its
   ``mismatch_share_limit``.  ``--control bf16`` or ``quant`` runs the
   check's control instead, which must come out not correct.

The last line of standard output is one JSON object; the numbers
compared for ``correct`` end standard error and the result's line.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")

sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import counts, traffic  # noqa: E402

TRACE_AT = 0.25          # share of the window before the profiler starts,
                         # unless the mix sets its own ``trace_at``
TRACE_S = 2.0            # length of the traced sub-window


class NoChip(RuntimeError):
    pass


def load_bench(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(os.path.join(ROOT, path) if not os.path.isabs(path)
              else path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> tuple:
    """(workload entry, configuration entry) of cell ``name``."""
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return wl, cf


def metrics_of(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str, directory: str = os.path.join(HERE, "metrics")):
    """``read(ctx)`` of metric ``name``: ``metrics/<name>.py``, else
    ``metrics/<name before its first dot>.py``."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(directory, stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "chipbench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} in {directory}")


class CompileCounter:
    """Tracing, lowering and compilation events JAX reports
    (``/jax/core/compile/*``), counted while ``on``."""

    def __init__(self, jax):
        self.on = False
        self.events = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **_):
        if self.on and event.startswith("/jax/core/compile/"):
            self.events += 1
            self.seconds += duration


def model_config(c: dict, name: str):
    from repro.models.config import ModelConfig
    m = counts.model_dims(c)
    return ModelConfig(name=name, family="dense", num_layers=m.layers,
                       d_model=m.d_model, num_heads=m.heads,
                       num_kv_heads=m.kv_heads, head_dim=m.head_dim,
                       d_ff=m.d_ff, vocab_size=m.vocab,
                       rope_theta=float(c["rope_theta"]),
                       norm_eps=float(c["rms_norm_eps"]), dtype="float32")


def seeds(seed: int) -> dict:
    """Weight, race and check seeds drawn from the run seed (any size)."""
    s = np.random.SeedSequence(int(seed)).generate_state(3)
    return {"weights": int(s[0] >> 1), "race": int(s[1] >> 1),
            "check": int(s[2])}


def build(cfg: dict, slots: int, buf: int, weight_seed: int,
          quant: bool = False):
    """The served stack: ``random_pair`` then ``build_server``, with the
    decode buffer pinned to ``buf``.  ``quant`` turns on the program's
    W8A8 + int8-KV path (the output check's control)."""
    from repro.launch import serve
    from repro.specdec import CachedSpecDecEngine, SpecDecServer
    sv = cfg["serving"]
    argv = ["--cache-mode", sv["cache_mode"], "--backend",
            sv["verifier_backend"], "--strategy", sv["strategy"],
            "--drafts", str(sv["num_drafts"]), "--draft-len",
            str(sv["draft_len"]), "--max-batch", str(slots),
            "--admission", sv["admission"], "--tp", str(sv["tp"])]
    args = serve.parse_args(argv)
    pair = serve.random_pair(model_config(cfg, cfg["name"] + "-target"),
                             model_config(cfg["drafter"],
                                          cfg["name"] + "-drafter"),
                             seed=weight_seed, tp=int(sv["tp"]))
    eng = serve.build_server(args, pair).engine
    if quant:
        eng = CachedSpecDecEngine(pair[0], pair[1],
                                  dataclasses.replace(eng.cfg, quant=True),
                                  pool_slots=slots)
    return SpecDecServer(eng, max_batch=slots, cache_mode=sv["cache_mode"],
                         admission=sv["admission"], min_buf_len=buf)


def warm_lengths(lo: int, hi: int) -> list:
    """Prompt lengths n in [lo, hi] at every edge of a power-of-two
    admission bucketing: n - 1 (the prefilled tokens) or n - 2 has at
    most two set bits, plus both ends."""
    def edge(x):
        return x > 0 and bin(x).count("1") <= 2
    return sorted({lo, hi} | {n for n in range(lo, hi + 1)
                              if edge(n - 1) or edge(n - 2)})


def warm(server, mix: dict, key, vocab: int) -> int:
    """Serve one short request at every warm length; returns rounds."""
    rng = np.random.default_rng(0)
    for n in warm_lengths(*traffic.length_range(mix["prompt_len"])):
        server.submit(rng.integers(1, vocab, size=n).astype(np.int32),
                      max_new=2)
    rounds = 0
    while server.queue or server.live:
        server.step(key)
        rounds += 1
    return rounds


def device_info(jax, trace=None) -> dict:
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}
    if trace is not None:
        info["busy_s"] = trace.busy_s()
        info["window_s"] = trace.window_s
    return info


class Profiler:
    """Starts the profiler at ``at`` seconds into the window and stops
    it ``length`` seconds later (called before every step)."""

    def __init__(self, jax, logdir: str, t0_ref: list, at: float,
                 length: float):
        self.jax, self.logdir = jax, logdir
        self.t0_ref, self.at, self.length = t0_ref, at, length
        self.state = "idle"

    def __call__(self, now: float):
        t0 = self.t0_ref[0]
        if self.state == "idle" and now >= t0 + self.at:
            self.jax.profiler.start_trace(self.logdir)
            self.state = "on"
            self.started = now
        elif self.state == "on" and now >= self.started + self.length:
            self.stop()

    def stop(self):
        if self.state == "on":
            self.stopped = time.time()
            self.jax.profiler.stop_trace()
            self.state = "done"


def check_sample(window, n: int, seed: int) -> list:
    """Seeded sample of ``n`` finished requests, the longest among them."""
    done = window.finished()
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.request.prompt)
                  + len(r.request.output))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


# Logit tolerances at which every run also prints the unexplained share
# (the readings a limit and its tolerance are set from); the first is
# the plain gap.
TOLERANCES = (0.0, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2)


def tolerances(cfg: dict) -> tuple:
    t = float(cfg["check"]["logit_tolerance"])
    return TOLERANCES + ((t,) if t not in TOLERANCES else ())


def reference(cfg: dict, s: dict, buf: int, dtype=None):
    """The plain reference pair for the run's seed, at the
    configuration's ``reference_precision`` (in ``dtype``, float32
    unless told otherwise)."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import Reference, pair_keys
    sv, dc = cfg["serving"], cfg["drafter"]
    return Reference(
        counts.model_dims(cfg), counts.model_dims(dc), pair_keys(s["weights"]),
        jax.random.PRNGKey(s["race"]),
        t_norm=(float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])),
        d_norm=(float(dc["rms_norm_eps"]), float(dc["rope_theta"])),
        k=sv["num_drafts"], l=sv["draft_len"], top_k=sv["top_k"],
        seq_len=buf, dtype=dtype or jnp.float32, tolerances=tolerances(cfg),
        precision=jax.lax.Precision[
            cfg["check"]["reference_precision"].upper()])


def check(cfg: dict, sample: list, s: dict, buf: int,
          control: str = None) -> dict:
    """Readings of the sampled requests' served tokens against the plain
    reference (``chipbench/reference.py``): ``mismatch_share`` is the
    share of tokens whose gap a logit error of at most the
    configuration's ``logit_tolerance`` does not explain.  With
    ``control="bf16"`` the tokens judged are those the reference
    computed in bfloat16 puts first at each served position, in the
    program's place; the served tokens' own readings are kept under
    ``served``."""
    from chipbench.reference import walk
    l = cfg["serving"]["draft_len"]
    tols = tolerances(cfg)
    at = tols.index(float(cfg["check"]["logit_tolerance"]))
    ref = reference(cfg, s, buf)
    low = None
    if control == "bf16":
        import jax.numpy as jnp
        low = reference(cfg, s, buf, dtype=jnp.bfloat16)
    gaps, ctl, where = [], [], []
    accepted, bad_len = 0, 0
    for i, rec in enumerate(sample):
        req = rec.request
        if len(req.output) != req.max_new:
            bad_len += 1
        blocks, _ = traffic.blocks_of(req)
        g, _, acc = walk(ref.stats(req.prompt, req.output, blocks, req.uid),
                         req.output, blocks, l, tols)
        gaps.append(g)
        accepted += int(acc.sum())
        where += [(float(x), i, j, len(req.prompt))
                  for j, x in enumerate(g[0]) if x > 0]
        if low is not None:
            _, pick, _ = walk(low.stats(req.prompt, req.output, blocks,
                                        req.uid), req.output, blocks, l,
                              tols)
            g_ctl, _, _ = walk(ref.stats(req.prompt, req.output, blocks,
                                         req.uid, np.maximum(pick, 0)),
                               req.output, blocks, l, tols)
            g_ctl[:, pick < 0] = np.inf
            ctl.append(g_ctl)

    def readings(parts):
        if not parts:
            return {"mismatch_share": 100.0, "by_tolerance": {},
                    "max_gap": float("inf")}
        g = np.concatenate(parts, axis=1)
        share = 100.0 * np.mean(g > 0, axis=1)
        return {"mismatch_share": float(share[at]),
                "by_tolerance": {str(t): float(share[d])
                                 for d, t in enumerate(tols)},
                "max_gap": float(np.max(g[0]))}
    out = readings(gaps)
    out.update({"flipped": int(sum(int(np.sum(g[0] > 0)) for g in gaps)),
                "tokens_checked": int(sum(g.shape[1] for g in gaps)),
                "accepted_checked": accepted,
                "requests_checked": len(sample), "short_outputs": bad_len,
                "widest": sorted(where, reverse=True)[:12]})
    if low is not None:
        out["served"] = readings(gaps)
        out.update(readings(ctl))
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        bench: dict = None, require_tpu: bool = True, control: str = None,
        rate: float = None, traffic_dir: str = traffic.DIR) -> dict:
    """One run of a cell; returns the result line as a dict."""
    bench = bench or load_bench()
    wl, cf = find_cell(bench, workload)
    cfg = load_json(cf["file"])
    mix = traffic.load_mix(wl["traffic"], traffic_dir)
    if rate is not None:
        mix["arrival"]["rate_per_s"] = rate
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise NoChip(f"no program under {SRC}")
    sys.path.insert(0, SRC)
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
        if len(devs) < int(wl["chips"]):
            raise NoChip(f"the cell needs {wl['chips']} chips, found "
                         f"{len(devs)}")
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    peak = peaks.get(devs[0].device_kind)
    if peak is None:
        if require_tpu:
            raise NoChip(f"no peaks for device kind "
                         f"{devs[0].device_kind!r}")
        peak = next(iter(peaks.values()))  # tests off the chip

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter(jax)

    s = seeds(seed)
    sv = cfg["serving"]
    slots = int(mix["slots"])
    buf = traffic.buf_len(mix, sv["draft_len"])
    vocab = int(cfg["vocab_size"])
    if sv["matmul_precision"] != "default":
        jax.config.update("jax_default_matmul_precision",
                          sv["matmul_precision"])
    server = build(cfg, slots, buf, s["weights"], quant=control == "quant")
    key = jax.random.PRNGKey(s["race"])
    warm_rounds = warm(server, mix, key, vocab)
    planned = traffic.plan(mix, seed, seconds, vocab)
    m0 = dataclasses.replace(server.metrics)

    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace \
        else (lambda name: contextlib.nullcontext())
    prof = None
    t0_ref = [0.0]
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        prof = Profiler(jax, TRACE_DIR, t0_ref,
                        mix.get("trace_at", TRACE_AT) * seconds,
                        min(TRACE_S, seconds / 2))

    def on_step(now):
        if not t0_ref[0]:
            t0_ref[0] = now
        if prof is not None:
            prof(now)

    setup_s = time.time() - T_START
    compiles.on = True
    window = traffic.drive(server, mix, planned, key, seconds, span=span,
                           on_step=on_step)
    compiles.on = False
    if prof is not None:
        prof.stop()
    summ = traffic.summary(window)
    m = server.metrics
    counters = {f: getattr(m, f) - getattr(m0, f)
                for f in ("rounds", "total_tokens", "total_blocks",
                          "host_syncs", "draft_syncs")}
    tr = None
    if trace:
        from chipbench import reduce
        import glob
        paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        tr = reduce.Trace(reduce.load(paths[0]))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    info = device_info(jax, tr)

    t_dims = counts.model_dims(cfg)
    d_dims = counts.model_dims(cfg["drafter"])
    k, l = sv["num_drafts"], sv["draft_len"]
    per_round = [counts.round_flops(t_dims, d_dims, k, l, c)
                 for c in window.rounds]
    ctx = {"trace": tr, "summary": summ, "counters": counters,
           "peak": peak, "chips": int(wl["chips"]), "slots": slots,
           "K": k, "L": l, "vocab": vocab,
           "round_flops": float(np.mean(per_round)) if per_round else 0.0}
    if tr is not None:
        admitted = [r.request for r in window.records
                    if r.request.t_admit is not None
                    and prof.started <= r.request.t_admit < prof.stopped]
        ctx["admitted_in_trace"] = len(admitted)
        ctx["records"], ctx["trace_started"] = window.records, prof.started
        ctx["prefill_flops_in_trace"] = sum(
            counts.prefill_flops(t_dims, len(q.prompt) - 1)
            + counts.prefill_flops(d_dims, len(q.prompt) - 1)
            for q in admitted)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for spec in metrics_of(bench, workload, kind):
        name = spec["name"]
        if name == "setup_s":
            val = setup_s
        elif kind == "end_to_end":
            val = summ.get(name)
        else:
            val = reader(name)(ctx)
        if val is not None:
            metrics[name] = {"value": float(val), "unit": spec["unit"]}

    print(f"window: {summ['attempted']} requests attempted, "
          f"{summ['failed']} failed, {counters['rounds']} rounds, "
          f"{counters['total_tokens']} tokens; compiles in window: "
          f"{compiles.events} ({compiles.seconds:.3f} s); warm-up rounds "
          f"{warm_rounds}; buf_len {buf}; slots {slots}")
    print(f"generator lateness: median {summ['late_p50_ms']:.3f} ms, max "
          f"{summ['late_max_ms']:.3f} ms")
    print("summary: " + json.dumps(summ))
    if tr is not None:
        race = counts.round_race(slots, k, l, vocab)
        bound = ("memory" if race["bytes"] / peak["hbm_bytes_per_s"]
                 >= race["flops"] / peak["bf16_flops_per_s"] else "compute")
        print(f"trace: window {tr.window_s:.4f} s, busy {tr.busy_s():.4f} s"
              f", {ctx['admitted_in_trace']} admitted; gls_row_race is "
              f"{bound}-bound ({race['bytes']:.0f} B, {race['flops']:.0f} "
              f"FLOP a call); top ops {tr.top_ops(5)}")

    sample = check_sample(window, int(mix["check_requests"]), s["check"])
    del server, window, ctx
    gc.collect()
    jax.config.update("jax_default_matmul_precision", None)
    got = check(cfg, sample, s, buf,
                control="bf16" if control == "bf16" else None)
    limit = cfg["check"]["mismatch_share_limit"]
    correct = (got["mismatch_share"] <= limit and got["tokens_checked"] > 0
               and got["short_outputs"] == 0)
    print(f"check: {got['requests_checked']} requests, "
          f"{got['tokens_checked']} tokens ({got['accepted_checked']} "
          f"after an accepted draft), {got['flipped']} with a positive "
          f"gap, widest {got['max_gap']}; widest (gap, request, token, "
          f"prompt): {got['widest']}")
    print(f"check: unexplained share by logit tolerance: "
          f"{json.dumps(got['by_tolerance'])}")
    if "served" in got:
        print(f"check: control {control}: mismatch_share "
              f"{got['mismatch_share']} (widest {got['max_gap']}); the "
              f"served tokens' own: {json.dumps(got['served'])}")
    compared = {"mismatch_share": {"value": got["mismatch_share"],
                                   "limit": limit},
                "tokens_checked": {"value": got["tokens_checked"],
                                   "limit": 1},
                "accepted_checked": {"value": got["accepted_checked"],
                                     "limit": 0},
                "short_outputs": {"value": got["short_outputs"],
                                  "limit": 0}}
    out = {"correct": bool(correct), "attempted": summ["attempted"],
           "failed": summ["failed"], "metrics": metrics, "device": info}
    if tr is not None:
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(10)}
    out["check"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the mix's arrival rate (the knee sweep)")
    ap.add_argument("--control", choices=("bf16", "quant"), default=None,
                    help="the output check's control, which must fail: "
                         "bf16 judges the tokens the reference computed in "
                         "bfloat16 puts first; quant serves through the "
                         "program's W8A8 + int8-KV path")
    opts = ap.parse_args(argv)
    try:
        out = run(opts.workload, opts.seed, opts.seconds, bool(opts.trace),
                  control=opts.control, rate=opts.rate)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for name, v in out["check"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
