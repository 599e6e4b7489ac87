"""Smoke test of the served path on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # the tensor-parallel phase, four chips

One chip: SmolLM-360M (target) and SmolLM-135M (drafter) at their
published widths in float32, with seeded random weights, serve 8
requests (prompts of 16-128 tokens, 32 new tokens each) through
``launch/serve.py``'s own construction — ``SpecDecServer`` →
``CachedSpecDecEngine`` → fused round → cache pool — with
``cache_mode="kv_fused"``, K=8, L=4, 8 slots and the GLS strategy,
three times: (a) the Pallas race verifier, (b) the XLA verifier,
(c) the paged arena with the Pallas verifier.  The three token streams
must be identical (the race kernel equals its reference bit for bit,
and paged equals contiguous), every request must complete with
in-vocabulary tokens, no draft token may cost a host sync, each round
must cost exactly one, and the lowered fused round of (a) must hold a
compiled Pallas kernel (``tpu_custom_call``).  With random weights the
135M drafter's drafts are never accepted, so (d)-(f) repeat (a)-(c)
with the 360M target drafting for itself: drafts are then accepted
(block efficiency must exceed 1), which drives the accept, rollback and
multi-token commit paths, and the three streams must again agree.

``--tp 4`` runs only the tensor-parallel phase: the full 36-layer
granite-8b target, its weights created already sharded, serves a few
requests at tp=4 with a 2-layer drafter, each device's peak below its
memory; then granite-8b widths cut to 4 target layers at tp=4 must emit
the tp=1 tokens for ``gls`` and ``specinfer``, and tp=1 and tp=4
engines stepped in lockstep must hold bit-equal KV arenas after
admission and after every round (the first difference is reported by
round, model, layer and row).

Any failed check raises; the script exits non-zero and prints no result.
The last line of a passing run is one JSON object naming the device.
The script needs the repository around it (``src/``) and a TPU: JAX on
another platform is refused rather than measured.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# The one-chip run: the SmolLM pair at published widths.
ONE_CHIP = ["--target", "smollm-360m", "--drafter", "smollm-135m",
            "--init-seed", "0", "--cache-mode", "kv_fused",
            "--strategy", "gls", "--drafts", "8", "--draft-len", "4",
            "--max-batch", "8", "--max-new", "32", "--requests", "8"]
BACKENDS = (("pallas", ["--backend", "pallas"]),
            ("xla", ["--backend", "xla"]),
            ("paged+pallas", ["--backend", "pallas", "--paged"]))


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def serve_once(serve, argv, pair, prompts, clock, jax):
    """Serve ``prompts`` through ``launch/serve.py``'s construction and
    check the served-path contracts; returns (tokens per request,
    server, stats)."""
    args = serve.parse_args(argv)
    server = serve.build_server(args, pair)
    for p in prompts:
        server.submit(p, max_new=args.max_new)
    c0, t0 = clock.compile_seconds(), time.perf_counter()
    done = server.run(jax.random.PRNGKey(0))
    wall = time.perf_counter() - t0
    m = server.metrics
    vocab = pair[0][1].vocab_size
    check(len(done) == len(prompts) and not server.failed,
          f"{len(done)}/{len(prompts)} requests completed, "
          f"{len(server.failed)} failed")
    outs = [list(r.output) for r in sorted(done, key=lambda r: r.uid)]
    for o in outs:
        check(len(o) == args.max_new, f"request emitted {len(o)} tokens")
        check(all(0 <= t < vocab for t in o), "token outside the vocabulary")
    check(m.draft_syncs == 0, f"draft_syncs={m.draft_syncs}")
    check(m.host_syncs == m.rounds,
          f"host_syncs={m.host_syncs} over {m.rounds} rounds")
    be = sum(r.block_efficiency for r in done) / len(done)
    stats = {"compile_s": clock.compile_seconds() - c0, "wall_s": wall,
             "rounds": m.rounds, "tokens": m.total_tokens,
             "block_efficiency": be}
    return outs, server, stats


def peak_bytes(jax):
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def fused_round_hlo(eng, jnp):
    """StableHLO text of the engine's fused round, lowered for the
    arenas it serves from."""
    pool = eng.pool
    s = pool.num_slots
    return eng._fused_round.lower(
        eng._t_verify_params, eng.d_params, pool.caches["target"],
        pool.caches["drafter"], pool.pos_device(),
        jnp.zeros((s,), jnp.int32), jnp.zeros((s,), bool),
        jnp.zeros((s, 2), jnp.uint32)).as_text()


def serve_backends(serve, pair, prompts, clock, jax, jnp, labels):
    """Serve ``prompts`` once per verifier backend in ``BACKENDS``;
    require equal token streams.  Returns the per-run stats."""
    tokens, stats = {}, []
    for label, (name, extra) in zip(labels, BACKENDS):
        outs, server, st = serve_once(serve, ONE_CHIP + extra, pair,
                                      prompts, clock, jax)
        tokens[name] = outs
        stats.append(st)
        print(f"run {label}: {name}: compile {st['compile_s']:.3f} s, "
              f"wall {st['wall_s']:.3f} s, rounds {st['rounds']}, tokens "
              f"{st['tokens']}, block efficiency "
              f"{st['block_efficiency']:.4f}, peak_bytes_in_use "
              f"{peak_bytes(jax)[0]}, draft_syncs 0, "
              f"host_syncs == rounds")
        if name == "pallas":
            hlo = fused_round_hlo(server.engine, jnp)
            check("tpu_custom_call" in hlo,
                  "the fused round holds no compiled Pallas kernel")
            print(f"fused round ({label}): tpu_custom_call present "
                  "(compiled gls race kernel)")
        del server
        gc.collect()
    first = BACKENDS[0][0]
    for name, _ in BACKENDS[1:]:
        check(tokens[name] == tokens[first],
              f"tokens of {name} differ from {first}")
    print(f"tokens ({labels[0]}-{labels[-1]}): pallas == xla == paged for "
          f"all {len(prompts)} requests")
    return stats


def one_chip(serve, clock, jax, jnp):
    from repro.kernels.pallas_mode import resolve_pallas_mode
    check(resolve_pallas_mode(None) == "compiled",
          "Pallas kernels would not compile on this backend")
    pair = serve.load_pair(serve.parse_args(ONE_CHIP))
    prompts = serve.random_prompts(8, pair[0][1].vocab_size, seed=0)
    print(f"prompt lengths: {[len(p) for p in prompts]}")
    serve_backends(serve, pair, prompts, clock, jax, jnp, "abc")
    # The target drafting for itself: its drafts are accepted.
    print("target as its own drafter (smollm-360m + smollm-360m):")
    stats = serve_backends(serve, (pair[0], pair[0]), prompts, clock, jax,
                           jnp, "def")
    for st in stats:
        check(st["block_efficiency"] > 1.0,
              f"self-drafted block efficiency {st['block_efficiency']} "
              "<= 1: no draft was accepted")


def first_differences(a, b):
    """[(request, first differing token index)] over per-request token
    lists ``a`` and ``b``."""
    return [(i, next(j for j, (x, y) in enumerate(zip(ra, rb)) if x != y))
            for i, (ra, rb) in enumerate(zip(a, b)) if ra != rb]


def arena_differences(engines):
    """Where the KV arenas of two engines differ: [(model, leaf, layer,
    rows that differ, max |diff|)] for every layer with an unequal
    element."""
    import numpy as np
    a, b = (e.pool.caches for e in engines)
    out = []
    for model in a:
        for leaf in a[model]:
            x, y = np.asarray(a[model][leaf]), np.asarray(b[model][leaf])
            for layer in range(x.shape[0]):
                ne = x[layer] != y[layer]
                if ne.any():
                    out.append((model, leaf, layer,
                                int(ne.any(axis=(1, 2, 3)).sum()),
                                float(np.abs(x[layer] - y[layer]).max())))
    return out


def lockstep(serve, cut, drafter, prompts, base, tp, max_new, jax,
             served):
    """Step a tp=1 and a tp=``tp`` engine through the same gls rounds
    (the scheduler's uids, buffer and (uid, blocks) keys; every request
    live from the start) and compare their KV arenas after admission
    and after every round, to tell where the two first part: in the
    sharded admission prefill or in a round.  Prints the first arena
    difference and the first token difference (and whether the tp=1
    stream is the one ``served`` through the scheduler); returns the
    token difference as text, or None when the tokens agree."""
    import numpy as np
    engines = []
    for n in (1, tp):
        pair = serve.random_pair(cut, drafter, seed=0, tp=n)
        args = serve.parse_args(base + ["--strategy", "gls", "--tp", str(n)])
        engines.append(serve.build_server(args, pair).engine)
    L = engines[0].cfg.draft_len
    buf = max(len(p) for p in prompts) + max_new + L + 2
    uids = list(range(1, len(prompts) + 1))
    for e in engines:
        e.admit_batch(list(zip(uids, prompts)), buf)
    tag = f"lockstep tp=1 vs tp={tp}"
    diff = arena_differences(engines)
    print(f"{tag}: arenas after admission "
          f"{'differ (model, leaf, layer, rows, max|diff|): ' + str(diff) if diff else 'equal'}")
    arena_seen, parted = bool(diff), None
    key = jax.random.PRNGKey(0)
    seqs = [[list(p) for p in prompts] for _ in engines]
    for r in range(max_new):
        live = [i for i in range(len(uids))
                if len(seqs[0][i]) - len(prompts[i]) < max_new]
        if not live:
            break
        subs = [jax.random.fold_in(jax.random.fold_in(key, uids[i]), r)
                for i in live]
        for e, seq in zip(engines, seqs):
            outs = e.gen_blocks(subs, [np.asarray(seq[i], np.int32)
                                       for i in live], buf,
                                uids=[uids[i] for i in live], fused=True)
            for i, o in zip(live, outs):
                seq[i].extend(o.new_tokens)
        if not arena_seen:
            diff = arena_differences(engines)
            if diff:
                arena_seen = True
                print(f"{tag}: arenas first differ after round {r + 1} "
                      f"(model, leaf, layer, rows, max|diff|): {diff}")
        if parted is None and seqs[0] != seqs[1]:
            parted = (f"tokens first differ in round {r + 1} (request, "
                      f"position incl. prompt): "
                      f"{first_differences(seqs[0], seqs[1])}")
            print(f"{tag}: {parted}")
    ours = [q[len(p):len(p) + max_new] for q, p in zip(seqs[0], prompts)]
    print(f"{tag}: lockstep tp=1 tokens == served tp=1 tokens: "
          f"{ours == served}")
    if not arena_seen:
        print(f"{tag}: arenas equal after every round")
    if parted is None:
        print(f"{tag}: tokens equal after every round")
    return parted


def tensor_parallel(serve, clock, jax, jnp, tp):
    from repro.configs import get_config
    check(jax.device_count() >= tp,
          f"--tp {tp} needs {tp} devices, found {jax.device_count()}")
    full = get_config("granite-8b").replace(dtype="float32")
    drafter = full.replace(name="granite-8b-drafter", num_layers=2)
    cut = full.replace(name="granite-8b-4l", num_layers=4)
    prompts = serve.random_prompts(4, full.vocab_size, seed=0)
    base = ["--cache-mode", "kv_fused", "--backend", "pallas",
            "--drafts", "8", "--draft-len", "4", "--max-batch", "4",
            "--max-new", "16"]
    print(f"prompt lengths: {[len(p) for p in prompts]}")

    # Full depth first, so each device's peak is the full model's.
    pair = serve.random_pair(full, drafter, seed=0, tp=tp)
    _, server, st = serve_once(serve, base + ["--strategy", "gls", "--tp",
                                              str(tp)],
                               pair, prompts, clock, jax)
    print(f"granite-8b (36 layers) tp={tp}: {len(prompts)} requests, "
          f"compile {st['compile_s']:.3f} s, wall {st['wall_s']:.3f} s, "
          f"rounds {st['rounds']}, tokens {st['tokens']}, block "
          f"efficiency {st['block_efficiency']:.4f}")
    peaks = peak_bytes(jax)[:tp]
    print(f"peak_bytes_in_use per device: {peaks}")
    for d, p in zip(jax.devices()[:tp], peaks):
        limit = (d.memory_stats() or {}).get("bytes_limit")
        check(p is not None and limit is not None and p < limit,
              f"device {d.id}: peak {p} not below its limit {limit}")
    del server, pair
    gc.collect()

    differ = []
    for strategy in ("gls", "specinfer"):
        tokens = {}
        for n in (1, tp):
            pair = serve.random_pair(cut, drafter, seed=0, tp=n)
            outs, server, st = serve_once(
                serve, base + ["--strategy", strategy, "--tp", str(n)],
                pair, prompts, clock, jax)
            tokens[n] = outs
            print(f"{strategy} tp={n} (4+2 layers): compile "
                  f"{st['compile_s']:.3f} s, wall {st['wall_s']:.3f} s, "
                  f"rounds {st['rounds']}, block efficiency "
                  f"{st['block_efficiency']:.4f}")
            del server, pair
            gc.collect()
        if strategy == "gls":
            served_gls = tokens[1]
        diff = first_differences(tokens[1], tokens[tp])
        if diff:
            differ.append(strategy)
            print(f"{strategy}: tp={tp} tokens differ from tp=1 in "
                  f"{len(diff)}/{len(prompts)} requests; first differing "
                  f"(request, token): {diff}")
        else:
            print(f"{strategy}: tp={tp} tokens == tp=1 tokens")
    parted = lockstep(serve, cut, drafter, prompts, base, tp, 16, jax,
                      served_gls)
    check(not differ, f"tp={tp} tokens differ from tp=1 for {differ}")
    check(parted is None, f"tp={tp} lockstep: {parted}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, default=1, choices=(1, 4),
                    help="4: run only the tensor-parallel phase on four "
                         "chips")
    opts = ap.parse_args()
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no repro package under {src}: run "
                           "chip_smoke.py from the repository root")
    sys.path.insert(0, src)

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX runs on {dev.platform!r} ({dev.device_kind})")
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")

    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    # Seconds JAX spends tracing, lowering and compiling, summed from
    # its ``/jax/core/compile/*`` events by the serving spans' recorder.
    from repro.serving import spans as clock
    if opts.tp == 1:
        one_chip(serve, clock, jax, jnp)
    else:
        tensor_parallel(serve, clock, jax, jnp, opts.tp)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
