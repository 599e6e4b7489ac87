"""Spans and counters of the serving loop (DESIGN.md §16): the recorder
(nesting, attrs, the ring's bound, compile events, the reserved
``bench.`` prefix), the span tree one kv_fused admission wave writes
with its counts, and the program names the chip benchmark finds the
device work by."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, init_params
from repro.serving import spans
from repro.specdec import CachedSpecDecEngine, SpecDecConfig, SpecDecServer
from repro.specdec.engine_cached import _bucket_plan, _max_bucket

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src", "repro")

TCFG = ModelConfig(name="t", family="dense", num_layers=2, d_model=48,
                   num_heads=4, num_kv_heads=2, head_dim=12, d_ff=96,
                   vocab_size=32, dtype="float32")
DCFG = TCFG.replace(name="d", num_layers=1)


def test_nesting_parents_and_attrs():
    rec = spans.Recorder()
    with rec.span("outer", a=1) as at:
        with rec.span("inner"):
            pass
        at["b"] = 2
    with rec.span("next"):
        pass
    inner, outer, nxt = rec.records()
    assert [r.name for r in (inner, outer, nxt)] == ["inner", "outer",
                                                     "next"]
    assert inner.parent == outer.id and outer.parent is None
    assert nxt.parent is None
    assert outer.attrs == {"a": 1, "b": 2} and inner.attrs == {}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns \
        <= outer.end_ns <= nxt.start_ns


def test_span_that_raises_is_recorded_and_unwinds():
    rec = spans.Recorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise ValueError("x")
    with rec.span("after"):
        pass
    names = [(r.name, r.parent) for r in rec.records()]
    outer = rec.records()[1]
    assert names == [("inner", outer.id), ("outer", None), ("after", None)]


def test_ring_is_bounded_and_resets():
    rec = spans.Recorder(size=4)
    for i in range(10):
        with rec.span("s", i=i):
            pass
    assert [r.attrs["i"] for r in rec.records()] == [6, 7, 8, 9]
    rec.reset()
    assert rec.records() == []
    with rec.span("s"):
        pass
    assert len(rec.records()) == 1


def test_process_recorder_reset():
    with spans.span("probe.reset"):
        pass
    assert any(r.name == "probe.reset" for r in spans.recorder())
    spans.reset()
    assert not any(r.name == "probe.reset" for r in spans.recorder())


def test_compile_events_land_under_the_open_span():
    """The process recorder's ``jax.monitoring`` listener turns compile
    events into ``jax.compile`` records parented to the open span, and
    sums their seconds into the process's compile clock."""
    c0 = spans.compile_seconds()
    with spans.span("probe.compile"):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((3, 7))).block_until_ready()
    recs = spans.recorder()
    assert spans.compile_seconds() > c0
    (outer,) = [r for r in recs if r.name == "probe.compile"]
    compiles = [r for r in recs if r.name == "jax.compile"
                and r.parent == outer.id]
    assert compiles
    assert all(r.attrs["event"].startswith("/jax/core/compile/")
               for r in compiles)
    assert all(outer.start_ns <= r.end_ns <= outer.end_ns
               for r in compiles)


def test_no_program_span_takes_the_bench_prefix():
    """``bench.`` names are the benchmark harness's own."""
    names = set()
    for d, _, files in os.walk(SRC):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    names |= set(re.findall(r"\bspan\(\s*\"([^\"]+)\"",
                                            fh.read()))
    assert {"serve.step", "serve.admit", "serve.keys", "serve.dispatch",
            "serve.commit", "serve.journal", "serve.snapshot",
            "serve.recover", "engine.round", "engine.prefill",
            "engine.fetch", "engine.check"} <= names
    assert not any(n.startswith("bench.") for n in names)


@pytest.fixture(scope="module")
def wave():
    """A kv_fused bucketed server: one request admitted and advanced,
    then a wave of three (one longer than the largest bucket) admitted
    while the first request's round runs."""
    tp = init_params(jax.random.PRNGKey(0), TCFG)
    dp = init_params(jax.random.PRNGKey(1), DCFG)
    sd = SpecDecConfig(num_drafts=2, draft_len=2, strategy="gls", top_k=0)
    eng = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd, pool_slots=4)
    server = SpecDecServer(eng, max_batch=4, cache_mode="kv_fused",
                           min_buf_len=80)
    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(3)
    first = rng.randint(1, 30, size=9).astype(np.int32)
    server.submit(first, max_new=6)
    server.step(key)                    # admission only
    server.step(key)                    # the first round
    wave = [rng.randint(1, 30, size=n).astype(np.int32) for n in (5, 20, 70)]
    for p in wave:
        server.submit(p, max_new=4)
    held = int(eng.pool.pos[eng._sessions[1].slot])
    spans.reset()
    server.step(key)                    # round + overlapped admission
    return server, spans.recorder(), wave, held


def _children(recs, parent):
    """The spans under ``parent``; compiles of first-seen shapes land
    wherever they happen and are left out."""
    return [r for r in recs if r.parent == parent.id
            and r.name != "jax.compile"]


def test_wave_span_tree(wave):
    _, recs, _, _ = wave
    (step,) = [r for r in recs if r.name == "serve.step"]
    assert step.parent is None
    assert [r.name for r in _children(recs, step)] == [
        "serve.admit", "serve.keys", "serve.dispatch", "serve.commit"]
    (dispatch,) = [r for r in recs if r.name == "serve.dispatch"]
    kids = [r.name for r in _children(recs, dispatch)]
    assert kids[0] == "engine.round" and kids[-2:] == ["engine.fetch",
                                                       "engine.check"]
    assert set(kids[1:-2]) == {"engine.prefill"}
    (admit,) = [r for r in recs if r.name == "serve.admit"]
    (keys,) = [r for r in recs if r.name == "serve.keys"]
    (rnd,) = [r for r in recs if r.name == "engine.round"]
    assert len(admit.attrs["uids"]) == 3 and keys.attrs["n"] == 1
    assert len(rnd.attrs["uids"]) == 1
    # Children lie inside their parent, in order.
    for r in recs:
        if r.name == "jax.compile":
            continue
        kids = sorted(_children(recs, r), key=lambda c: c.start_ns)
        assert all(r.start_ns <= c.start_ns and c.end_ns <= r.end_ns
                   for c in kids)
        assert all(a.end_ns <= b.start_ns for a, b in zip(kids, kids[1:]))


def test_wave_prefill_counts(wave):
    """Prompt tokens count once per request (the prompt less its
    pending token); the cells computed are every arena row x each
    (chunk, bucket) group's width of the bucket plan."""
    server, recs, prompts, _ = wave
    eng = server.engine
    rows_n = eng.pool.num_slots * eng.cfg.num_drafts
    plans = [_bucket_plan(len(p) - 1, _max_bucket(eng.pool.buf_len))
             for p in prompts]
    groups = sorted({(c, b) for plan in plans
                     for c, (_, _, b) in enumerate(plan)})
    for model in ("target", "drafter"):
        pre = [r.attrs for r in recs if r.name == "engine.prefill"
               and r.attrs["model"] == model]
        assert sum(a["tokens"] for a in pre) == sum(len(p) - 1
                                                    for p in prompts)
        assert all(a["rows"] == rows_n for a in pre)
        assert sorted(a["bucket"] for a in pre) == sorted(b for _, b in
                                                          groups)
        assert sum(a["rows"] * a["bucket"] for a in pre) == \
            rows_n * sum(b for _, b in groups)
        assert sorted(u for a in pre for u in a["uids"]) == \
            sorted(u for u, plan in zip((2, 3, 4), plans) for _ in plan)


def test_wave_arena_bytes(wave):
    """Reserved bytes are both arenas' whole; live bytes the positions
    the advancing slot held when the round was dispatched, over its K
    rows."""
    server, recs, _, held = wave
    eng = server.engine
    (rnd,) = [r.attrs for r in recs if r.name == "engine.round"]
    leaves = [leaf for a in eng.pool.caches.values() for leaf in a.values()]
    per_position = sum(leaf.nbytes // (leaf.shape[1] * leaf.shape[3])
                       for leaf in leaves)
    assert rnd["arena_bytes"] == sum(leaf.nbytes for leaf in leaves)
    assert rnd["live_bytes"] == per_position * eng.cfg.num_drafts * held
    assert 0 < rnd["live_bytes"] <= rnd["arena_bytes"]


def test_program_names_match_the_benchmark(wave):
    """The fused round and the slot prefill compile to the module names
    ``chipbench/programs.py`` finds them by on the chip; a rename would
    silently empty ``round_device_ms`` and ``prefill_ms_per_req``."""
    sys.path.insert(0, ROOT)
    from chipbench.programs import PREFILL, ROUND
    server, _, _, _ = wave
    eng, pool = server.engine, server.engine.pool
    s = pool.num_slots
    rows = s * eng.cfg.num_drafts
    round_ = eng._fused_round.lower(
        eng._t_verify_params, eng.d_params, pool.caches["target"],
        pool.caches["drafter"], pool.pos_device(),
        jnp.zeros(s, jnp.int32), jnp.zeros(s, bool),
        jnp.stack([jax.random.PRNGKey(0)] * s)).compile()
    prefill = eng._slot_prefill["target"].lower(
        eng.t_params, jnp.zeros((rows, 16), jnp.int32),
        pool.caches["target"], jnp.zeros(rows, jnp.int32),
        jnp.zeros(rows, bool)).compile()

    def module(compiled):
        return re.match(r"HloModule (\S+?),", compiled.as_text()).group(1)
    assert re.search(ROUND, module(round_))
    assert not re.search(PREFILL, module(round_))
    assert re.search(PREFILL, module(prefill))
    assert not re.search(ROUND, module(prefill))


def test_journal_snapshot_and_recover_spans(tmp_path):
    """With a journal, snapshots and a fault on every round of request
    1 (quarantined after its retry): the commit's journal append and
    snapshot write nest under ``serve.commit``, each recovered fault
    under its ``serve.step``, and the quarantine's journal record under
    ``serve.recover``."""
    from repro.serving import FaultPlan
    tp = init_params(jax.random.PRNGKey(0), TCFG)
    dp = init_params(jax.random.PRNGKey(1), DCFG)
    sd = SpecDecConfig(num_drafts=2, draft_len=2, strategy="gls", top_k=0)
    eng = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd, pool_slots=2)
    server = SpecDecServer(
        eng, max_batch=2, cache_mode="kv_fused", journal_dir=str(tmp_path),
        snapshot_every=1, retry_budget=1,
        fault_plan=FaultPlan(kernel_dispatch=1.0, only_uids=(1,)))
    server.submit(np.arange(1, 8, dtype=np.int32), max_new=6)
    server.submit(np.arange(2, 9, dtype=np.int32), max_new=6)
    spans.reset()
    server.run(jax.random.PRNGKey(2))
    recs = spans.recorder()
    by_id = {r.id: r for r in recs}
    parents = {name: {by_id[r.parent].name for r in recs if r.name == name}
               for name in ("serve.journal", "serve.snapshot",
                            "serve.recover")}
    assert parents == {"serve.journal": {"serve.commit", "serve.recover"},
                       "serve.snapshot": {"serve.commit"},
                       "serve.recover": {"serve.step"}}
    assert server.metrics.quarantined == 1 and server.metrics.completed == 1
