"""The readers of the program's own spans and counters: host gaps
between rounds, the idle time inside them, prefill and arena shares and
admission to first token — on hand-made span records and trace events,
and end to end on a traced CPU run of the tiny cell."""

import itertools
import json
import types

import pytest

from chipbench import program_spans, run
from chipbench.reduce import Event, Events, Trace
from repro.serving.spans import Record

U = 1000                       # ns in a microsecond
OFF = 1_792_000_000_000_000_000   # time.time_ns() at the trace's zero


_IDS = itertools.count()


def _rec(name, start, end, **attrs):
    """A record at [start, end] microseconds after the trace's zero."""
    return Record(name, OFF + start * U, OFF + end * U, None, attrs,
                  next(_IDS))


def _req(due_us, admit_us=None, first_us=None):
    t = (lambda us: None if us is None else (OFF + us * U) / 1e9)
    return types.SimpleNamespace(
        due=t(due_us), request=types.SimpleNamespace(t_admit=t(admit_us),
                                                     t_first=t(first_us)))


def _ctx():
    """A window of requests due from -300 to 600 us: three rounds and an
    idle wait before the profiler starts (-5 us), three steps in a
    400 us traced window with a wait before the third, two rounds after
    it; a warm-up step before the window and a step after it."""
    spans = [Event("bench.step", 0, 100 * U), Event("bench.step", 110 * U,
                                                    200 * U),
             Event("bench.wait", 200 * U, 300 * U),
             Event("bench.step", 300 * U, 400 * U)]
    # Device 0 busy while each round runs; idle [0, 10], [55, 120],
    # [170, 200], [380, 400] outside the wait: 125 us.  The records sit
    # 1 us after their bench.step, so the host gap between rounds 1 and
    # 2 is [59, 119] on the trace's clock.
    ops = {0: [Event("fusion.1", 10 * U, 55 * U),
               Event("fusion.1", 120 * U, 170 * U),
               Event("fusion.1", 220 * U, 230 * U),
               Event("fusion.1", 300 * U, 380 * U)]}
    tr = Trace(Events(ops, {0: []}, spans))

    def rnd(start, end, uids, live):
        return _rec("engine.round", start, end, uids=uids,
                    arena_bytes=1000, live_bytes=live)

    recs = [
        # Warm-up, before the first due request.
        _rec("serve.step", -900, -800),
        _rec("engine.prefill", -890, -880, model="target", rows=64,
             bucket=16, tokens=3),
        rnd(-870, -860, [0], 1000),
        _rec("engine.fetch", -860, -850),
        # Before the profiler: gaps of 20 and 80 us, then an idle wait
        # (no request in common across it).
        _rec("serve.step", -290, -200),
        rnd(-290, -285, [1], 100),
        _rec("engine.prefill", -284, -282, model="target", rows=64,
             bucket=128, tokens=95),
        _rec("engine.prefill", -282, -280, model="drafter", rows=64,
             bucket=128, tokens=95),
        _rec("engine.fetch", -280, -250),
        _rec("serve.step", -240, -150),
        rnd(-238, -230, [1, 2], 200),
        _rec("engine.fetch", -230, -200),
        _rec("serve.step", -140, -60),
        rnd(-130, -120, [2], 300),
        _rec("engine.fetch", -120, -100),
        _rec("serve.step", -40, -10),
        rnd(-35, -30, [3], 400),
        _rec("engine.fetch", -30, -20),
        # Traced: the pair across the profiler's start, [59, 119] inside
        # it, and one that spans the wait.
        _rec("serve.step", 1, 99),
        rnd(5, 10, [3], 500),
        _rec("engine.fetch", 30, 60),
        _rec("serve.step", 112, 199),
        rnd(115, 120, [3, 4], 600),
        _rec("engine.fetch", 120, 180),
        _rec("serve.step", 302, 398),
        rnd(305, 310, [4, 5], 700),
        _rec("engine.fetch", 310, 390),
        # After the profiler stopped: the pair across its stop, then a
        # 30 us gap.
        _rec("serve.step", 500, 540),
        rnd(505, 510, [5], 800),
        _rec("engine.fetch", 510, 530),
        _rec("serve.step", 545, 595),
        rnd(550, 560, [5], 900),
        _rec("engine.fetch", 560, 580),
        # After the last due request: the drain.
        _rec("serve.step", 700, 790),
        rnd(705, 710, [5], 1000),
        _rec("engine.prefill", 712, 715, model="target", rows=64,
             bucket=16, tokens=9),
        _rec("engine.fetch", 715, 720),
    ]
    records = [_req(-300, -290, -200), _req(-280, -238, -100),
               _req(-45, -40, -25), _req(0, 5, 400), _req(600)]
    return {"trace": tr, "spans": recs, "records": records,
            "trace_started": (OFF - 5 * U) / 1e9}


def test_clock_offset_lines_steps_up():
    ctx = _ctx()
    assert program_spans.clock_offset(ctx) == OFF + 1 * U
    # A step that does not nest in its bench.step: no offset.
    bad = dict(ctx, spans=[r._replace(end_ns=r.end_ns + 5_000 * U)
                           if r.name == "serve.step" else r
                           for r in ctx["spans"]])
    assert program_spans.clock_offset(bad) is None


def test_host_gap_reads_the_window_outside_the_profiler():
    """Counted: 20 and 80 us before the profiler, 30 us after it.  Left
    out: the pairs inside the profiler's time (from its start at -5 us
    to the first step after the traced ones, at 500 us), those across
    its start and its stop, and the drain's after the last due."""
    ctx = _ctx()
    gaps = program_spans.host_gaps(ctx)
    assert [[(s - OFF) // U, (e - OFF) // U] for s, e in gaps] == \
        [[-250, -230], [-200, -120], [530, 560]]
    assert run.reader("host_gap_ms.chat")(ctx) == pytest.approx(0.030)
    # Without a trace, every pair of the window counts.
    untraced = dict(ctx, trace_started=None)
    assert len(program_spans.host_gaps(untraced)) == 7


def test_host_gap_leaves_out_pairs_across_a_wait():
    """Rounds with no request in common have an idle wait between them
    (or an admission into an empty server): no pair.  On the trace's
    clock, fetch 1 -> round 2 (60 us) counts; fetch 2 -> round 3 spans
    the wait; the pairs across the profiler's start and stop fall
    outside the traced window."""
    ctx = _ctx()
    ends = [((f - OFF) // U, (e - OFF) // U)
            for f, e in program_spans.round_pairs(ctx["spans"])]
    assert (-20, 10) in ends and (-100, -30) not in ends
    assert (-860, -285) not in ends
    assert program_spans.traced_gaps(ctx) == [[59 * U, 119 * U]]


def test_idle_in_host_gap_share():
    """The gap [59, 119] lies inside idle [55, 120]: 60 of the 125 idle
    us; idle outside any gap ([0, 10], [170, 200], [380, 400]) counts
    only in the base, and the wait in neither."""
    ctx = _ctx()
    assert run.reader("idle_in_host_gap_share.offline")(ctx) == \
        pytest.approx(100.0 * 60 / 125)


def test_prefill_and_arena_shares_from_the_first_due_on():
    """The warm-up's records before the first due request and the
    drain's after the last are left out."""
    ctx = _ctx()
    assert run.reader("prefill_useful_share.chat")(ctx) == \
        pytest.approx(100.0 * 190 / (2 * 64 * 128))
    assert run.reader("arena_live_share.offline")(ctx) == \
        pytest.approx(100.0 * 4500 / 9000)


def test_admit_to_first_reads_requests_due_before_the_trace():
    ctx = _ctx()
    # Due before the profiler started: 90, 138 and 15 us.
    assert run.reader("admit_to_first_ms")(ctx) == \
        pytest.approx(0.090, abs=1e-3)


@pytest.mark.parametrize("name", ["host_gap_ms.chat",
                                  "idle_in_host_gap_share.chat",
                                  "prefill_useful_share.chat",
                                  "arena_live_share.chat",
                                  "admit_to_first_ms"])
def test_empty_window_reads_nothing(name):
    """No round pair, prefill, round or request in the window, or a
    program that records no spans: every reader reads None."""
    ctx = _ctx()
    late = (OFF + 10_000 * U) / 1e9
    empty = dict(ctx, spans=[r for r in ctx["spans"]
                             if r.name == "serve.step"],
                 records=[_req(10_000, 10_010, 10_100)], trace_started=late)
    assert run.reader(name)(empty) is None
    if name != "admit_to_first_ms":
        assert run.reader(name)(dict(ctx, spans=None)) is None


def test_traced_run_reads_the_program_spans(tmp_path):
    """A traced CPU run of the tiny cell: the readers find the program's
    spans in its process, on the profiler's clock, with no device plane
    (so no idle share).  The profiler starts half way in, so that the
    host gaps before it hold a few seconds of rounds (the CPU profiler's
    stop can outlast the window)."""
    from chipbench import traffic
    from chipbench.tests.test_bench_run import TINY, TINY_TRAFFIC
    mix = dict(traffic.load_mix("tiny-open", TINY_TRAFFIC), trace_at=0.5)
    (tmp_path / "tiny-open.json").write_text(json.dumps(mix))
    names = ("host_gap_ms.chat", "idle_in_host_gap_share.chat",
             "prefill_useful_share.chat", "arena_live_share.chat",
             "admit_to_first_ms")
    bench = dict(TINY, per_layer=[{"name": n, "unit": "x"} for n in names])
    out = run.run("tiny-open", 2 ** 33 + 5, 6.0, True, bench=bench,
                  require_tpu=False, traffic_dir=str(tmp_path))
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == set(names) - {"idle_in_host_gap_share.chat"}
    assert 0 < m["host_gap_ms.chat"] < 1e3
    assert 0 < m["prefill_useful_share.chat"] <= 100
    assert 0 < m["arena_live_share.chat"] <= 100
    assert m["admit_to_first_ms"] > 0
