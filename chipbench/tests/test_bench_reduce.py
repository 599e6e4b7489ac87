"""Trace reduction: busy and idle intervals, program and kernel sums,
gap attribution — on hand-made events and on excerpts of a recorded
chip trace."""

import json
import os

import pytest

from chipbench import reduce, run
from chipbench.reduce import Event, Events, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _events():
    # Two devices; ns.  Window 0..100 from the bench spans.
    ops = {0: [Event("fusion.1", 10, 20), Event("fusion.2", 15, 30),
               Event("%gls_row_race.1 = (f32[40,8]) custom-call(x)", 50, 60),
               Event("%copy.3 = f32[40,8] copy(%jit_gls_row_race_.4)", 60,
                     60.5),
               Event("fusion.1", 90, 120),
               Event("%while.7 = s32[] while(%t), body=%b", 10, 30)],
           1: [Event("fusion.1", 10, 40), Event("all-gather.3", 60, 70)]}
    modules = {0: [Event("jit_round_core", 10, 30),
                   Event("jit_round_core", 50, 60)],
               1: [Event("jit_round_core", 10, 40)]}
    spans = [Event("bench.step", 0, 60), Event("bench.wait", 60, 85),
             Event("bench.submit", 85, 100)]
    return Events(ops, modules, spans)


def test_merge_and_overlap():
    assert reduce.merge([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 15) == \
        [[1, 4], [5, 8], [9, 15]]
    assert reduce.overlap([[0, 10], [20, 30]], [[5, 25]]) == 10


def test_busy_and_idle():
    tr = Trace(_events())
    assert (tr.lo, tr.hi) == (0, 100)
    assert tr.busy[0] == [[10, 30], [50, 60.5], [90, 100]]
    assert tr.busy[1] == [[10, 40], [60, 70]]
    assert tr.busy_s() == pytest.approx((40.5 + 40) / 2 * 1e-9)
    busy, span = tr.busy_outside("bench.wait")
    # device 0 loses [60, 60.5] to the wait, device 1 loses [60, 70]
    assert busy == pytest.approx((40 + 30) / 2 * 1e-9)
    assert span == pytest.approx(75e-9)


def test_program_and_kernel_sums():
    tr = Trace(_events())
    secs, n = tr.module_time(r"round_core")
    assert n == 2 and secs == pytest.approx((30 + 30) / 2 * 1e-9)
    from chipbench.programs import RACE
    secs, n = tr.op_time(RACE)
    assert n == 1 and secs == pytest.approx(10 / 2 * 1e-9)
    assert tr.op_time(r"all-gather")[0] == pytest.approx(5e-9)
    top = tr.top_ops(10)
    assert not any(name.startswith("while") for name, _ in top)
    assert top[0][0] == "fusion.1"
    assert top[0][1] == pytest.approx((10 + 10 + 30) / 2 * 1e-9)


def test_gap_attribution():
    tr = Trace(_events())
    gaps = tr.idle_gaps(10)
    # device 0 idle: [0,10] step, [30,50] step, [60.5,90] mostly wait
    assert [g[0] for g in gaps] == ["bench.wait", "bench.step",
                                    "bench.step"]
    assert gaps[0][1] == pytest.approx(29.5e-9)
    assert tr.span_at(120) == "host.other"


def _excerpt(part):
    with open(os.path.join(DATA, "chat_trace_excerpt.json")) as f:
        ex = json.load(f)[part]
    ev = Events({0: [Event(*e) for e in ex["ops"]]},
                {0: [Event(*e) for e in ex["modules"]]},
                [Event(*e) for e in ex["spans"]])
    return ev, Trace(ev, window=(0.0, ex["length_ns"]))


def _brute_busy(ops, lo, hi, step=100.0):
    """Busy ns of the union of op intervals, sampled on a grid."""
    covered, t = 0, lo
    while t < hi:
        covered += any(e.start <= t < e.end for e in ops)
        t += step
    return covered * step


@pytest.mark.parametrize("part", ["race", "between_rounds"])
def test_recorded_chip_trace(part):
    """Two excerpts of the chat cell traced on a v5e chip (one round's
    race kernel; the host gap between two rounds): the reduction agrees
    with a brute-force count, and idle gaps are named by bench spans."""
    ev, tr = _excerpt(part)
    assert tr.devices == [0] and ev.ops[0] and ev.spans
    brute = _brute_busy(ev.ops[0], tr.lo, tr.hi)
    assert tr.busy_s() == pytest.approx(brute * 1e-9, rel=0.01)
    gaps = tr.idle_gaps(10)
    assert all(g[0].startswith("bench.") or g[0] == "host.other"
               for g in gaps)
    assert sum(g[1] for g in gaps) <= tr.window_s - tr.busy_s() + 1e-9
    secs, rounds = tr.module_time(r"round_core")
    assert rounds >= 1 and 0 < secs <= tr.window_s
    if part == "between_rounds":
        assert rounds == 2 and gaps[0][0] == "bench.step"
        assert tr.module_time(r"threefry_fold_in")[1] > 0


def test_race_kernel_in_the_recorded_trace():
    """The chat round's race call: one call over two (40, 8, 49152)
    f32 inputs in HBM, about 168 us, which the roofline reader turns
    into a share under 100%."""
    from chipbench import counts
    _, tr = _excerpt("race")
    from chipbench.programs import RACE
    secs, calls = tr.op_time(RACE)
    assert calls == 1 and 150e-6 < secs < 200e-6
    (name,) = tr.op_names(RACE)
    assert reduce.operands(name) == [("f32", (40, 8, 49152), 0)] * 2
    ctx = {"trace": tr, "slots": 8, "K": 8, "L": 4, "vocab": 49152,
           "peak": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    share = run.reader("race_roofline.chat")(ctx)
    c = counts.round_race(8, 8, 4, 49152)
    assert share == pytest.approx(100 * c["bytes"] / 819e9 / secs)
    assert 50 < share < 100


def test_race_roofline_reads_nothing_off_hbm():
    """Inputs padded to 16 rows and placed in the core's fast memory
    (as the compiler lays out the 2-slot round): no HBM roofline."""
    name = ("%gls_row_race.1 = (f32[16,8]{1,0:T(8,128)S(1)}, s32[16,8]"
            "{1,0:T(8,128)S(1)}) custom-call(f32[16,8,49152]{2,1,0:T(8,128)"
            "S(1)} %pad.4, f32[16,8,49152]{2,1,0:T(8,128)S(1)} %pad.5), "
            "custom_call_target=\"tpu_custom_call\"")
    assert reduce.operands(name) == [("f32", (16, 8, 49152), 1)] * 2
    ev = Events({0: [Event(name, 10, 40)]}, {0: []},
                [Event("bench.step", 0, 100)])
    ctx = {"trace": Trace(ev), "slots": 2, "K": 8, "L": 4,
           "vocab": 49152,
           "peak": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    assert run.reader("race_roofline.chat")(ctx) is None
