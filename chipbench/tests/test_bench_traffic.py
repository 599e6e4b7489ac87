"""Traffic generation and the window generator, on a fake server."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import run, traffic


@pytest.mark.parametrize("mix", ["chat-open", "long-offline"])
def test_same_seed_same_schedule(mix):
    m = traffic.load_mix(mix)
    a = traffic.plan(m, 2 ** 31 + 12345, 40.0, 49152)
    b = traffic.plan(m, 2 ** 31 + 12345, 40.0, 49152)
    c = traffic.plan(m, 7, 40.0, 49152)
    key = lambda p: (p.due, p.max_new, p.prompt.tobytes())
    assert [key(p) for p in a] == [key(p) for p in b]
    assert [key(p) for p in a] != [key(p) for p in c]
    # another seed: the same set of sizes in another order, at the same
    # arrival times
    sizes = lambda ps: sorted((len(p.prompt), p.max_new) for p in ps)
    assert sizes(a) == sizes(c)
    assert [p.due for p in a] == [p.due for p in c]
    if m["loop"] == "open":
        assert [len(p.prompt) for p in a] != [len(p.prompt) for p in c]


def test_chat_mix_cv_and_clipping():
    m = traffic.load_mix("chat-open")
    ps = traffic.plan(m, 1, 2000.0, 49152)
    due = np.array(sorted(p.due for p in ps))
    gaps = np.diff(due)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(m["arrival"]["cv"],
                                                         rel=0.1)
    # the traced sub-window opens just before two arrivals, so that its
    # trace holds admission prefills
    due51 = [p.due / 51.0 for p in traffic.plan(m, 1, 51.0, 49152)]
    lo = m["trace_at"]
    assert sum(lo < d < lo + 2.0 / 51.0 for d in due51) >= 2
    assert len(ps) == round(m["arrival"]["rate_per_s"] * 2000.0)
    assert due[0] >= 0 and due[-1] < 2000.0
    plens = np.array([len(p.prompt) for p in ps])
    olens = np.array([p.max_new for p in ps])
    for spec, x in ((m["prompt_len"], plens), (m["output_len"], olens)):
        assert x.min() == spec["min"] and x.max() == spec["max"]
        assert np.median(x) == pytest.approx(spec["median"], rel=0.1)
    assert all(1 <= t < 49152 for p in ps[:50] for t in p.prompt)


def test_long_mix_lengths():
    m = traffic.load_mix("long-offline")
    ps = traffic.plan(m, 3, 40.0, 49152)
    assert len(ps) == m["pool"]
    assert all(1024 <= len(p.prompt) <= 1536 and p.max_new == 64
               for p in ps)
    assert traffic.buf_len(m, 4) == 1536 + 64 + 6
    # the 8 grid lengths in turn, in the same order for every seed
    grid = [round(1024 + i * 512 / 7) for i in range(8)]
    assert [len(p.prompt) for p in ps] == grid * (len(ps) // 8)
    other = traffic.plan(m, 4, 40.0, 49152)
    assert [len(p.prompt) for p in ps] == [len(p.prompt) for p in other]


class FakeServer:
    """The scheduler's surface: one step takes ``step_s`` of fake time
    and gives every live request one token; ``capacity`` slots."""

    def __init__(self, clock, capacity=2, step_s=0.1, stall_uid=None):
        self.clock, self.capacity, self.step_s = clock, capacity, step_s
        self.queue, self.live = [], []
        self.uid = 0
        self.stall_uid = stall_uid

    def submit(self, prompt, max_new):
        self.uid += 1
        self.queue.append(SimpleNamespace(
            uid=self.uid, prompt=prompt, max_new=max_new, output=[],
            token_times=[], t_first=None, t_admit=None, t_done=None,
            error=None))
        return self.uid

    def step(self, key):
        self.clock.t += self.step_s
        now = self.clock.t
        for r in self.live:
            if r.uid == self.stall_uid:
                continue
            r.output.append(1)
            r.token_times.append(now)
            r.t_first = r.t_first or now
            if len(r.output) == r.max_new:
                r.t_done = now
        self.live = [r for r in self.live if r.t_done is None]
        while self.queue and len(self.live) < self.capacity:
            r = self.queue.pop(0)
            r.t_admit = now
            self.live.append(r)
        return []


class Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def _open_mix(grace=1.0):
    return {"loop": "open", "grace_s": grace}


def test_due_time_accounting():
    clock = Clock()
    srv = FakeServer(clock, capacity=1, step_s=0.5)
    planned = [traffic.Planned(0.0, np.ones(3, np.int32), 2),
               traffic.Planned(0.1, np.ones(3, np.int32), 2)]
    w = traffic.drive(srv, _open_mix(grace=5.0), planned, None, 2.0,
                      clock=clock, sleep=clock.sleep)
    r0, r1 = w.records
    assert r0.due == 1000.0 and r1.due == pytest.approx(1000.1)
    # the second was due at 0.1 but the generator was inside a 0.5 s step:
    # it is sent late and timed from its due time
    assert r1.sent == pytest.approx(1000.5)
    assert traffic.ttft_s(r1, w) == pytest.approx(r1.request.t_first
                                                  - 1000.1)
    s = traffic.summary(w)
    assert s["attempted"] == 2 and s["failed"] == 0
    assert s["late_max_ms"] == pytest.approx(400.0)


def test_failed_after_grace():
    clock = Clock()
    srv = FakeServer(clock, capacity=2, step_s=0.1, stall_uid=2)
    planned = [traffic.Planned(0.0, np.ones(3, np.int32), 3),
               traffic.Planned(0.2, np.ones(3, np.int32), 3)]
    w = traffic.drive(srv, _open_mix(grace=1.0), planned, None, 1.0,
                      clock=clock, sleep=clock.sleep)
    assert [len(r.request.output) for r in w.records] == [3, 0]
    assert len(w.failed()) == 1 and len(w.finished()) == 1
    s = traffic.summary(w)
    assert s["failed"] == 1 and s["attempted"] == 2
    # the failed request's TTFT is the whole wait it was left with
    assert traffic.ttft_s(w.records[1], w) == pytest.approx(w.end - 1000.2)
    assert w.end >= 1000.0 + 1.0 + 1.0 - 1e-9


def test_closed_loop_keeps_clients_busy():
    clock = Clock()
    srv = FakeServer(clock, capacity=2, step_s=0.1)
    planned = [traffic.Planned(0.0, np.ones(3, np.int32), 2)
               for _ in range(100)]
    w = traffic.drive(srv, {"loop": "closed", "clients": 2, "grace_s": 5},
                      planned, None, 2.0, clock=clock, sleep=clock.sleep)
    assert not w.failed()
    assert traffic.summary(w)["late_max_ms"] == 0.0
    # 2 clients, 3 steps of 0.1 s per request (admit, 2 tokens)
    assert 13 <= len(w.records) <= 15
    assert traffic.tokens_in_window(w) <= 2 * 20


def test_blocks_of_groups_by_commit_time():
    req = SimpleNamespace(token_times=[1.0, 2.0, 2.0, 2.0, 3.0])
    assert traffic.blocks_of(req) == ([0, 1, 1, 1, 2],
                                      [True, True, False, False, True])


def test_new_mix_and_metric_found_by_name(tmp_path):
    """A later change adds a traffic mix and a per-layer metric as new
    files; the harness finds both by name, with no edit."""
    mix = dict(traffic.load_mix("chat-open"), schedule_seed=99)
    (tmp_path / "my-mix.json").write_text(json.dumps(mix))
    assert traffic.load_mix("my-mix", str(tmp_path)) == mix
    (tmp_path / "my_metric.py").write_text(
        "def read(ctx):\n    return ctx['x'] * 2\n")
    assert run.reader("my_metric", str(tmp_path))({"x": 21}) == 42
    # a dotted name falls back to the reader of its stem
    assert run.reader("my_metric.chat", str(tmp_path))({"x": 1}) == 2
    with pytest.raises(FileNotFoundError):
        run.reader("absent", str(tmp_path))


def test_every_listed_metric_has_a_reader():
    bench = run.load_bench()
    for m in bench["per_layer"]:
        assert callable(run.reader(m["name"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(traffic.DIR,
                                           w["traffic"] + ".json"))


def test_commit_gaps_are_between_rounds():
    req = SimpleNamespace(token_times=[1.0, 1.5, 1.5, 2.5])
    rec = traffic.Record(due=0.0, sent=0.0, request=req)
    assert traffic.commit_gaps_s(rec) == [0.5, 1.0]
