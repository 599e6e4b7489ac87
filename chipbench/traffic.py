"""Traffic: seeded request schedules and the generator that drives one window.

A mix is a JSON file of parameters under ``chipbench/traffic/``, read
by ``load_mix``.  Its lengths and arrivals are drawn from the mix's own
``schedule_seed``, so every run seed gets the same set of sizes and the
same arrival times; the run seed only shuffles which size arrives when
and draws the token ids.

``loop: "open"``: independent users.  ``round(rate * seconds)``
requests are due inside the window, spaced by Gamma gaps of the mix's
coefficient of variation and scaled so that the last gap ends at the
window's end.  Each request is timed from when it was due, so a stall
of the generator or the server counts against every request behind it.

``loop: "closed"``: ``clients`` callers that each wait for their reply
before sending the next request from a shuffled pool; a request is due
when its caller sends it.  A mix may set ``shuffle: false``: every
seed then sends the sizes in the same order too, for a closed loop
whose clients move in step, where the order decides which prompts
share an admission wave and so changes the work.

``drive`` runs one window against a server with the scheduler's
surface (``submit``, ``step``, ``queue``, ``live``) and returns a
``Window`` of per-request records.  After the window no request is
sent; the server runs on for the mix's ``grace_s`` so that requests due
in the window can finish, and one still unfinished then is failed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import Callable, Optional

import numpy as np

DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")


def load_mix(name: str, directory: str = DIR) -> dict:
    with open(os.path.join(directory, name + ".json")) as f:
        return json.load(f)


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]))
    if dist == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, size=n)
    if dist == "grid":
        vals = np.linspace(spec["min"], spec["max"], int(spec["count"]))
        return np.resize(np.round(vals).astype(int), n)
    if dist == "lognormal":
        x = np.round(spec["median"] * np.exp(spec["sigma"]
                                             * rng.standard_normal(n)))
        return np.clip(x, spec["min"], spec["max"]).astype(int)
    raise ValueError(f"unknown length distribution {dist!r}")


def length_range(spec: dict) -> tuple:
    if spec["dist"] == "fixed":
        return int(spec["value"]), int(spec["value"])
    return int(spec["min"]), int(spec["max"])


def buf_len(mix: dict, draft_len: int) -> int:
    """Decode buffer that holds the mix's longest request: prompt +
    output + the L drafts and 2 more, as the scheduler sizes it."""
    return (length_range(mix["prompt_len"])[1]
            + length_range(mix["output_len"])[1] + draft_len + 2)


@dataclasses.dataclass
class Planned:
    due: float            # seconds after the window opens (open loop)
    prompt: np.ndarray
    max_new: int


def plan(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The window's requests in sending order."""
    fixed = np.random.default_rng(mix["schedule_seed"])
    run = np.random.default_rng(seed)
    if mix["loop"] == "open":
        n = max(1, int(round(mix["arrival"]["rate_per_s"] * seconds)))
    else:
        n = int(mix["pool"])
    plens = _lengths(mix["prompt_len"], n, fixed)
    olens = _lengths(mix["output_len"], n, fixed)
    due = np.zeros(n)
    if mix["loop"] == "open":
        arr = mix["arrival"]
        if arr["process"] != "gamma":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        shape = 1.0 / arr["cv"] ** 2
        gaps = fixed.gamma(shape, 1.0 / shape, size=n)
        c = np.concatenate([[0.0], np.cumsum(gaps)])
        due = seconds * c[:n] / c[n]
    order = run.permutation(n) if mix.get("shuffle", True) else range(n)
    return [Planned(float(due[i]),
                    run.integers(1, vocab, size=int(plens[j])).astype(
                        np.int32),
                    int(olens[j]))
            for i, j in enumerate(order)]


@dataclasses.dataclass
class Record:
    due: float            # absolute time.time() when it was due
    sent: float           # absolute time.time() when submitted
    request: object       # the server's request object


@dataclasses.dataclass
class Window:
    t0: float
    seconds: float
    end: float            # when the generator stopped waiting (grace end)
    records: list
    rounds: list          # per advancing step: cached contexts of its slots

    def finished(self) -> list:
        return [r for r in self.records if _done(r.request)]

    def failed(self) -> list:
        return [r for r in self.records if not _done(r.request)]


def _done(req) -> bool:
    return req.t_done is not None and req.error is None


def drive(server, mix: dict, planned: list, key, seconds: float, *,
          span: Callable = lambda name: contextlib.nullcontext(),
          on_step: Optional[Callable] = None,
          clock: Callable = time.time,
          sleep: Callable = time.sleep) -> Window:
    """Run one window of ``seconds`` and the grace after it.

    ``span(name)`` wraps each call into the server (``bench.step``,
    ``bench.submit``) and each idle wait (``bench.wait``);
    ``on_step(now)`` is called before every step (the traced run starts
    and stops its profiler there)."""
    records = []
    rounds = []
    todo = list(planned)
    closed = mix["loop"] == "closed"
    clients = int(mix.get("clients", 0))
    t0 = clock()
    t_end = t0 + seconds

    def submit(p: Planned, due: float):
        with span("bench.submit"):
            uid = server.submit(p.prompt, max_new=p.max_new)
        req = next(r for r in server.queue if r.uid == uid)
        records.append(Record(due=due, sent=clock(), request=req))

    def step():
        adv = [len(r.prompt) + len(r.output) - 1 for r in server.live]
        with span("bench.step"):
            server.step(key)
        if adv:
            rounds.append(adv)

    while True:
        now = clock()
        if now >= t_end:
            break
        if on_step is not None:
            on_step(now)
        if closed:
            outstanding = sum(1 for r in records if not _done(r.request))
            while todo and outstanding < clients:
                submit(todo.pop(0), clock())
                outstanding += 1
        else:
            while todo and t0 + todo[0].due <= now:
                p = todo.pop(0)
                submit(p, t0 + p.due)
        if server.queue or server.live:
            step()
        else:
            nxt = t0 + todo[0].due if todo and not closed else t_end
            with span("bench.wait"):
                sleep(max(0.0, min(nxt, t_end) - clock()))
    if not closed:
        # Requests due before the window closed but not yet sent (the
        # generator was inside a step) are sent now and timed from their due.
        for p in todo:
            if p.due < seconds:
                submit(p, t0 + p.due)
    if on_step is not None:
        on_step(clock())
    grace_end = clock() + float(mix["grace_s"])
    while any(not _done(r.request) for r in records) and clock() < grace_end:
        if not (server.queue or server.live):
            break
        step()
    return Window(t0=t0, seconds=seconds, end=clock(), records=records,
                  rounds=rounds)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def ttft_s(rec: Record, window: Window) -> float:
    """First token time minus due time; a request with no token by the
    end of the grace counts the whole wait it was left with."""
    t = rec.request.t_first
    return (t if t is not None else window.end) - rec.due


def tpot_s(rec: Record) -> Optional[float]:
    req = rec.request
    n = len(req.token_times)
    if not _done(req) or n < 2:
        return None
    return (req.token_times[-1] - req.token_times[0]) / (n - 1)


def commit_gaps_s(rec: Record) -> list:
    """Gaps between a request's successive commits (tokens one round
    commits share a stamp, so this is the time between its rounds)."""
    t = sorted(set(rec.request.token_times))
    return [b - a for a, b in zip(t, t[1:])]


def tokens_in_window(window: Window) -> int:
    lo, hi = window.t0, window.t0 + window.seconds
    return sum(1 for r in window.records for t in r.request.token_times
               if lo <= t < hi)


def blocks_of(req) -> tuple:
    """(block index, opens its block) per emitted token: tokens one
    round committed share one timestamp."""
    blocks, first = [], []
    b, prev = -1, None
    for t in req.token_times:
        if t != prev:
            b, prev = b + 1, t
            first.append(True)
        else:
            first.append(False)
        blocks.append(b)
    return blocks, first


def summary(window: Window) -> dict:
    """The window's end-to-end numbers and the generator's own lateness."""
    recs = window.records
    late = [r.sent - r.due for r in recs]
    ttft = [ttft_s(r, window) for r in recs]
    mean = (lambda v: float(np.mean(v)) * 1e3 if v else None)
    tpot = [x for x in (tpot_s(r) for r in recs) if x is not None]
    itl = [g for r in recs for g in commit_gaps_s(r)]
    wait = [r.request.t_admit - r.due for r in recs
            if r.request.t_admit is not None]
    close = window.t0 + window.seconds
    backlog = sum(1 for r in recs if r.request.t_admit is None
                  or r.request.t_admit > close)
    return {
        "attempted": len(recs),
        "failed": len(window.failed()),
        "ttft_p95_ms": percentile(ttft, 95) * 1e3 if ttft else None,
        "ttft_p50_ms": percentile(ttft, 50) * 1e3 if ttft else None,
        "ttft_mean_ms": mean(ttft),
        "tpot_mean_ms": mean(tpot),
        "tpot_p95_ms": percentile(tpot, 95) * 1e3 if tpot else None,
        "tpot_p50_ms": percentile(tpot, 50) * 1e3 if tpot else None,
        "itl_p95_ms": percentile(itl, 95) * 1e3 if itl else None,
        "itl_count": len(itl),
        "queue_wait_p50_ms": percentile(wait, 50) * 1e3 if wait else None,
        "queue_wait_p95_ms": percentile(wait, 95) * 1e3 if wait else None,
        "tokens_per_s": tokens_in_window(window) / window.seconds,
        "late_p50_ms": percentile(late, 50) * 1e3 if late else 0.0,
        "late_max_ms": max(late) * 1e3 if late else 0.0,
        "backlog_at_close": backlog,
    }
