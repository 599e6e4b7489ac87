"""Spans and counters of the serving loop (DESIGN.md §16).

``span(name, **attrs)`` times one block of host work.  While it is open
it is a ``jax.profiler.TraceAnnotation`` of the same name, so a profiler
trace shows it beside the device timeline; when it closes it appends one
``Record`` to a bounded ring owned by the process.  Counts ride on the
span of the work they count, as ``attrs``: the ``with`` target is the
attrs dict, for counts known only inside the block.

Times are ``time.time_ns()``, the clock of ``Request``'s stamps.  The
profiler's host events use the same realtime clock, written in an
xplane as ns since the session's ``profile_start_time`` (its "Task
Environment" plane).  ``parent`` is the id of the span open around this
one on the same thread.  One ``jax.monitoring`` listener turns JAX's
``/jax/core/compile/*`` events into ``jax.compile`` records under the
open span, so a compile mid-serving shows which step paid for it.

Names starting ``bench.`` are the benchmark harness's own; no program
span takes that prefix.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

# Records the ring keeps: a serving round writes 8-12, and a 51 s
# benchmark window with its warm-up about 5,000.
RING_SIZE = 1 << 15
COMPILE_PREFIX = "/jax/core/compile/"


class Record(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]     # ``id`` of the enclosing span, or None
    attrs: dict
    id: int


class Recorder:
    """A bounded ring of ``Record``s and the per-thread stack of open
    spans."""

    def __init__(self, size: int = RING_SIZE):
        self.ring: collections.deque = collections.deque(maxlen=size)
        self.compile_s = 0.0
        self._ids = itertools.count()
        self._local = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs) -> "_Span":
        return _Span(self, name, attrs)

    def on_duration(self, event: str, duration_secs: float, **_) -> None:
        """``jax.monitoring`` listener: a compile event ends now."""
        if not event.startswith(COMPILE_PREFIX):
            return
        end = time.time_ns()
        self.compile_s += duration_secs
        stack = self._stack()
        self.ring.append(Record("jax.compile", end - int(duration_secs * 1e9),
                                end, stack[-1] if stack else None,
                                {"event": event}, next(self._ids)))

    def records(self) -> list:
        return list(self.ring)

    def reset(self) -> None:
        self.ring.clear()
        self.compile_s = 0.0


class _Span:
    __slots__ = ("_rec", "_name", "_attrs", "_ann", "_id", "_parent",
                 "_start")

    def __init__(self, rec: Recorder, name: str, attrs: dict):
        self._rec, self._name, self._attrs = rec, name, attrs

    def __enter__(self) -> dict:
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        stack = self._rec._stack()
        self._parent = stack[-1] if stack else None
        self._id = next(self._rec._ids)
        stack.append(self._id)
        self._start = time.time_ns()
        return self._attrs

    def __exit__(self, *exc) -> bool:
        end = time.time_ns()
        self._rec._stack().pop()
        self._rec.ring.append(Record(self._name, self._start, end,
                                     self._parent, self._attrs, self._id))
        self._ann.__exit__(*exc)
        return False


_PROCESS = Recorder()
jax.monitoring.register_event_duration_secs_listener(_PROCESS.on_duration)


def span(name: str, **attrs) -> _Span:
    """A span of the process's recorder (module docstring)."""
    return _PROCESS.span(name, **attrs)


def recorder() -> list:
    """The process's records, oldest first."""
    return _PROCESS.records()


def compile_seconds() -> float:
    """Seconds the process has spent in JAX's compile events."""
    return _PROCESS.compile_s


def reset() -> None:
    _PROCESS.reset()
