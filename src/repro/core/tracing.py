"""One tracing surface for the program: spans and counters.

``span(name, **ids)`` times a region of host code; ``count(name, n)`` adds to
a named counter. Both record only while a ``jax.profiler`` trace is being
taken (``TraceMe.is_enabled()``): outside a trace a span costs one check and
returns a shared no-op context (no span object, no clock read, no
annotation), and a count costs one check.

While recording, each span also enters a ``jax.profiler.TraceAnnotation`` of
its name, so it lands on the host plane of the profiler's ``.xplane.pb``, on
the clock of the device's operations. In memory it keeps its name, start and
end (``time.perf_counter_ns``), its thread, the enclosing span of that thread
(``parent``) and its identifiers (``session=``, ``step=``, ``nbytes=``...):
spans of one read session share its ``session``, spans of one training step
its ``step``.

``record(name, start_ns, end_ns, **ids)`` adds a span timed elsewhere: a
reader worker process's read, reported through its event ring with
``perf_counter`` stamps (``CLOCK_MONOTONIC``, shared across processes on
Linux). Such spans are kept in memory only.

``snapshot()`` returns what was recorded; ``reset()`` clears it. Records
stay in memory and are read once the traced window ends.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional


def _trace_active() -> bool:
    """Whether a profiler trace is being taken. No trace can be active
    before jax is imported, so the check stays free of the import; once jax
    is loaded it is the profiler's own flag."""
    global _enabled
    if "jax" not in sys.modules:
        return False
    from jax._src.lib import _profiler

    _enabled = _profiler.TraceMe.is_enabled
    return _enabled()


_enabled = _trace_active
_ids = itertools.count(1)
_local = threading.local()
_spans: List["Span"] = []
_counters: Dict[str, int] = {}
_lock = threading.Lock()


@dataclass
class Span:
    name: str
    start: int                   # perf_counter_ns
    end: int
    thread: int
    parent: Optional[int]        # id of the enclosing span on the thread
    id: int
    ids: Dict[str, object] = field(default_factory=dict)

    @property
    def ns(self) -> int:
        return self.end - self.start


class _Live:
    """A span being recorded."""

    __slots__ = ("name", "ids", "id", "parent", "start", "_note")

    def __init__(self, name: str, ids: Dict[str, object]):
        self.name = name
        self.ids = ids

    def __enter__(self) -> "_Live":
        import jax.profiler

        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.id = next(_ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._note = jax.profiler.TraceAnnotation(self.name, **self.ids)
        self._note.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self._note.__exit__(*exc)
        _local.stack.pop()
        _spans.append(Span(self.name, self.start, end, threading.get_ident(),
                           self.parent, self.id, self.ids))

    def tag(self, **ids) -> None:
        """Identifiers known only once the span has begun."""
        self.ids.update(ids)


class _Off:
    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def tag(self, **ids) -> None:
        return None


_OFF = _Off()


def active() -> bool:
    """Whether spans and counters are being recorded (a trace is taken)."""
    return _enabled()


def span(name: str, **ids):
    """Context manager timing the region it encloses (see module doc)."""
    if not _enabled():
        return _OFF
    return _Live(name, ids)


def record(name: str, start_ns: int, end_ns: int, **ids) -> None:
    """A span timed elsewhere, from ``perf_counter`` stamps in ns."""
    if not _enabled():
        return
    _spans.append(Span(name, int(start_ns), int(end_ns),
                       threading.get_ident(), None, next(_ids), ids))


def count(name: str, n: int = 1) -> None:
    if not _enabled():
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


@dataclass
class Snapshot:
    spans: List[Span]
    counters: Dict[str, int]

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def total_s(self, name: str) -> float:
        return sum(s.ns for s in self.named(name)) / 1e9

    def self_s(self, name: str) -> float:
        """Time of the spans called ``name`` less that of the spans nested
        directly in them."""
        own = {s.id: s.ns for s in self.named(name)}
        for s in self.spans:
            if s.parent in own:
                own[s.parent] -= s.ns
        return sum(own.values()) / 1e9


def snapshot() -> Snapshot:
    with _lock:
        return Snapshot(list(_spans), dict(_counters))


def reset() -> None:
    with _lock:
        _spans.clear()
        _counters.clear()
