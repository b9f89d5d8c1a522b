"""Spans of the served path: one bounded ring per process, on the
profiler's clock as well.

``span(name, **attrs)`` times a block of the served path.  Each span
records its name, a span id, its parent's id (the span open around it in
the same thread or task), start and end on ``time.perf_counter`` and a few
integer or short string attrs.  A child inherits its parent's ``batch``
attr, so every span under one micro-batch names that batch.  Finished
spans go into a ring of :data:`RING_SPANS` records; the oldest fall out
first.  The same call opens ``jax.profiler.TraceAnnotation(name,
**attrs)``: while a profiler trace runs, the span lands on the host plane
of the ``.xplane.pb``, on the clock the device ops use, and otherwise the
annotation costs a check.

``spans(since, until)`` is the operator's view: the finished spans that
started and ended inside an interval, and whether the ring may have
dropped any of them.

The spans of the served path, from the frontend down:

=====================  ======================================================
``frontend.request``   ``MicroBatchFrontend.submit``, submit to answer
                       (``request``, ``batch``)
``frontend.queue``     submit to its batch starting on the executor thread
                       (``request``, ``batch``; ring only, see :func:`record`)
``frontend.batch``     one micro-batch on the executor (``batch``,
                       ``queries``)
``session.execute``    ``Session.execute`` (``queries``)
``session.plan``       parse, route and grouping into shapes
``engine.encode``      padding the term ids of one device batch
``engine.window``      one device step call (``program``, ``window``,
                       ``lanes``, ``live_lanes``, ``traced``)
``engine.device_wait`` the wait for that step's outputs
``engine.fetch``       their copy to the host and the per-query masking
                       (``bytes``)
``engine.merge``       the per-batch union of the windows' answers
=====================  ======================================================
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from collections import deque
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: finished spans the ring holds; a batch of 16 AND queries over two
#: sweep windows makes 43
RING_SPANS = 1 << 16

_ids = itertools.count(1)  # next() is atomic under the interpreter lock
_current: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "repro_span", default=None)


def next_id() -> int:
    """A process-unique id, shared by spans, requests and batches."""
    return next(_ids)


class SpanRecord(NamedTuple):
    name: str
    id: int
    parent: int  # 0 at a root
    start: float  # time.perf_counter
    end: float
    attrs: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Spans(NamedTuple):
    records: list[SpanRecord]
    #: the ring dropped a span that ended at or after ``since``, so the
    #: interval may be missing spans
    dropped: bool


class Recorder:
    """The bounded ring of finished spans."""

    def __init__(self, capacity: int = RING_SPANS):
        self._ring: deque[SpanRecord] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._dropped_end: float | None = None  # latest end of a dropped span

    def add(self, rec: SpanRecord) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                end = self._ring[0].end
                if self._dropped_end is None or end > self._dropped_end:
                    self._dropped_end = end
            self._ring.append(rec)

    def spans(self, since: float, until: float) -> Spans:
        with self._lock:
            held = list(self._ring)
            last = self._dropped_end
        dropped = last is not None and last >= since
        return Spans([r for r in held if since <= r.start and r.end <= until],
                     dropped)


_recorder = Recorder()


class Span:
    """One open span (see the module docstring); use it as a context
    manager.  ``set`` adds attrs known only once the work has run."""

    __slots__ = ("name", "id", "parent", "attrs", "start", "end",
                 "_note", "_token")

    def __init__(self, name: str, attrs: dict):
        up = _current.get()
        if up is not None and "batch" in up.attrs and "batch" not in attrs:
            attrs["batch"] = up.attrs["batch"]
        self.name, self.id, self.attrs = name, next_id(), attrs
        self.parent = 0 if up is None else up.id
        self.start = self.end = 0.0

    def __enter__(self) -> "Span":
        self._token = _current.set(self)
        self._note = TraceAnnotation(self.name, **self.attrs)
        self._note.__enter__()
        self.start = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._note.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._note.__exit__(*exc)
        _current.reset(self._token)
        _recorder.add(SpanRecord(self.name, self.id, self.parent, self.start,
                                 self.end, self.attrs))

    @property
    def seconds(self) -> float:
        return self.end - self.start


def span(name: str, **attrs) -> Span:
    """A span around a ``with`` block (module docstring)."""
    return Span(name, attrs)


def record(name: str, start: float, end: float, parent: int = 0,
           **attrs) -> None:
    """Put a span whose ends lie on two threads into the ring directly,
    once it has ended: a profiler annotation opens and closes on one
    thread, so such a span is not on the trace's host plane."""
    _recorder.add(SpanRecord(name, next_id(), parent, start, end, attrs))


def spans(since: float = float("-inf"), until: float = float("inf")) -> Spans:
    """The finished spans that started at or after ``since`` and ended at or
    before ``until`` (``time.perf_counter`` seconds), oldest end first."""
    return _recorder.spans(since, until)
