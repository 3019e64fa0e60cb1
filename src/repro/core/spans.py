"""Program spans: what each layer of a job did, and when.

``span(name, **attrs)`` times a block on ``time.perf_counter_ns`` and
opens a ``jax.profiler.TraceAnnotation`` of the same name and attributes,
so that while a profile runs every span also lands in the trace's
``/host:CPU`` plane, on the clock of the device events. ``record`` adds a
span whose start is known only afterwards; such spans are kept in memory
only.

The job a span works for and the span that encloses it travel in a
:mod:`contextvars` context. Code that hands work to another thread
captures the context at the hand-off (``contextvars.copy_context()``) and
runs the work under it, so the other thread's spans keep their job and
parent.

The recorder is always on. It keeps the newest ``MAX_SPANS`` spans, which
``recorded()`` returns. Counts (bytes, tier, node, ...) ride on the spans
as attributes.
"""
from __future__ import annotations

import collections
import contextvars
import itertools
import threading
import time
from typing import Any, NamedTuple

from jax.profiler import TraceAnnotation

MAX_SPANS = 1 << 16


class Span(NamedTuple):
    name: str
    job: str | None
    id: int
    parent: int | None      # id of the enclosing span, None at a root
    thread: str
    start_ns: int           # time.perf_counter_ns
    end_ns: int
    attrs: dict


# Plain tuples in Span's field order: a tuple is cheaper to build on the
# hot path than the named one ``recorded()`` hands out.
_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_ids = itertools.count(1)
_job: contextvars.ContextVar = contextvars.ContextVar("span_job",
                                                      default=None)
_parent: contextvars.ContextVar = contextvars.ContextVar("span_parent",
                                                         default=None)


class _Open:
    """One open span; see :func:`span`."""

    __slots__ = ("name", "job", "attrs", "_id", "_parent", "_token",
                 "_job_token", "_note", "_start")

    def __init__(self, name: str, job: str | None, attrs: dict):
        self.name, self.job, self.attrs = name, job, attrs

    def __enter__(self) -> dict:
        self._parent = _parent.get()
        self._id = next(_ids)
        self._token = _parent.set(self._id)
        if self.job is None:
            self.job, self._job_token = _job.get(), None
            self._note = TraceAnnotation(self.name, **self.attrs)
        else:
            self._job_token = _job.set(self.job)
            self._note = TraceAnnotation(self.name, job=self.job,
                                         **self.attrs)
        self._note.__enter__()
        self._start = time.perf_counter_ns()
        return self.attrs

    def __exit__(self, exc_type, exc, tb) -> None:
        end = time.perf_counter_ns()
        self._note.__exit__(exc_type, exc, tb)
        if self._job_token is not None:
            _job.reset(self._job_token)
        _parent.reset(self._token)
        _spans.append((self.name, self.job, self._id, self._parent,
                       threading.current_thread().name, self._start, end,
                       self.attrs))


def span(name: str, *, job: str | None = None, **attrs: Any) -> _Open:
    """``with span(name, **attrs) as attrs: ...`` records the block.
    ``job`` names the job the block works for; nested spans, and threads
    that run under a copy of this context, inherit it. The yielded dict
    takes attributes known only at the end (a tier, a status)."""
    return _Open(name, job, attrs)


def record(name: str, start_ns: int, end_ns: int, *,
           job: str | None = None, **attrs: Any) -> None:
    """Record a span that already ended, under the current parent."""
    _spans.append((name, job if job is not None else _job.get(),
                   next(_ids), _parent.get(),
                   threading.current_thread().name, start_ns, end_ns, attrs))


def recorded() -> list[Span]:
    """The kept spans, oldest first."""
    return [Span._make(s) for s in list(_spans)]


def self_ns(parent: Span, spans: list[Span]) -> int:
    """``parent``'s duration minus the union of its direct children's
    intervals, each clipped to it."""
    lo, hi = parent.start_ns, parent.end_ns
    covered, reach = 0, lo
    for s, e in sorted((max(c.start_ns, lo), min(c.end_ns, hi))
                       for c in spans if c.parent == parent.id):
        s = max(s, reach)
        if e > s:
            covered += e - s
            reach = e
    return (hi - lo) - covered
