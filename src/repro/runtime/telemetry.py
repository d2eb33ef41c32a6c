"""Spans and counters that the program records about its own host work.

A ``span`` times one piece of host work (a scheduler tick, an admission,
the padding of a pricing block) and a ``count`` adds to a named counter.
Both record only while a profiler session records
(``jax.profiler.TraceAnnotation.is_enabled()``): there is no switch of
their own.  A recorded span is also a ``jax.profiler.TraceAnnotation`` of
the same name, so it lands in the profiler's trace beside the device's
operations; the in-process registry keeps its duration, its enclosing span
and its attributes (an admission's spans carry ``rid=``).  While no
profiler records, a span is one call that returns a shared no-op context
and a count is one call.

An operator traces a serving day or a pricing call and reads the registry
in the same process::

    import jax
    from repro.runtime import telemetry

    telemetry.reset()
    with jax.profiler.trace("/tmp/trace"):
        engine.run_scheduler(requests)
    telemetry.snapshot()
    # {"spans": {"sched.tick": {"count": ..., "total_s": ..., "self_s": ...},
    #            ...},
    #  "counters": {"engine.scatter_calls": ..., ...}}

Spans are host-side only: never open one inside a jitted function, where
``jax.named_scope`` and a kernel's ``name=`` are the instruments.  The
names the program records are listed in docs/SERVING.md and beside
``cost_many`` in docs/ARCHITECTURE.md.
"""
from __future__ import annotations

import contextvars
import threading
import time
from collections import defaultdict
from typing import NamedTuple

from jax.profiler import TraceAnnotation

__all__ = ["span", "count", "snapshot", "records", "reset", "SpanRecord"]

_enabled = TraceAnnotation.is_enabled

#: recorded spans, in the order they opened: [name, start, end, parent
#: record or None, attrs]; ``end`` is None while the span is open
_records: list = []
_counters: dict = {}
_counters_lock = threading.Lock()
#: the innermost open span's record in this context
_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_telemetry_span", default=None)


class SpanRecord(NamedTuple):
    """One closed span: perf-counter seconds, and the index in
    ``records()`` of the span that enclosed it (None at the top)."""
    name: str
    start: float
    end: float
    parent: int | None
    attrs: dict


class _Off:
    """The context of a span that records nothing (shared by all)."""
    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> None:
        return None


_OFF = _Off()


class _Span:
    __slots__ = ("_rec", "_ann", "_token")

    def __init__(self, name: str, attrs: dict):
        self._rec = [name, 0.0, None, None, attrs]
        self._ann = TraceAnnotation(name, **attrs)

    def __enter__(self) -> None:
        rec = self._rec
        rec[3] = _current.get()
        self._token = _current.set(rec)
        _records.append(rec)
        self._ann.__enter__()
        rec[1] = time.perf_counter()

    def __exit__(self, *exc) -> None:
        self._rec[2] = time.perf_counter()
        self._ann.__exit__(*exc)
        _current.reset(self._token)


def span(name: str, **attrs):
    """A context manager timing the host work inside it as ``name``
    (recorded only while a profiler session records)."""
    if not _enabled():
        return _OFF
    return _Span(name, attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` (only while a profiler session
    records)."""
    if _enabled():
        with _counters_lock:
            _counters[name] = _counters.get(name, 0) + n


def records() -> list[SpanRecord]:
    """Every closed span recorded since the last ``reset``, in the order
    they opened (a span whose parent is still open has parent None)."""
    closed = [rec for rec in _records if rec[2] is not None]
    index = {id(rec): i for i, rec in enumerate(closed)}
    return [SpanRecord(name, start, end,
                       None if parent is None else index.get(id(parent)),
                       attrs)
            for name, start, end, parent, attrs in closed]


def snapshot() -> dict:
    """Per span name the count, the total seconds and the self seconds
    (each span's duration less its closed child spans'), and every
    counter: ``{"spans": {name: {"count", "total_s", "self_s"}},
    "counters": {name: n}}``."""
    children: dict = defaultdict(float)
    for _, start, end, parent, _ in _records:
        if end is not None and parent is not None:
            children[id(parent)] += end - start
    spans: dict = {}
    for rec in _records:
        name, start, end = rec[0], rec[1], rec[2]
        if end is None:
            continue
        s = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                    "self_s": 0.0})
        s["count"] += 1
        s["total_s"] += end - start
        s["self_s"] += end - start - children.get(id(rec), 0.0)
    with _counters_lock:
        counters = dict(_counters)
    return {"spans": spans, "counters": counters}


def reset() -> None:
    """Forget every recorded span and counter."""
    _records.clear()
    with _counters_lock:
        _counters.clear()
