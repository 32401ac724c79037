"""Spans of the program, recorded only while a ``jax.profiler`` session runs.

``span(name, **attrs)`` is the one way the program marks a stretch of host
time. Outside a profiler session it returns a shared no-op context (its
value is ``None``) and records nothing: the cost is one
``TraceAnnotation.is_enabled()`` call. Inside one it enters a
``jax.profiler.TraceAnnotation``, so the span lands in the trace beside the
device's operations, and appends a ``Record`` to a bounded buffer in
memory. Records are on ``time.perf_counter_ns``'s clock; a reader puts them
on the trace's clock by matching spans it sees in both.

A span opened while no other is open starts a request: its children share
its request id and name their parent. Two host stalls are recorded as spans
too, while a session runs: ``host.gc`` (each garbage collection, with its
generation) and ``host.compile`` (each of JAX's compile events, back-dated
by its duration, with the event's name and the function's). A compile span
is known only when it has ended, so it is in the buffer and not in the
profiler's trace.

``spans()`` returns the buffer's records and ``clear()`` empties it.
``BUFFER.dropped`` counts the records that found the buffer full.
"""
from __future__ import annotations

import contextlib
import gc
import itertools
import time
from dataclasses import dataclass

import jax
from jax.profiler import TraceAnnotation

COMPILE_EVENTS = "/jax/core/compile/"  # tracing, lowering, backend compile


@dataclass(slots=True)
class Record:
    id: int
    name: str
    start_ns: int             # time.perf_counter_ns()
    end_ns: int
    parent: int | None        # id of the span open when this one began
    request: int | None       # id of the outermost span open then
    attrs: dict


class Buffer:
    """At most ``limit`` records; later ones are counted, not kept."""

    def __init__(self, limit: int):
        self.limit, self.records, self.dropped = limit, [], 0

    def add(self, rec: Record) -> None:
        if len(self.records) < self.limit:
            self.records.append(rec)
        else:
            self.dropped += 1


BUFFER = Buffer(1 << 16)
_OFF = contextlib.nullcontext()
_ids = itertools.count()
_open: list = []   # the spans open now, innermost last (the serving thread's)


class _Span:
    __slots__ = ("rec", "_ann")

    def __init__(self, name: str, attrs: dict):
        rid = next(_ids)
        top = _open[-1].rec if _open else None
        self.rec = Record(rid, name, 0, 0, top and top.id,
                          top.request if top else rid, attrs)
        self._ann = TraceAnnotation(name, **attrs)

    def set(self, **attrs) -> None:
        """Attributes known only inside the span."""
        self.rec.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __enter__(self):
        self._ann.__enter__()
        _open.append(self)
        self.rec.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.rec.end_ns = time.perf_counter_ns()
        _open.pop()
        self._ann.__exit__(*exc)
        BUFFER.add(self.rec)
        return False


def span(name: str, **attrs):
    """A context that records ``name`` while a profiler session runs; its
    value is the span (``.set(**attrs)`` adds attributes), or ``None``."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name, attrs)


def spans() -> list[Record]:
    return list(BUFFER.records)


def clear() -> None:
    BUFFER.records.clear()
    BUFFER.dropped = 0


def _stall(name: str, start_ns: int, end_ns: int, **attrs) -> None:
    top = _open[-1].rec if _open else None
    BUFFER.add(Record(next(_ids), name, start_ns, end_ns, top and top.id,
                      top and top.request, attrs))


_gc_open: list = []   # (start_ns, annotation) of the collection under way


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if TraceAnnotation.is_enabled():
            ann = TraceAnnotation("host.gc", generation=info["generation"])
            ann.__enter__()
            _gc_open.append((time.perf_counter_ns(), ann))
    elif _gc_open:
        start, ann = _gc_open.pop()
        _stall("host.gc", start, time.perf_counter_ns(),
               generation=info["generation"])
        ann.__exit__(None, None, None)


def _on_compile(event: str, duration: float, **kw) -> None:
    if event.startswith(COMPILE_EVENTS) and TraceAnnotation.is_enabled():
        end = time.perf_counter_ns()
        _stall("host.compile", end - round(duration * 1e9), end,
               event=event, **kw)


gc.callbacks.append(_on_gc)
jax.monitoring.register_event_duration_secs_listener(_on_compile)
