"""The program's own spans (``repro.runtime.tracing``) on the trace's clock.

The serving session records its spans in memory while the profiler runs,
on the host's ``perf_counter_ns`` clock; the trace holds the harness's
``bench.task`` spans on the profiler's clock. Each task is one
``generate`` call, so the k-th ``serve.generate`` record belongs to the
k-th task span: the offset between the clocks is the median of their start
differences, and the largest difference from it (the residual) says how
well they agree. With the records on the trace's clock, each idle gap of
the device is named by the innermost program span at its middle, or
``client`` where the host was in none (the harness's own loop).

A program without the tracing module (an older commit) has no records:
``view`` is then ``None``, and so is every metric that reads it.
"""
from __future__ import annotations

import bisect
import functools
import statistics
from dataclasses import dataclass

CALL = "serve.generate"
CLIENT = "client"


@dataclass(frozen=True)
class Span:
    start: float          # ns on the trace's clock
    end: float
    name: str
    attrs: dict


@dataclass
class View:
    spans: list           # Span, by start
    offset_ns: float      # added to the program's times
    residual_ns: float    # largest |start difference - offset| over tasks
    gaps: list            # (start, end) idle on the first device, in order


def records():
    """The program's records, or None where it has no tracing module."""
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    return tracing.spans()


def align(task_starts, call_starts):
    """(offset, residual) that put the k-th call on the k-th task, or None
    when their counts differ."""
    if not task_starts or len(task_starts) != len(call_starts):
        return None
    diffs = [t - c for t, c in zip(sorted(task_starts), sorted(call_starts))]
    offset = statistics.median(diffs)
    return offset, max(abs(d - offset) for d in diffs)


def idle_gaps(tr) -> list:
    """Holes in the first device's busy intervals within the window of
    ``tr`` (a ``trace.Trace``)."""
    busy = tr._busy(sorted(tr.devices)[0])
    edges = [tr.t0] + [x for iv in busy for x in iv] + [tr.t1]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def build(tr, recs) -> View | None:
    calls = [r.start_ns for r in recs if r.name == CALL]
    fit = align([s for s, _ in tr.spans], calls)
    if fit is None:
        return None
    offset, residual = fit
    spans = sorted((Span(r.start_ns + offset, r.end_ns + offset, r.name,
                         r.attrs) for r in recs),
                   key=lambda s: (s.start, -s.end))
    return View(spans, offset, residual, idle_gaps(tr))


def innermost(spans, t: float) -> Span | None:
    """The innermost span open at ``t`` (the latest to start), if any."""
    inner = None
    for s in spans:
        if s.start > t:
            break
        if t < s.end and (inner is None or (s.start, -s.end) >=
                          (inner.start, -inner.end)):
            inner = s
    return inner


def longest_gaps(v: View, k: int = 10) -> list:
    """The ``k`` longest idle gaps as (start, seconds, innermost span or
    None), longest first."""
    top = sorted(v.gaps, key=lambda g: g[0] - g[1])[:k]
    return [(s, (e - s) * 1e-9, innermost(v.spans, (s + e) / 2))
            for s, e in top]


def report(v: View, tr) -> str:
    """The alignment, each span name's count and median and largest host
    milliseconds, and the longest gaps with the span, its step, the task
    and the seconds into it."""
    by = {}
    for s in v.spans:
        by.setdefault(s.name, []).append((s.end - s.start) * 1e-6)
    lines = [f"offset {v.offset_ns:.0f} ns, largest residual "
             f"{v.residual_ns:.0f} ns"]
    lines += [f"{n}: {len(ms)} spans, median {statistics.median(ms):.3f} ms,"
              f" max {max(ms):.3f} ms" for n, ms in sorted(by.items())]
    starts = [a for a, _ in tr.spans]
    for start, seconds, s in longest_gaps(v):
        k = bisect.bisect_right(starts, start) - 1
        step = f" step {s.attrs['step']}" if s and "step" in s.attrs else ""
        lines.append(f"gap {seconds:.6f} s: {s.name if s else CLIENT}{step}, "
                     f"task {k} +{(start - starts[k]) * 1e-9:.3f} s")
    return "\n".join("[program spans] " + x for x in lines)


@functools.lru_cache(maxsize=1)
def _view(tr) -> View | None:
    recs = records()
    if not recs:
        return None
    v = build(tr, recs)
    from repro.runtime import tracing
    print(f"[program spans] {len(recs)} records, {tracing.BUFFER.dropped} "
          f"dropped, {sum(r.name == CALL for r in recs)} {CALL} spans for "
          f"{len(tr.spans)} task spans" + ("" if v else ": not aligned"))
    if v:
        print(report(v, tr))
    return v


def view(run) -> View | None:
    """The traced window's program spans and idle gaps, once per trace."""
    return None if run.trace is None else _view(run.trace)
