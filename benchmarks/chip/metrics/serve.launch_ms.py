"""Median milliseconds, over the window's tasks, from the start of a
``serve.generate`` span to the start of the first device operation at or
after it: the host's share of a task's start (cache allocation and the
first dispatch), with the program's spans on the trace's clock."""
import bisect
import statistics

from benchmarks.chip import program_spans


def read(run):
    v = program_spans.view(run)
    if v is None:
        return None
    ops = run.trace.devices[sorted(run.trace.devices)[0]]["ops"]
    starts = sorted(o[0] for o in ops)
    waits = []
    for s in v.spans:
        if s.name == program_spans.CALL:
            i = bisect.bisect_left(starts, s.start)
            if i < len(starts):
                waits.append((starts[i] - s.start) * 1e-6)
    return statistics.median(waits) if waits else None
