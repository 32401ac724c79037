"""Median host milliseconds of one decode iteration of ``generate``: its
``serve.decode`` span, around the step's dispatch and the index add, over
the window. Read from the program's own records; the device runs the step
behind it, so a reading near ``model.decode_step_ms`` means the host waits
for the device at each dispatch."""
import statistics

from benchmarks.chip import program_spans


def read(run):
    ms = [(r.end_ns - r.start_ns) * 1e-6 for r in program_spans.records() or ()
          if r.name == "serve.decode"]
    return statistics.median(ms) if ms else None
