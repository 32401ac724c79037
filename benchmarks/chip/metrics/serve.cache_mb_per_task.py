"""Megabytes (1e6 bytes) of cache that ``generate`` allocates per task: the
summed ``cache_bytes`` of the window's ``serve.generate`` spans over their
count. Read from the program's own records."""
from benchmarks.chip import program_spans


def read(run):
    sizes = [r.attrs["cache_bytes"] for r in program_spans.records() or ()
             if r.name == "serve.generate"]
    return sum(sizes) / len(sizes) / 1e6 if sizes else None
