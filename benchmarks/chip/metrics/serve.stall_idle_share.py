"""Share (%) of the traced window in which the device is idle while the
host is inside a ``host.gc`` or ``host.compile`` span (a garbage
collection or a compile), with the program's spans on the trace's
clock."""
from benchmarks.chip import program_spans

STALLS = ("host.gc", "host.compile")


def merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a, b) -> float:
    """Summed length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    v = program_spans.view(run)
    if v is None:
        return None
    stalls = merged((s.start, s.end) for s in v.spans if s.name in STALLS)
    window = run.trace.t1 - run.trace.t0
    return 100.0 * overlap(v.gaps, stalls) / window
