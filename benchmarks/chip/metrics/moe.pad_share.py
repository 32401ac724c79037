"""Share (%) of the rows the grouped expert kernel computed that no token
was routed to: 1 - routed / computed, summed over the window's
``serve.generate`` records (``moe_rows_routed``: assignments that fell on
the held experts; ``moe_rows_computed``: rows of the kernel's tiles in
use, over layers, prefill and decode). Padding fills each expert's last
tile. Read from the program's own records; None where they lack the
counters."""
from benchmarks.chip import program_spans


def read(run):
    calls = [r.attrs for r in program_spans.records() or ()
             if r.name == program_spans.CALL
             and "moe_rows_computed" in r.attrs]
    computed = sum(a["moe_rows_computed"] for a in calls)
    if not computed:
        return None
    return 100.0 * (1.0 - sum(a["moe_rows_routed"] for a in calls) / computed)
