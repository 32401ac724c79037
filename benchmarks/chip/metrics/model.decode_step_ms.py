"""Device milliseconds of one run of the jitted decode step, averaged over
the runs in the window (trace's program events)."""


def read(run):
    if run.trace is None:
        return None
    seconds, runs = run.trace.module_runs("jit_decode")
    return 1e3 * seconds / runs if runs else None
