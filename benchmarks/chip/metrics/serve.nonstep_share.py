"""Share (%) of the window's device time spent in programs other than the
serving session's jitted prefill and decode steps: the cache's zeroing,
the eager argmax, concatenation and index arithmetic of ``generate``."""

STEPS = ("jit_prefill", "jit_decode")


def read(run):
    if run.trace is None:
        return None
    total = run.trace.op_seconds(lambda name, program: True)
    if total <= 0:
        return None
    steps = run.trace.op_seconds(lambda name, program: program in STEPS)
    return 100.0 * (total - steps) / total
