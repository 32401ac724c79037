"""Model FLOPs of every prompt and generated token of the window's finished
tasks (``work.task_flops``) over the window (host clock) times the chip's
peak bf16 FLOP/s, in %."""
from benchmarks.chip import work


def read(run):
    if run.peaks is None or not run.done:
        return None
    flops = sum(work.task_flops(run.spec, d.tokens.shape[0],
                                d.task.prompt_len, d.tokens.shape[1])
                for d in run.done)
    return 100.0 * flops / (run.elapsed * run.peaks["flops_per_s"])
