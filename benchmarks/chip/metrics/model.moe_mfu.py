"""Model FLOPs of this chip's share for every prompt and generated token of
the window's finished tasks (``work_mla_moe.task_flops``: routed rows at
tokens x k x held / published experts) over the window (host clock) times
the chip's peak bf16 FLOP/s, in %."""
from benchmarks.chip import work_mla_moe


def read(run):
    if run.peaks is None or not run.done:
        return None
    flops = sum(work_mla_moe.task_flops(run.spec, d.tokens.shape[0],
                                        d.task.prompt_len, d.tokens.shape[1])
                for d in run.done)
    return 100.0 * flops / (run.elapsed * run.peaks["flops_per_s"])
