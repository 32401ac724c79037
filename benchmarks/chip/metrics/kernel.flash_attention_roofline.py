"""Share (%) of its roofline that the Pallas flash-attention kernel reached
in the window: the least time the chip needs for the causal prefill
attention of every finished task (``work.flash_attention_work``: FLOPs over
peak FLOP/s or bytes over peak bandwidth, whichever is larger) over the
summed device time of the kernel's events."""
from benchmarks.chip import work

def is_kernel(name, program):
    """The Pallas call's instruction, 'flash_attention_pallas.<n>'."""
    return name.startswith("flash_attention_pallas")


def read(run):
    if run.trace is None or run.peaks is None or not run.done:
        return None
    seconds = run.trace.op_seconds(is_kernel)
    if seconds <= 0:
        return None
    flops = nbytes = 0.0
    for d in run.done:
        f, b = work.flash_attention_work(run.spec, d.tokens.shape[0],
                                         d.task.prompt_len)
        flops, nbytes = flops + f, nbytes + b
    least = max(flops / run.peaks["flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
