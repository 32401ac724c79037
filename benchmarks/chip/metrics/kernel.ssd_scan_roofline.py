"""Share (%) of its roofline that the Pallas SSD scan kernel reached in the
window: the least time the chip needs for the chunked SSD of every finished
task's prompt (``work.ssd_scan_work``: FLOPs over peak FLOP/s or bytes over
peak bandwidth, whichever is larger) over the summed device time of the
kernel's events."""
from benchmarks.chip import work

def is_kernel(name, program):
    """The Pallas call's instruction, 'ssd_scan_pallas.<n>'."""
    return name.startswith("ssd_scan_pallas")


def read(run):
    if run.trace is None or run.peaks is None or not run.done:
        return None
    seconds = run.trace.op_seconds(is_kernel)
    if seconds <= 0:
        return None
    flops = nbytes = 0.0
    for d in run.done:
        f, b = work.ssd_scan_work(run.spec, d.tokens.shape[0],
                                  d.task.prompt_len)
        flops, nbytes = flops + f, nbytes + b
    least = max(flops / run.peaks["flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
