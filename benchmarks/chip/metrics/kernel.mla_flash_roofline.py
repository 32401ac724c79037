"""Share (%) of its roofline that the Pallas flash kernel reached in the
window's latent-attention prefills (q/k width nope + rope, v width v): the
least time the chip needs for the causal prefill attention of every
finished task (``work_mla_moe.mla_flash_work``: FLOPs over peak FLOP/s or
bytes over peak bandwidth, whichever is larger) over the summed device time
of the kernel's events.

The events are those of the whole trace, not only those inside the task
spans' window: the trace holds the profiler session, which is the window's
tasks and nothing else, but its shift of the device's clock onto the
host's can leave part of the device's events outside the window (a third
of them in one traced run of ``v2lite.ragsynth`` on a TPU v5e), which would
time only part of the work counted here."""
from benchmarks.chip import work_mla_moe


def is_kernel(name, program):
    """The Pallas call's instruction, 'flash_attention_pallas.<n>'."""
    return name.startswith("flash_attention_pallas")


def session_seconds(trace, pred) -> float:
    """Summed device seconds of the trace's operations for which
    ``pred(name, program)`` holds, window or not, averaged over devices."""
    per = [sum(o[1] - o[0] for o in d["ops"] if pred(*o[2:]))
           for d in trace.devices.values()]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def read(run):
    if run.trace is None or run.peaks is None or not run.done:
        return None
    seconds = session_seconds(run.trace, is_kernel)
    if seconds <= 0:
        return None
    flops = nbytes = 0.0
    for d in run.done:
        f, b = work_mla_moe.mla_flash_work(run.spec, d.tokens.shape[0],
                                           d.task.prompt_len)
        flops, nbytes = flops + f, nbytes + b
    least = max(flops / run.peaks["flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
