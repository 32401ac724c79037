"""Share (%) of its roofline that the grouped expert kernel (the Pallas
expert FFN over routed rows) reached in the traced window: the least time
the chip needs for the kernel's work over the summed device time of the
kernel's events.

The work is what the program's counters on the ``serve.generate`` records
say the kernel did (``work_mla_moe.expert_ffn_calls``): FLOPs of the rows
routed to the held experts, and bytes of the weights of every expert the
kernel read plus the rows in and out. For each call's prefill and its
decode steps apart, the least time is the FLOPs over peak FLOP/s or the
bytes over peak bandwidth, whichever is larger; summed over the calls.

The records and the events both cover the whole profiler session, which is
the window's tasks and nothing else, so the events are those of the whole
trace: the trace's shift of the device's clock onto the host's can leave
part of them outside the task spans' window (a third in one traced run of
``v2lite.ragsynth`` on a TPU v5e). None where the program lacks the
counters."""
from benchmarks.chip import program_spans, work_mla_moe


def is_kernel(name, program):
    """The Pallas call's instruction, 'expert_ffn_pallas.<n>'."""
    return name.startswith("expert_ffn_pallas")


def session_seconds(trace, pred) -> float:
    """Summed device seconds of the trace's operations for which
    ``pred(name, program)`` holds, window or not, averaged over devices."""
    per = [sum(o[1] - o[0] for o in d["ops"] if pred(*o[2:]))
           for d in trace.devices.values()]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    calls = [r.attrs for r in program_spans.records() or ()
             if r.name == program_spans.CALL
             and "moe_experts_read" in r.attrs]
    seconds = session_seconds(run.trace, is_kernel)
    if not calls or seconds <= 0:
        return None
    peak_f, peak_b = run.peaks["flops_per_s"], run.peaks["hbm_bytes_per_s"]
    least = 0.0
    for a in calls:
        decode = (a["moe_decode_rows_routed"], a["moe_decode_experts_read"])
        prefill = (a["moe_rows_routed"] - decode[0],
                   a["moe_experts_read"] - decode[1])
        for rows, experts in (prefill, decode):
            f, b = work_mla_moe.expert_ffn_calls(run.spec, rows, experts)
            least += max(f / peak_f, b / peak_b)
    return 100.0 * least / seconds
