"""Reduction of a profiler trace to device busy time, idle gaps, program and
kernel times: on events written out by hand, and on a small trace recorded
on a TPU v5e (``data/tiny.xplane.pb``: two tasks, each one ``prefill``
program with the flash-attention and SSD-scan kernels and one ``decode``
program; see ``benchmarks/chip/record_trace.py``)."""
import os

import pytest

from benchmarks.chip import harness, trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")
DEV = "/device:TPU:0"


def hand_trace():
    # two tasks at [100, 400) and [500, 800); ops overlap inside a task
    ops = [(100, 200, "fusion.1", "jit_prefill"),
           (150, 250, "fusion.2", "jit_prefill"),
           (300, 350, "fusion.3", "jit_decode"),
           (500, 600, "argmax", "jit_argmax"),
           (700, 790, "fusion.3", "jit_decode"),
           (900, 950, "late", "jit_decode")]          # after the window
    modules = [(100, 250, "jit_prefill(3)"), (300, 350, "jit_decode(4)"),
               (500, 600, "jit_argmax(5)"), (700, 790, "jit_decode(4)"),
               (900, 950, "jit_decode(4)")]
    return trace.Trace({DEV: {"ops": ops, "modules": modules}},
                       [(100, 400), (500, 800)])


def test_window_busy_and_idle():
    t = hand_trace()
    assert t.window_s() == pytest.approx(700e-9)
    # union: [100, 250) + [300, 350) + [500, 600) + [700, 790) = 390 ns
    assert t.busy_s() == pytest.approx(390e-9)


def test_program_and_operation_times():
    t = hand_trace()
    assert t.module_runs("jit_decode") == (pytest.approx(140e-9), 2)
    assert t.module_runs("jit_prefill") == (pytest.approx(150e-9), 1)
    assert t.op_seconds(lambda n, p: p == "jit_decode") == \
        pytest.approx(140e-9)


def test_gaps_are_named_by_the_host_and_the_next_program():
    assert hand_trace().gaps() == [
        ("between tasks, before jit_argmax", pytest.approx(150e-9)),
        ("in a task, before jit_decode", pytest.approx(100e-9)),
        ("in a task, before jit_decode", pytest.approx(50e-9)),
        ("in a task, before window end", pytest.approx(10e-9))]


def test_breakdown_lists_at_most_ten():
    b = hand_trace().breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0] == ["jit_decode/fusion.3", pytest.approx(140e-9)]


def test_operations_take_the_program_that_encloses_them():
    ops = [(100, 120, "fusion.1"), (130, 140, "fusion.2"), (400, 410, "copy")]
    modules = [(95, 125, "jit_prefill(1)"), (128, 150, "jit_decode(2)")]
    named = trace._with_modules(ops, modules)
    assert [o[3] for o in named] == ["jit_prefill", "jit_decode", ""]


def test_a_loop_event_is_left_out_for_its_body():
    ops = [(100, 400, "while.2"), (110, 200, "fusion.1"),
           (200, 390, "fusion.2"), (400, 450, "copy")]
    assert [o[2] for o in trace._leaves(ops)] == ["fusion.1", "fusion.2",
                                                   "copy"]


def test_instruction_names():
    assert trace.instruction(
        "%flash_attention_pallas.1 = bf16[1,2]{1,0} custom-call(%a)") == \
        "flash_attention_pallas.1"


def test_no_task_span_is_an_error():
    with pytest.raises(ValueError):
        trace.Trace({DEV: {"ops": [], "modules": []}}, [])


def test_recorded_chip_trace():
    t = trace.load(DATA)
    assert list(t.devices) == [DEV]
    assert len(t.spans) == 2
    assert 0 < t.busy_s() < t.window_s()
    seconds, runs = t.module_runs("jit_decode")
    assert runs == 2 and seconds > 0
    assert t.module_runs("jit_prefill")[1] == 2
    for name in ("kernel.flash_attention_roofline",
                 "kernel.ssd_scan_roofline"):
        reader = harness.load_module(harness.ROOT, "metrics", name)
        kernel = t.op_seconds(reader.is_kernel)
        assert 0 < kernel < t.module_runs("jit_prefill")[0]
    b = t.breakdown()
    assert b["device_ops"] and b["idle_gaps"]
