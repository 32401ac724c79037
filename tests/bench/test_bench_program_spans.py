"""The program's spans on the trace's clock (``program_spans.py``) and the
four metrics that read them, on a trace and program records written out
by hand, the program's clock 1e9 ns behind the trace's."""
from types import SimpleNamespace

import pytest

from benchmarks.chip import harness, program_spans, trace
from repro.runtime.tracing import Record

DEV = "/device:TPU:0"
OFF = 10**9


def hand_trace():
    # two tasks at [1000, 5000) and [6000, 9000); the first program starts
    # with the first task, so the device's clock needs no shift
    modules = [(1000, 1100, "jit_broadcast_in_dim(1)"),
               (1150, 2500, "jit_prefill(2)"), (2500, 3200, "jit_decode(3)"),
               (3600, 4000, "jit_decode(3)"),
               (4850, 4950, "jit_concatenate(4)"),
               (6700, 8000, "jit_prefill(2)"), (8200, 8700, "jit_decode(3)")]
    ops = [(s, e, "fusion", trace.module_name(n)) for s, e, n in modules]
    return trace.Trace({DEV: {"ops": ops, "modules": modules}},
                       [(1000, 5000), (6000, 9000)])


def rec(i, name, start, end, parent, request, **attrs):
    """A record at trace times ``start``/``end``, on the program's clock."""
    return Record(i, name, start - OFF, end - OFF, parent, request, attrs)


def hand_records():
    return [
        rec(0, "serve.generate", 1000, 4900, None, 0, cache_bytes=3_000_000),
        rec(1, "serve.init_cache", 1000, 1050, 0, 0),
        rec(2, "serve.prefill", 1100, 1200, 0, 0),
        rec(3, "serve.sample", 1200, 1250, 0, 0),
        rec(4, "serve.decode", 1300, 1350, 0, 0, step=1),
        rec(5, "host.gc", 3000, 3500, 0, 0, generation=2),
        rec(6, "serve.concat", 4700, 4800, 0, 0),
        rec(7, "serve.generate", 6000, 8800, None, 7, cache_bytes=1_000_000),
        rec(8, "serve.init_cache", 6000, 6100, 7, 7),
        rec(9, "host.compile", 6100, 6500, 8, 7, event="backend_compile"),
        rec(10, "host.gc", 6300, 6600, 7, 7, generation=0),
        rec(11, "serve.prefill", 6600, 6650, 7, 7),
        rec(12, "serve.decode", 7000, 7030, 7, 7, step=1),
        rec(13, "serve.decode", 8050, 8150, 7, 7, step=2),
    ]


@pytest.fixture()
def run(monkeypatch):
    recs = hand_records()
    monkeypatch.setattr(program_spans, "records", lambda: recs)
    program_spans._view.cache_clear()
    yield SimpleNamespace(trace=hand_trace(), records=recs)
    program_spans._view.cache_clear()


def test_the_offset_is_recovered_exactly():
    v = program_spans.build(hand_trace(), hand_records())
    assert (v.offset_ns, v.residual_ns) == (OFF, 0)
    assert [(s.start, s.end) for s in v.spans[:2]] == [(1000, 4900),
                                                       (1000, 1050)]


def test_the_residual_is_the_largest_start_difference_from_the_median():
    recs = hand_records()
    recs[7].start_ns += 30    # the second call starts 30 ns later
    assert program_spans.align([1000, 6000], [r.start_ns for r in recs
                                              if r.name == "serve.generate"]) \
        == (OFF - 15, 15)


def test_a_count_mismatch_gives_none(run, monkeypatch):
    recs = [r for r in hand_records() if r.id != 7]
    assert program_spans.build(hand_trace(), recs) is None
    assert program_spans.align([], []) is None
    monkeypatch.setattr(program_spans, "records", lambda: recs)
    assert program_spans.view(run) is None
    for name in ("serve.launch_ms", "serve.stall_idle_share"):
        assert harness.load_module(harness.ROOT, "metrics", name).read(
            run) is None


def test_gaps_are_named_by_the_innermost_span_or_client():
    v = program_spans.build(hand_trace(), hand_records())
    named = [(start, seconds, s.name if s else "client")
             for start, seconds, s in program_spans.longest_gaps(v)]
    assert named == [(4950, pytest.approx(1750e-9), "client"),
                     (4000, pytest.approx(850e-9), "serve.generate"),
                     (3200, pytest.approx(400e-9), "host.gc"),
                     (8700, pytest.approx(300e-9), "client"),
                     (8000, pytest.approx(200e-9), "serve.decode"),
                     (1100, pytest.approx(50e-9), "serve.prefill")]
    assert len(program_spans.longest_gaps(v, 2)) == 2
    # a child that starts with its parent is the innermost
    assert program_spans.innermost(v.spans, 1020).name == "serve.init_cache"
    assert program_spans.innermost(v.spans, 6200).name == "host.compile"
    assert program_spans.innermost(v.spans, 5500) is None
    lines = program_spans.report(v, hand_trace()).splitlines()
    assert lines[0] == "[program spans] offset 1000000000 ns, largest " \
        "residual 0 ns"
    assert "[program spans] gap 0.000000 s: serve.decode step 2, task 1 " \
        "+0.000 s" in lines


@pytest.mark.parametrize("metric, value", [
    # decode spans of 50, 30 and 100 ns
    ("serve.decode_dispatch_ms", 50e-6),
    # first operations 0 ns and 700 ns after each call's start
    ("serve.launch_ms", 350e-6),
    # idle under host.gc: [3200, 3500); under gc + compile, merged
    # [6100, 6600) of the gap [4950, 6700): 800 ns of an 8000 ns window
    ("serve.stall_idle_share", 10.0),
    # 3e6 and 1e6 bytes
    ("serve.cache_mb_per_task", 2.0),
])
def test_each_reader_gives_its_hand_worked_value(run, metric, value):
    reader = harness.load_module(harness.ROOT, "metrics", metric)
    assert reader.read(run) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["serve.decode_dispatch_ms",
                                    "serve.launch_ms",
                                    "serve.stall_idle_share",
                                    "serve.cache_mb_per_task"])
def test_a_program_without_spans_reads_none(run, monkeypatch, metric):
    monkeypatch.setattr(program_spans, "records", lambda: None)
    reader = harness.load_module(harness.ROOT, "metrics", metric)
    assert reader.read(run) is None
