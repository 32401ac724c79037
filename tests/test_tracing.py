"""Spans of the serving session (``runtime/tracing.py``), on the CPU at
reduced widths: recorded only inside a profiler session, one request per
``generate`` call, host stalls, the buffer's bound."""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.model_zoo import build_model
from repro.runtime import tracing
from repro.runtime.serve import ServeSession

NEW = 5


@pytest.fixture(scope="module")
def session():
    model = build_model(get_config("deepseek-7b", reduced=True))
    return ServeSession(model, model.init(jax.random.PRNGKey(0)))


@pytest.fixture()
def prompts():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 256,
                              dtype=jnp.int32)


@pytest.fixture(autouse=True)
def empty_buffer():
    tracing.clear()
    yield
    tracing.clear()


def traced(tmp_path, fn, *args):
    with jax.profiler.trace(str(tmp_path)):
        out = np.asarray(fn(*args))
    return out, [r for r in tracing.spans() if r.name.startswith("serve.")]


def test_outside_a_session_nothing_is_recorded(session, prompts):
    np.asarray(session.generate(prompts, NEW))
    assert tracing.spans() == [] and tracing.BUFFER.dropped == 0
    with tracing.span("x") as s:
        assert s is None


def test_generate_records_one_request(session, prompts, tmp_path):
    _, recs = traced(tmp_path, session.generate, prompts, NEW)
    recs.sort(key=lambda r: r.start_ns)
    assert [r.name for r in recs] == (
        ["serve.generate", "serve.init_cache", "serve.prefill",
         "serve.sample"] + ["serve.decode"] * (NEW - 1) + ["serve.concat"])
    top = recs[0]
    assert top.parent is None and top.request == top.id
    assert {r.request for r in recs} == {top.id}
    assert all(r.parent == top.id for r in recs[1:])
    assert [r.attrs["step"] for r in recs[4:-1]] == list(range(1, NEW))
    B, S = prompts.shape
    cache = session.model.init_cache(B, S + NEW)
    assert top.attrs == {
        "batch": B, "prompt_len": S, "new_tokens": NEW,
        "cache_bytes": sum(x.nbytes for x in jax.tree.leaves(cache))}
    for r in recs:
        assert top.start_ns <= r.start_ns <= r.end_ns <= top.end_ns
    for a, b in zip(recs[1:], recs[2:]):
        assert a.end_ns <= b.start_ns


def test_tokens_are_the_same_with_and_without_a_session(session, prompts,
                                                        tmp_path):
    off = np.asarray(session.generate(prompts, NEW))
    on, recs = traced(tmp_path, session.generate, prompts, NEW)
    assert recs and np.array_equal(on, off)


def test_gc_and_compile_are_recorded_as_host_stalls(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("outer"):
            gc.collect()
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    recs = tracing.spans()
    outer = next(r for r in recs if r.name == "outer")
    gcs = [r for r in recs if r.name == "host.gc"]
    compiles = [r for r in recs if r.name == "host.compile"]
    assert any(r.attrs == {"generation": 2} for r in gcs)
    assert {r.attrs["event"] for r in compiles} >= {
        "/jax/core/compile/backend_compile_duration"}
    assert any(r.attrs.get("fun_name") == "<lambda>" for r in compiles)
    for r in gcs + compiles:
        assert r.parent == outer.id and r.request == outer.request
        assert outer.start_ns <= r.start_ns <= r.end_ns <= outer.end_ns


def test_the_buffer_keeps_its_bound_and_counts_what_it_drops(
        tmp_path, monkeypatch):
    monkeypatch.setattr(tracing.BUFFER, "limit", 3)
    with jax.profiler.trace(str(tmp_path)):
        for i in range(5):
            with tracing.span("s", i=i):
                pass
    assert [r.attrs["i"] for r in tracing.spans()] == [0, 1, 2]
    assert tracing.BUFFER.dropped == 2
    tracing.clear()
    assert tracing.spans() == [] and tracing.BUFFER.dropped == 0
