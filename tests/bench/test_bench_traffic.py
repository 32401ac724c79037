"""The task stream and the end-to-end metrics taken over its tasks."""
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.traffic import TaskStream

MIX = {"driver": "generate", "batch": 8, "new_tokens": 64,
       "buckets": [{"prompt_len": 128, "weight": 3},
                   {"prompt_len": 256, "weight": 1}]}
BIG = 2**33 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_every_block_of_four_holds_the_mix(seed):
    s = TaskStream(MIX, seed, 102400)
    lens = [s.prompt_len(i) for i in range(400)]
    for b in range(100):
        assert sorted(lens[4 * b:4 * b + 4]) == [128, 128, 128, 256]


def test_the_seed_fixes_order_and_prompts():
    a, b = TaskStream(MIX, BIG, 102400), TaskStream(MIX, BIG, 102400)
    for i in (0, 5, 99):
        ta, tb = a.task(i), b.task(i)
        assert ta.prompt_len == tb.prompt_len
        np.testing.assert_array_equal(ta.prompts, tb.prompts)
        assert ta.prompts.shape == (8, ta.prompt_len)
        assert ta.prompts.dtype == np.int32
        assert 0 <= ta.prompts.min() and ta.prompts.max() < 102400


def test_seeds_past_32_bits_give_other_streams():
    """A seed and the same seed plus 2**32 must not collide."""
    low, high = TaskStream(MIX, 12345, 102400), TaskStream(MIX, BIG, 102400)
    assert not np.array_equal(low.task(0).prompts, high.task(0).prompts)
    a = harness.weights_key(12345)
    b = harness.weights_key(BIG)
    import jax
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))


def test_warm_tasks_cover_each_length_once():
    s = TaskStream(MIX, 3, 100)
    assert [t.prompt_len for t in s.warm_tasks()] == [128, 256]
    assert s.prompt_lens == [128, 256]


def _run(latencies, tokens_per_task=4):
    done, t = [], 0.0
    for lat in latencies:
        done.append(SimpleNamespace(
            start=t, end=t + lat,
            tokens=np.zeros((2, tokens_per_task // 2), np.int32),
            task=SimpleNamespace(prompt_len=128)))
        t += lat
    return SimpleNamespace(done=done, elapsed=t, setup_s=1.5)


def _metric(name):
    return harness.load_module(harness.ROOT, "metrics", name)


def test_percentiles_are_taken_over_all_tasks():
    # three runs of tasks: the mean of the runs' medians would read
    # (2 + 5 + 100) / 3; the median over all nine tasks reads 5
    lat = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 100.0, 100.0, 100.0]
    assert _metric("task_s_p50").read(_run(lat)) == 5.0
    lat = list(np.arange(1, 101, dtype=float))
    run = _run(lat)
    assert _metric("task_s_p90").read(run) == pytest.approx(90.1)
    assert _metric("task_s_p50").read(run) == pytest.approx(50.5)


def test_rate_is_all_tokens_over_the_whole_window():
    run = _run([0.5, 1.5, 1.0], tokens_per_task=8)
    assert _metric("output_tokens_per_s").read(run) == pytest.approx(24 / 3.0)
    assert _metric("setup_s").read(run) == 1.5


def test_no_task_reads_no_latency():
    run = _run([])
    assert _metric("task_s_p50").read(run) is None
