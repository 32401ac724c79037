"""The comparison that decides ``correct``, driven end to end on the CPU.

Each test runs the harness over a tiny cell (``bench_tiny``) past its look
for a chip: the program serves a short window, the reference checks a
sample of what it served. A sound program reads correct; a program broken
underneath, in each way a served cell can break, reads not correct; so does
the float8 control put in its place. (A cell on one chip has no exchange
between chips to leave out.)
"""
import json

import jax.numpy as jnp
import pytest

import bench_tiny
from benchmarks.chip import calibrate, harness
from repro.runtime import serve

CELLS = ["tiny-dense.mix", "tiny-ssm.mix"]
SEED = 2**33 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def run(root, cell, capsys, seed=SEED, trace=0):
    code = harness.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "0.3", "--trace", str(trace)],
                        root=root, on_chip=False)
    out, err = capsys.readouterr()
    assert code == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    compared = result["compared"]
    assert err.strip().splitlines()[-len(compared):] == [
        f"{k} {v['value']!r} limit {v['limit']!r}" for k, v in compared.items()]
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_program_is_correct(root, cell, capsys):
    result = run(root, cell, capsys)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"output_tokens_per_s", "task_s_p50",
                                      "task_s_p90", "setup_s"}


def _state_unchanged(monkeypatch):
    real = serve.build_decode_step

    def broken(model, opts, mesh=None):
        step = real(model, opts, mesh)

        def decode(params, cache, tokens, index, key=None):
            nxt, last, _ = step(params, cache, tokens, index, key)
            return nxt, last, cache
        return decode
    monkeypatch.setattr(serve, "build_decode_step", broken)


def _half_batch(monkeypatch):
    real = serve.ServeSession.generate

    def broken(self, prompts, max_new_tokens=32, extras=None):
        half = real(self, prompts[:prompts.shape[0] // 2], max_new_tokens)
        return jnp.concatenate([half, half], axis=0)
    monkeypatch.setattr(serve.ServeSession, "generate", broken)


def _token_altered(monkeypatch):
    real = serve.ServeSession.generate

    def broken(self, prompts, max_new_tokens=32, extras=None):
        out = real(self, prompts, max_new_tokens)
        mid = max_new_tokens // 2
        return out.at[:, mid].set((out[:, mid] + 1) % 256)
    monkeypatch.setattr(serve.ServeSession, "generate", broken)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_a_broken_program_is_not_correct(root, cell, fault, monkeypatch,
                                         capsys):
    fault(monkeypatch)
    result = run(root, cell, capsys)
    assert result["correct"] is False
    gap = result["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_float8_control_is_not_correct(root, cell):
    """The reference in float8 in the program's place reads above the
    limit, on seeds where the program reads within it."""
    c = harness.load_cell(root, cell)
    limit = c.limits["logit_gap"]
    for seed in (1, SEED):
        r = calibrate.readings(c, seed, 0.3, control=True)
        assert r["program_gap"] <= limit < r["control_gap"], r


def test_no_chip_no_result(root, capsys):
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                      "1", "--trace", "0"], root=root)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
