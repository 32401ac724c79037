"""CPU checks of the chip bring-up pieces: ``chip_smoke.py``'s decode-vs-
prefill check at reduced widths, its refusal to run without a TPU, and the
persistent compilation cache's location."""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from repro.configs.registry import get_config
from repro.launch import compile_cache
from repro.models.model_zoo import build_model
from repro.runtime.serve import ServeSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _session_and_prompts(arch, seed=0, S=32):
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    sess = ServeSession(model, model.init(jax.random.PRNGKey(seed)))
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (4, S + 1), dtype=np.int32)
    return sess, jnp.asarray(prompts), S + 8


@pytest.mark.parametrize("arch", ["deepseek-7b", "mamba2-370m"])
def test_decode_prefill_gap_within_tolerance(arch):
    sess, prompts, max_len = _session_and_prompts(arch)
    gap, logits = chip_smoke.decode_prefill_gap(sess, prompts, max_len)
    assert all(np.isfinite(x).all() for x in logits)
    assert [x.shape for x in logits] == [(4, sess.model.cfg.vocab_size)] * 3
    assert gap <= chip_smoke.DECODE_RTOL, gap


@pytest.mark.parametrize("arch", ["deepseek-7b", "mamba2-370m"])
def test_decode_prefill_gap_catches_a_wrong_token(arch):
    """The tolerance separates a right decode from one fed the wrong token."""
    sess, prompts, max_len = _session_and_prompts(arch)
    wrong = prompts.at[:, -1].set((prompts[:, -1] + 1)
                                  % sess.model.cfg.vocab_size)
    _, cache = sess.prefill(sess.params, {"tokens": prompts[:, :-1]},
                            sess.model.init_cache(4, max_len))
    _, step, _ = sess.decode(sess.params, cache, wrong[:, -1:],
                             jnp.asarray(prompts.shape[1] - 1, jnp.int32))
    full, _ = sess.prefill(sess.params, {"tokens": prompts},
                           sess.model.init_cache(4, max_len))
    step, full = np.asarray(step, np.float32), np.asarray(full, np.float32)
    gap = np.linalg.norm(step - full) / np.linalg.norm(full)
    assert gap > chip_smoke.DECODE_RTOL, gap


def _run(args, cwd, env):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = _run([os.path.join(REPO, "chip_smoke.py")], REPO, env)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run(["chip_smoke.py"], tmp_path, {**env, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    entry = os.path.join(path, "entry")
    ignored = subprocess.run(["git", "check-ignore", "-q", entry], cwd=REPO)
    assert ignored.returncode in (0, 128)  # 128: not a git checkout


def test_compile_cache_honours_the_environment(tmp_path):
    """With the variable set, cache entries land there and nowhere else."""
    cache = tmp_path / "cache"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "PYTHONPATH": os.path.join(REPO, "src")}
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()\n")
    before = set(os.listdir(compile_cache.DEFAULT_DIR)) \
        if os.path.isdir(compile_cache.DEFAULT_DIR) else set()
    r = _run(["-c", code], tmp_path, env)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(cache)
    assert any(n.startswith("jit__lambda") for n in os.listdir(cache))
    after = set(os.listdir(compile_cache.DEFAULT_DIR)) \
        if os.path.isdir(compile_cache.DEFAULT_DIR) else set()
    assert after == before
