"""The benchmark's float32 references against the program's forward pass, on
the CPU at the registry's reduced sizes, on the same seeded weights.

The program computes in bfloat16 with float32 accumulation, so the two
differ by rounding: at these sizes the relative L2 gap of the logits reads
about 1e-2. A reference that departs from the program's mathematics (a
rotation of the wrong pairs, a convolution the wrong way round, a missing
skip term) reads 0.3 and more.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from benchmarks.chip import harness
from repro.models.model_zoo import build_model

TOL = 5e-2


def _setup(spec, seed):
    ref = harness.load_module(harness.ROOT, "reference", spec["family"])
    model = build_model(harness.program_config(spec, ref))
    w = ref.init_weights(spec, harness.weights_key(seed))
    return ref, model, w


@pytest.mark.parametrize("spec", [bench_tiny.DENSE, bench_tiny.SSM],
                         ids=["dense", "ssm"])
@pytest.mark.parametrize("seed", [0, 2**33 + 1])
def test_reference_matches_the_program(spec, seed):
    ref, model, w = _setup(spec, seed)
    tokens = np.random.default_rng(seed % 97).integers(
        0, spec["vocab_size"], (2, 48)).astype(np.int32)
    got, _, _ = model.apply(ref.program_params(w), {"tokens": tokens})
    want = ref.logits(w, spec, tokens, 0)
    got, want = np.asarray(got, np.float32), np.asarray(want)
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < TOL, rel
    # the head at ``start`` onwards only
    tail = np.asarray(ref.logits(w, spec, tokens, 40))
    np.testing.assert_allclose(tail, want[:, 40:], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("spec", [bench_tiny.DENSE, bench_tiny.SSM],
                         ids=["dense", "ssm"])
def test_float8_control_departs_further(spec):
    ref, model, w = _setup(spec, 3)
    tokens = np.random.default_rng(3).integers(
        0, spec["vocab_size"], (2, 48)).astype(np.int32)
    want = np.asarray(ref.logits(w, spec, tokens, 0))
    got, _, _ = model.apply(ref.program_params(w), {"tokens": tokens})
    ctl = np.asarray(ref.logits(w, spec, tokens, 0, fp8=True))
    rel = lambda x: np.linalg.norm(x - want) / np.linalg.norm(want)
    assert rel(ctl) > 3 * rel(np.asarray(got, np.float32))


def test_a_wrong_reference_is_seen():
    """The comparison has teeth: the dense reference with the rotation
    applied to halves instead of interleaved pairs reads far off."""
    spec = bench_tiny.DENSE
    ref, model, w = _setup(spec, 0)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 48)).astype(np.int32)
    got, _, _ = model.apply(ref.program_params(w), {"tokens": tokens})

    def halves(x, theta):
        T, _, hd = x.shape
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    real = ref._rope
    try:
        ref._rope = halves
        jax.clear_caches()
        want = np.asarray(ref.logits(w, spec, tokens, 0))
    finally:
        ref._rope = real
        jax.clear_caches()
    got = np.asarray(got, np.float32)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) > 0.3
