"""Per-kernel validation: shape/dtype sweeps, Pallas interpret mode vs the
pure-jnp oracle in ``kernels/ref.py`` (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gmm import gmm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _qkv(key, B, Sq, Sk, H, KVH, D, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, Sq, H, D), dtype)
    k = jax.random.normal(k2, (B, Sk, KVH, D), dtype)
    v = jax.random.normal(k3, (B, Sk, KVH, D), dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


class TestFlashAttention:
    @pytest.mark.parametrize("B,Sq,Sk,H,KVH,D", [
        (1, 128, 128, 4, 4, 64),     # MHA
        (2, 128, 128, 4, 2, 64),     # GQA 2:1
        (1, 256, 256, 8, 1, 32),     # MQA
        (1, 100, 100, 4, 2, 64),     # ragged (padding path)
        (1, 64, 192, 2, 2, 128),     # cross lengths
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_naive(self, B, Sq, Sk, H, KVH, D, dtype):
        q, k, v = _qkv(jax.random.PRNGKey(0), B, Sq, Sk, H, KVH, D, dtype)
        got = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                     block_q=64, block_k=64)
        want = ref.mha_naive(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=TOL[dtype], rtol=TOL[dtype])

    @pytest.mark.parametrize("window", [0, 32])
    @pytest.mark.parametrize("softcap", [0.0, 20.0])
    def test_window_softcap(self, window, softcap):
        q, k, v = _qkv(jax.random.PRNGKey(1), 1, 128, 128, 4, 2, 64,
                       jnp.float32)
        got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                     softcap=softcap, interpret=True,
                                     block_q=64, block_k=64)
        want = ref.mha_naive(q, k, v, causal=True, window=window,
                             logit_softcap=softcap)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_kv_valid_mask(self):
        """Decode-style: only the first kv_valid cache entries count."""
        q, k, v = _qkv(jax.random.PRNGKey(2), 1, 1, 256, 4, 2, 64,
                       jnp.float32)
        got = flash_attention_pallas(q, k, v, causal=True, q_offset=99,
                                     kv_valid=100, interpret=True)
        want = ref.mha_naive(q[:, :1], k[:, :100], v[:, :100], causal=True,
                             q_offset=99)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_chunked_ref_equals_naive(self):
        """The CPU execution path (mha_chunked) is the oracle's twin."""
        q, k, v = _qkv(jax.random.PRNGKey(3), 2, 96, 96, 4, 2, 32,
                       jnp.float32)
        got = ref.mha_chunked(q, k, v, causal=True, block_k=32)
        want = ref.mha_naive(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


class TestSSDScan:
    @pytest.mark.parametrize("B,L,nh,P,N,G,chunk", [
        (1, 64, 2, 16, 16, 1, 16),
        (2, 128, 4, 32, 16, 2, 32),
        (1, 96, 2, 16, 32, 1, 32),     # L not multiple of chunk handled above
    ])
    def test_vs_ref(self, B, L, nh, P, N, G, chunk):
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (B, L, nh, P)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, nh)))
        a_log = jnp.ones((nh,)) * 0.5
        b = jax.random.normal(ks[2], (B, L, G, N)) * 0.3
        c = jax.random.normal(ks[3], (B, L, G, N)) * 0.3
        d_skip = jax.random.normal(ks[4], (nh,))
        y_p, st_p = ssd_scan_pallas(x, dt, a_log, b, c, d_skip, chunk=chunk,
                                    interpret=True)
        y_r, st_r = ref.ssd_chunked(x, dt, a_log, b, c, d_skip,
                                    chunk_size=chunk)
        np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(st_p), np.asarray(st_r),
                                   atol=1e-4, rtol=1e-4)

    def test_decode_step_matches_scan(self):
        """Stepwise recurrent decode == chunked scan on the same sequence."""
        B, L, nh, P, N, G = 1, 32, 2, 16, 16, 1
        key = jax.random.PRNGKey(7)
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (B, L, nh, P)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, nh)))
        a_log = jnp.ones((nh,)) * 0.5
        b = jax.random.normal(ks[2], (B, L, G, N)) * 0.3
        c = jax.random.normal(ks[3], (B, L, G, N)) * 0.3
        d_skip = jax.random.normal(ks[4], (nh,))
        y_scan, st_scan = ref.ssd_chunked(x, dt, a_log, b, c, d_skip,
                                          chunk_size=16)
        state = jnp.zeros((B, nh, P, N))
        ys = []
        for t in range(L):
            y_t, state = ref.ssd_decode_step(
                state, x[:, t], dt[:, t], a_log, b[:, t], c[:, t], d_skip)
            ys.append(y_t)
        y_step = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_scan),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(state), np.asarray(st_scan),
                                   atol=1e-4, rtol=1e-4)


class TestGMM:
    @pytest.mark.parametrize("E,C,d,f", [
        (2, 16, 32, 64), (8, 64, 128, 64), (4, 8, 256, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_naive(self, E, C, d, f, dtype):
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(k1, (E, C, d), dtype)
        w = jax.random.normal(k2, (E, d, f), dtype)
        got = gmm_pallas(x, w, interpret=True)
        want = ref.gmm_naive(x, w)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=TOL[dtype] * d ** 0.5,
                                   rtol=TOL[dtype])


class TestOpsDispatch:
    def test_decode_attention_matches_flash(self):
        """The GEMV decode path == flash over the valid prefix."""
        B, Sk, H, KVH, D = 2, 64, 4, 2, 32
        q, k, v = _qkv(jax.random.PRNGKey(5), B, 1, Sk, H, KVH, D,
                       jnp.float32)
        idx = 40
        got = ops.decode_attention(q, k, v, k[:, idx:idx + 1],
                                   v[:, idx:idx + 1], cache_index=idx)
        want = ref.mha_naive(q, k[:, :idx + 1], v[:, :idx + 1], causal=True,
                             q_offset=idx)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("H,KVH,window,softcap", [
        (4, 4, 0, 0.0),      # MHA
        (8, 2, 0, 0.0),      # GQA 4:1
        (4, 2, 16, 0.0),     # sliding window
        (4, 2, 0, 30.0),     # logit softcap
    ])
    @pytest.mark.parametrize("idx", [0, 37, 63])   # first, mid, last slot
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_decode_attention_vs_naive(self, H, KVH, window, softcap, idx,
                                       dtype):
        """Attention over the unwritten cache plus the new token == the naive
        reference over the cache with the token written at ``cache_index``.
        Every cache slot holds data, so a slot at or past the index that
        leaked into the scores would show."""
        B, T, D = 2, 64, 32
        q, kc, vc = _qkv(jax.random.PRNGKey(idx), B, 1, T, H, KVH, D, dtype)
        _, kn, vn = _qkv(jax.random.PRNGKey(idx + 1), B, 1, 1, H, KVH, D,
                         dtype)
        got = ops.decode_attention(q, kc, vc, kn, vn, cache_index=idx,
                                   window=window, logit_softcap=softcap,
                                   scale=0.3)
        kw, vw = kc.at[:, idx].set(kn[:, 0]), vc.at[:, idx].set(vn[:, 0])
        want = ref.mha_naive(q, kw, vw, causal=True, window=window,
                             logit_softcap=softcap, scale=0.3, q_offset=idx,
                             kv_len=jnp.full((B,), idx + 1))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=TOL[dtype], rtol=TOL[dtype])
