"""Per-kernel validation: shape/dtype sweeps, Pallas interpret mode vs the
pure-jnp oracle in ``kernels/ref.py`` (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (VMEM_BUDGET, flash_attention_pallas,
                                          flash_blocks, flash_plan,
                                          vmem_bytes)
from repro.kernels.ssd_scan import ssd_scan_pallas


def _qkv(key, B, Sq, Sk, H, KVH, D, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, Sq, H, D), dtype)
    k = jax.random.normal(k2, (B, Sk, KVH, D), dtype)
    v = jax.random.normal(k3, (B, Sk, KVH, D), dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _case(B, Sq, Sk, H, KVH, D, *, dv=None, q_offset=0, blocks=(64, 64),
          causal=True, kv_valid=None, id=None):
    """One flash case, named by its shape unless ``id`` names it."""
    return pytest.param(B, Sq, Sk, H, KVH, D, dv or D, q_offset, blocks,
                        causal, kv_valid,
                        id=id or f"{B}-{Sq}-{Sk}-{H}-{KVH}-{D}")


class TestFlashAttention:
    @pytest.mark.parametrize(
            "B,Sq,Sk,H,KVH,D,Dv,q_offset,blocks,causal,kv_valid", [
        _case(1, 128, 128, 4, 4, 64),      # MHA
        _case(2, 128, 128, 4, 2, 64),      # GQA 2:1
        _case(1, 256, 256, 8, 1, 32),      # MQA
        _case(1, 100, 100, 4, 2, 64),      # ragged (padding path)
        _case(1, 64, 192, 2, 2, 128),      # cross lengths
        # tiles above 128, several q and k blocks: whole blocks skipped
        _case(1, 512, 512, 2, 1, 64, blocks=(256, 128), id="skip-256x128"),
        _case(1, 512, 512, 2, 2, 64, blocks=(128, 256), id="skip-128x256"),
        # a chunk after a prefix: q_offset > 0, Sq != Sk
        _case(1, 384, 768, 2, 2, 64, q_offset=384, blocks=(128, 256),
              id="q_offset-384"),
        # the planned tiles at a ragged length (1280: 640 x 640) and at 100
        _case(1, 1280, 1280, 2, 1, 64, blocks=None, id="planned-1280"),
        _case(1, 100, 100, 4, 2, 64, blocks=None, id="planned-100"),
        # latent attention's narrower v (q/k 192, v 128)
        _case(1, 512, 512, 2, 2, 192, dv=128, blocks=(256, 256),
              id="dv128-d192"),
        # cross-attention skips nothing
        _case(1, 64, 640, 2, 2, 64, blocks=None, causal=False,
              id="noncausal-planned"),
        # keys past kv_valid: a k block straddles it (600), and the whole
        # block beyond it is skipped (300)
        _case(1, 640, 640, 2, 1, 64, blocks=(128, 384), kv_valid=600,
              id="kv_valid-600"),
        _case(1, 640, 640, 2, 1, 64, blocks=(128, 256), kv_valid=300,
              id="kv_valid-300"),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_naive(self, B, Sq, Sk, H, KVH, D, Dv, q_offset, blocks,
                      causal, kv_valid, dtype):
        q, k, _ = _qkv(jax.random.PRNGKey(0), B, Sq, Sk, H, KVH, D, dtype)
        v = jax.random.normal(jax.random.PRNGKey(4), (B, Sk, KVH, Dv), dtype)
        bq, bk = blocks or (None, None)
        got = flash_attention_pallas(q, k, v, causal=causal,
                                     q_offset=q_offset, kv_valid=kv_valid,
                                     interpret=True, block_q=bq, block_k=bk)
        want = ref.mha_naive(
            q, k, v, causal=causal, q_offset=q_offset,
            kv_len=None if kv_valid is None else jnp.full((B,), kv_valid))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=TOL[dtype], rtol=TOL[dtype])

    @pytest.mark.parametrize("window,S,blocks", [
        pytest.param(0, 128, (64, 64), id="0"),
        pytest.param(32, 128, (64, 64), id="32"),
        # a window under one tile, so that whole blocks fall out of it
        pytest.param(100, 1024, (256, 128), id="100-S1024"),
    ])
    @pytest.mark.parametrize("softcap", [0.0, 20.0])
    def test_window_softcap(self, window, S, blocks, softcap):
        q, k, v = _qkv(jax.random.PRNGKey(1), 1, S, S, 4, 2, 64,
                       jnp.float32)
        got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                     softcap=softcap, interpret=True,
                                     block_q=blocks[0], block_k=blocks[1])
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

    @pytest.mark.parametrize("S", [128, 256, 1024, 1280, 4096, 8192])
    @pytest.mark.parametrize("D,Dv", [(128, 128), (192, 128)])
    def test_plan_at_the_cells_shapes(self, S, D, Dv):
        """The planned tiles divide the length padded to 128, fit the VMEM
        budget, and the grid steps that run are the causal blocks."""
        bq, bk = flash_plan(S, S, D, Dv, jnp.bfloat16)
        s_p = -(-S // 128) * 128
        assert s_p % bq == 0 and s_p % bk == 0, (bq, bk)
        assert vmem_bytes(bq, bk, D, Dv) <= VMEM_BUDGET
        if S >= 1024:
            assert bq * bk >= 256 * 256, (bq, bk)
        nq, nk = s_p // bq, s_p // bk
        causal = sum(ik * bk <= (iq + 1) * bq - 1
                     for iq in range(nq) for ik in range(nk))
        assert flash_blocks(S, S, bq, bk) == (causal, nq * nk)

    def test_blocks_at_8192_by_512(self):
        assert flash_blocks(8192, 8192, 512, 512) == (136, 256)

    @pytest.mark.parametrize("Sq,Sk,bq,bk,window,q_offset,kv_valid", [
        (512, 512, 128, 128, 0, 0, None),
        (512, 512, 256, 128, 100, 0, None),
        (256, 768, 128, 256, 0, 512, None),
        (256, 768, 256, 128, 200, 512, 700),
        (640, 640, 128, 384, 300, 0, 600),
    ])
    def test_blocks_are_the_unmasked_blocks(self, Sq, Sk, bq, bk, window,
                                            q_offset, kv_valid):
        """The blocks the kernel computes are those the mask leaves any
        element of, counted on the mask itself."""
        q_pos = q_offset + np.arange(Sq)[:, None]
        k_pos = np.arange(Sk)[None, :]
        mask = (k_pos <= q_pos) & (k_pos < (kv_valid or Sk))
        if window:
            mask &= q_pos - k_pos < window
        assert mask.any(axis=1).all()
        want = sum(mask[iq:iq + bq, ik:ik + bk].any()
                   for iq in range(0, Sq, bq) for ik in range(0, Sk, bk))
        got, steps = flash_blocks(Sq, Sk, bq, bk, window=window,
                                  q_offset=q_offset, kv_valid=kv_valid)
        assert (got, steps) == (want, -(-Sq // bq) * -(-Sk // bk))

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
