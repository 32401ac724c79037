"""Compile the device path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what the Pallas interpreter accepts: blocks off the
(8, 128) tiling, too much VMEM, a program larger than HBM. These tests compile
the kernels at the published widths of the models that use them, and the
full-width deepseek-7b and deepseek-v2-lite decode steps, for one chip of a
``v5e:2x2`` topology; each decode step's compiled program must also write
its donated cache in place.
Nothing runs; only the compiler is exercised.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every pytest worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gmm import expert_ffn_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.models.model_zoo import build_model
from repro.models.moe import dispatch_rows
from repro.runtime.serve import ServeOptions, abstract_cache, build_decode_step

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described device is written to the cache but cannot be
    read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("batch,seq", [
    pytest.param(4, 128, id="128"),
    pytest.param(4, 2048, id="2048"),
    pytest.param(2, 1280, id="2-1280"),   # ds7b.rag_grade's long prompts
])
def test_flash_attention_compiles_at_deepseek_7b_widths(one_chip, batch, seq):
    """At the tiles flash_plan picks for the shape."""
    cfg = get_config("deepseek-7b")
    qkv = _on(one_chip, (batch, seq, cfg.n_heads, cfg.head_dim_))
    compiled = jax.jit(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True)
    ).lower(qkv, qkv, qkv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_370m_widths(one_chip):
    cfg = get_config("mamba2-370m")
    s = cfg.ssm
    H = s.expand * cfg.d_model // s.head_dim
    B, L, f32 = 2, 2 * s.chunk_size, jnp.float32
    compiled = jax.jit(
        lambda x, dt, a, b, c, d: ssd_scan_pallas(x, dt, a, b, c, d,
                                                  chunk=s.chunk_size)
    ).lower(_on(one_chip, (B, L, H, s.head_dim)),
            _on(one_chip, (B, L, H), f32), _on(one_chip, (H,), f32),
            _on(one_chip, (B, L, s.n_groups, s.d_state)),
            _on(one_chip, (B, L, s.n_groups, s.d_state)),
            _on(one_chip, (H,), f32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_deepseek_7b_decode_step_fits_one_v5e(one_chip):
    """Published widths, batch 4, a 144-token cache: what chip_smoke.py
    serves. Parameters, cache and outputs must fit the chip's 16 GiB."""
    model = build_model(get_config("deepseek-7b"))
    place = lambda tree: jax.tree.map(
        lambda a: _on(one_chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(abstract_cache(model, 4, 144))
    compiled = jax.jit(build_decode_step(model, ServeOptions())).lower(
        params, cache, _on(one_chip, (4, 1), jnp.int32),
        _on(one_chip, (), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes
    assert 12 * 2**30 < total < V5E_HBM_BYTES, total / 2**30


def test_deepseek_7b_decode_writes_its_donated_cache_in_place(one_chip):
    """Published widths, batch 8, a 192-position cache, donated as
    ``ServeSession`` donates it. The layer scan reads the cache and emits only
    the new token's K/V rows, which two in-place updates write: no second
    cache in temporaries, and no copy of the stacked or one layer's cache."""
    cfg = get_config("deepseek-7b")
    model = build_model(cfg)
    place = lambda tree: jax.tree.map(
        lambda a: _on(one_chip, a.shape, a.dtype), tree)
    B, T = 8, 192
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(abstract_cache(model, B, T))
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert cache_bytes == 754_974_720
    compiled = jax.jit(build_decode_step(model, ServeOptions()),
                       donate_argnums=(1,)).lower(
        params, cache, _on(one_chip, (B, 1), jnp.int32),
        _on(one_chip, (), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01 * cache_bytes
    cache_shaped = re.compile(
        rf"= bf16\[(\d+,)?{B},{T},{cfg.n_kv_heads},{cfg.head_dim_}\]")
    copies = [line.strip() for line in compiled.as_text().splitlines()
              if cache_shaped.search(line)
              and (line.lstrip().startswith("%copy") or " copy(" in line)]
    assert not copies, copies[:3]


def _latent_flash_compiles(one_chip, batch, seq):
    cfg = get_config("deepseek-v2-lite")
    qk = _on(one_chip, (batch, seq, cfg.n_heads,
                        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    v = _on(one_chip, (batch, seq, cfg.n_heads, cfg.v_head_dim))
    compiled = jax.jit(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True)
    ).lower(qk, qk, v).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_with_deepseek_v2_lite_latent_widths(one_chip):
    """q/k at nope + rope = 192, v at 128, as the latent attention's
    prefill runs the kernel, at the tiles flash_plan picks."""
    _latent_flash_compiles(one_chip, 2, 2048)


def test_flash_attention_compiles_at_v2lite_ragsynth_long_prompts(one_chip):
    """B 8, S 8192, the long prompts of v2lite.ragsynth: flash_plan's
    1024 x 1024 tiles sit at the VMEM budget at q/k 192, v 128."""
    _latent_flash_compiles(one_chip, 8, 8192)


def _expert_ffn_compiles(one_chip, arch, block_m, tokens):
    cfg = get_config(arch)
    m = cfg.moe
    n, d, f = m.held, cfg.d_model, m.d_ff_expert
    M = dispatch_rows(tokens, m.top_k, n, block_m)
    i32 = jnp.int32
    compiled = jax.jit(
        lambda *a: expert_ffn_pallas(*a, block_m=block_m)
    ).lower(_on(one_chip, (M, d)), _on(one_chip, (n, d, f)),
            _on(one_chip, (n, d, f)), _on(one_chip, (n, f, d)),
            _on(one_chip, (M // block_m,), i32),
            _on(one_chip, (1,), i32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("block_m, tokens", [(16, 8), (128, 8192)])
def test_expert_ffn_compiles_at_deepseek_v2_lite_expert_widths(
        one_chip, block_m, tokens):
    """The grouped expert kernel over a decode step's and a prefill chunk's
    routed rows, the 8 held experts' whole weights in VMEM blocks."""
    _expert_ffn_compiles(one_chip, "deepseek-v2-lite", block_m, tokens)


@pytest.mark.parametrize("block_m, tokens", [(16, 8), (128, 8192)])
def test_expert_ffn_compiles_at_deepseek_moe_16b_expert_widths(
        one_chip, block_m, tokens):
    """As above, with all 64 experts of deepseek-moe-16b held."""
    _expert_ffn_compiles(one_chip, "deepseek-moe-16b", block_m, tokens)


def test_deepseek_v2_lite_decode_reads_its_latent_cache_in_place(one_chip):
    """Published widths, 8 of 64 experts held, batch 8, a 1024-position
    latent cache (31,104 bytes a token), donated: no cache-sized temporary
    and no copy of a cache-shaped buffer."""
    cfg = get_config("deepseek-v2-lite")
    model = build_model(cfg)
    place = lambda tree: jax.tree.map(
        lambda a: _on(one_chip, a.shape, a.dtype), tree)
    B, T = 8, 1024
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(abstract_cache(model, B, T))
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache["groups"]))
    assert cache_bytes == B * T * 31_104
    compiled = jax.jit(build_decode_step(model, ServeOptions()),
                       donate_argnums=(1,)).lower(
        params, cache, _on(one_chip, (B, 1), jnp.int32),
        _on(one_chip, (), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * cache_bytes
    # a copy into VMEM (memory space S(1)) is a prefetch, not a second cache
    cache_shaped = re.compile(rf"= bf16\[(\d+,)?{B},{T},(512|64)\]\{{[^}}]*\}}")
    copies = [line.strip() for line in compiled.as_text().splitlines()
              if (m := cache_shaped.search(line)) and "S(1)" not in m.group()
              and (line.lstrip().startswith("%copy") or " copy(" in line)]
    assert not copies, copies[:3]
