"""Model facade: build a (specs, init, apply, cache) bundle from a config."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import transformer
from .common import (abstract_params, dtype_of, init_params, logical_axes,
                     param_count)
from .moe import DistContext, LOCAL


class Model:
    """Thin, stateless facade over the functional model defined by ``cfg``."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.specs = transformer.lm_specs(cfg)

    # -- parameters ---------------------------------------------------------
    def init(self, key) -> Any:
        return init_params(self.specs, key, dtype_of(self.cfg.param_dtype))

    def abstract(self) -> Any:
        return abstract_params(self.specs, dtype_of(self.cfg.param_dtype))

    def axes(self) -> Any:
        return logical_axes(self.specs)

    def param_count(self) -> int:
        return param_count(self.specs)

    def active_param_count(self) -> int:
        """Per-token active params (MoE discount for roofline MODEL_FLOPS):
        every expert layer's held routed experts give way to the top-k a
        token passes through."""
        cfg = self.cfg
        m = cfg.moe
        layers = sum(gd.repeat for gd in transformer.layer_plan(cfg)
                     for b in gd.blocks if b.kind == "moe")
        per_expert = 3 * cfg.d_model * m.d_ff_expert
        return (param_count(self.specs)
                - (m.held - m.top_k) * per_expert * layers)

    # -- inputs -------------------------------------------------------------
    def extra_inputs(self, batch: int, seq_len: int, abstract=False):
        """Modality-stub inputs (DESIGN.md: frontends are stubs)."""
        cfg = self.cfg
        extras = {}
        if cfg.family == "encdec":
            shape = (batch, seq_len, cfg.d_model)
            extras["frames"] = (jax.ShapeDtypeStruct(shape, jnp.bfloat16)
                                if abstract else jnp.zeros(shape, jnp.bfloat16))
        if cfg.family == "vlm":
            shape = (batch, cfg.vision.num_patches, cfg.vision.d_vision)
            extras["patches"] = (jax.ShapeDtypeStruct(shape, jnp.bfloat16)
                                 if abstract else jnp.zeros(shape, jnp.bfloat16))
        return extras

    # -- execution ----------------------------------------------------------
    def apply(self, params, inputs, *, mode="train", dist: DistContext = LOCAL,
              cache=None, cache_index=None, remat_policy=None,
              scan_unroll: int = 1):
        return transformer.forward(
            params, inputs, cfg=self.cfg, dist=dist, mode=mode, cache=cache,
            cache_index=cache_index, remat_policy=remat_policy,
            scan_unroll=scan_unroll)

    def enc_len_for(self, seq_len: int) -> int:
        """Cross-attention KV length: encoder states (encdec) or image
        patches (vlm)."""
        if self.cfg.family == "encdec":
            return seq_len
        if self.cfg.family == "vlm":
            return self.cfg.vision.num_patches
        return 0

    def init_cache(self, batch: int, max_len: int, *, enc_len: int = 0):
        return transformer.init_cache(self.cfg, batch, max_len,
                                      enc_len=enc_len)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
