"""Serving steps: prefill, decode (KV cache / SSM state), sampling, batching.

``jit_prefill_step`` / ``jit_decode_step`` are the dry-run entry points for
the ``prefill_32k`` / ``decode_32k`` / ``long_500k`` shape cells; the
``ServeSession`` class is the real-execution path used by the examples, the
executor and ``launch/serve.py``: one static batch of equal-length prompts
per call, prefilled together and then decoded one token per step.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.model_zoo import Model
from ..models.moe import make_dist
from . import sharding as shd
from . import tracing


@dataclass(frozen=True)
class ServeOptions:
    temperature: float = 0.0      # 0 = greedy
    scan_unroll: int = 1


def cache_shardings(model: Model, cache_abstract, mesh, rules=None):
    axes = shd.cache_logical_axes(cache_abstract)
    return shd.tree_shardings(axes, cache_abstract, mesh, rules)


def abstract_cache(model: Model, batch: int, max_len: int, enc_len: int = 0):
    return jax.eval_shape(
        lambda: model.init_cache(batch, max_len, enc_len=enc_len))


def build_prefill_step(model: Model, opts: ServeOptions, mesh=None):
    dist = make_dist(mesh)

    def prefill(params, inputs, cache):
        logits, cache, _ = model.apply(params, inputs, mode="prefill",
                                       cache=cache, cache_index=0, dist=dist,
                                       scan_unroll=opts.scan_unroll)
        return logits[:, -1], cache

    return prefill


def build_decode_step(model: Model, opts: ServeOptions, mesh=None):
    dist = make_dist(mesh)

    def decode(params, cache, tokens, index, key=None):
        """tokens: (B, 1); index: scalar int32 position. -> (next, cache)."""
        logits, cache, _ = model.apply(params, {"tokens": tokens},
                                       mode="decode", cache=cache,
                                       cache_index=index, dist=dist,
                                       scan_unroll=opts.scan_unroll)
        last = logits[:, -1]
        if opts.temperature > 0 and key is not None:
            nxt = jax.random.categorical(key, last / opts.temperature, -1)
        else:
            nxt = jnp.argmax(last, axis=-1)
        return nxt.astype(jnp.int32)[:, None], last, cache

    return decode


def jit_decode_step(model: Model, opts: ServeOptions, mesh, batch: int,
                    max_len: int, enc_len: int = 0, rules=None):
    """pjit'd single-token decode over a sharded cache (dry-run entry)."""
    decode = build_decode_step(model, opts, mesh)
    cache_abs = abstract_cache(model, batch, max_len, enc_len=enc_len)
    c_sh = cache_shardings(model, cache_abs, mesh, rules)
    p_abs = model.abstract()
    p_sh = shd.tree_shardings(model.axes(), p_abs, mesh, rules)
    tok_sh = NamedSharding(mesh, shd.data_spec((batch, 1), mesh))
    repl = NamedSharding(mesh, P())
    fn = jax.jit(lambda params, cache, tokens, index:
                 decode(params, cache, tokens, index),
                 in_shardings=(p_sh, c_sh, tok_sh, repl),
                 out_shardings=(tok_sh, None, c_sh),
                 donate_argnums=(1,))
    return fn, (p_abs, cache_abs)


def jit_prefill_step(model: Model, opts: ServeOptions, mesh, batch: int,
                     seq_len: int, rules=None):
    prefill = build_prefill_step(model, opts, mesh)
    enc_len = model.enc_len_for(seq_len)
    cache_abs = abstract_cache(model, batch, seq_len, enc_len=enc_len)
    c_sh = cache_shardings(model, cache_abs, mesh, rules)
    p_abs = model.abstract()
    p_sh = shd.tree_shardings(model.axes(), p_abs, mesh, rules)
    tok_abs = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
    in_abs = {"tokens": tok_abs,
              **model.extra_inputs(batch, seq_len, abstract=True)}
    in_sh = jax.tree.map(
        lambda a: NamedSharding(mesh, shd.data_spec(a.shape, mesh)), in_abs)
    fn = jax.jit(prefill,
                 in_shardings=(p_sh, in_sh, c_sh),
                 out_shardings=(None, c_sh),
                 donate_argnums=(2,))
    return fn, (p_abs, in_abs, cache_abs)


# ---------------------------------------------------------------------------
# Real-execution serving session (examples / core.executor)
# ---------------------------------------------------------------------------


class ServeSession:
    """Batched request serving against a locally-materialized model."""

    def __init__(self, model: Model, params, max_len: int = 256,
                 opts: ServeOptions = ServeOptions()):
        self.model, self.params, self.opts = model, params, opts
        self.max_len = max_len
        # Both steps consume the cache they are given. Without donation each
        # asynchronously dispatched decode step allocates a fresh cache while
        # its input is still pending, so a host running ahead of the device
        # holds many caches at once (2.7 GiB over the parameters for
        # deepseek-7b at batch 4 on a v5e, nearly all of the chip's headroom).
        self.prefill = jax.jit(build_prefill_step(model, opts),
                               donate_argnums=(2,))
        self.decode = jax.jit(build_decode_step(model, opts),
                              donate_argnums=(1,))

    def generate(self, prompts, max_new_tokens: int = 32, extras=None):
        """prompts: (B, S) int32 array -> (B, max_new_tokens) int32.

        Under a profiler session each call records the spans ``serve.*``
        of one request (``tracing``): the spans time the host's dispatch,
        the device runs behind them. A model with expert layers adds
        ``experts_held`` to ``serve.generate`` and, read once at the end of
        the call, the cache's counters ``moe_rows_routed`` (assignments that
        fell on the held experts), ``moe_rows_computed`` (rows the grouped
        kernel computed) and ``moe_experts_read`` (held experts with a row,
        whose weights the kernel read, once a kernel call), summed over
        layers, prefill and decode, and the decode steps' part of the first
        and last, ``moe_decode_rows_routed`` and ``moe_decode_experts_read``."""
        B, S = prompts.shape
        moe = self.model.cfg.moe
        held = {"experts_held": moe.held} if moe.num_experts else {}
        with tracing.span("serve.generate", batch=B, prompt_len=S,
                          new_tokens=max_new_tokens, **held) as call:
            enc_len = self.model.enc_len_for(S)
            with tracing.span("serve.init_cache"):
                cache = self.model.init_cache(B, S + max_new_tokens,
                                              enc_len=enc_len)
            if call is not None:
                call.set(cache_bytes=sum(
                    x.nbytes for x in jax.tree.leaves(cache["groups"])))
            inputs = {"tokens": prompts, **(extras or {})}
            with tracing.span("serve.prefill"):
                last_logits, cache = self.prefill(self.params, inputs, cache)
            with tracing.span("serve.sample"):
                tok = jnp.argmax(last_logits, -1).astype(jnp.int32)[:, None]
            out = [tok]
            idx = jnp.asarray(S, jnp.int32)
            for step in range(1, max_new_tokens):
                with tracing.span("serve.decode", step=step):
                    tok, _, cache = self.decode(self.params, cache, tok, idx)
                    idx = idx + 1
                out.append(tok)
            with tracing.span("serve.concat"):
                tokens = jnp.concatenate(out, axis=1)
            if call is not None and "moe_rows" in cache:
                prefill, decode = jax.device_get(cache["moe_rows"]).tolist()
                routed, computed, read = (p + d for p, d in zip(prefill,
                                                                 decode))
                call.set(moe_rows_routed=routed, moe_rows_computed=computed,
                         moe_experts_read=read,
                         moe_decode_rows_routed=decode[0],
                         moe_decode_experts_read=decode[2])
            return tokens
