"""Train-step builder: loss, microbatch accumulation, remat, shardings.

``build_train_step`` returns a pure (state, batch) -> (state, metrics)
function plus the sharding trees needed to jit it on a production mesh. The
same builder serves the smoke tests (1 CPU device, mesh=None) and the
512-device dry-run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.model_zoo import Model
from ..models.moe import make_dist
from ..optim import adamw
from ..optim.schedule import warmup_cosine
from . import sharding as shd


@dataclass(frozen=True)
class TrainOptions:
    remat_policy: str | None = "full"    # None | full | dots | minimal
    microbatches: int = 1
    opt: adamw.AdamWConfig = adamw.AdamWConfig()
    warmup_steps: int = 100
    total_steps: int = 10_000
    scan_unroll: int = 1                 # big value = unroll layer scans


def cross_entropy(logits, labels):
    """logits: (B, S, V) fp32; labels: (B, S) int32. Mean NLL."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def init_train_state(model: Model, key, opts: TrainOptions):
    params = model.init(key)
    return {"params": params, "opt": adamw.init_opt_state(params, opts.opt),
            "step": jnp.zeros((), jnp.int32)}


def abstract_train_state(model: Model, opts: TrainOptions):
    params = model.abstract()
    opt = jax.eval_shape(lambda p: adamw.init_opt_state(p, opts.opt), params)
    return {"params": params, "opt": opt,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def state_shardings(model: Model, mesh, opts: TrainOptions, rules=None):
    """NamedSharding tree for the train state (moments inherit params)."""
    p_abs = model.abstract()
    p_shard = shd.tree_shardings(model.axes(), p_abs, mesh, rules)

    def moment_shard(ps):
        if isinstance(ps, dict):  # int8 {q, scale}: q like param, scale repl.
            return {"q": ps, "scale": NamedSharding(mesh, P())}
        return ps

    if opts.opt.moment_dtype == "int8":
        m_shard = jax.tree.map(
            lambda s: {"q": s, "scale": NamedSharding(mesh, P())}, p_shard)
    else:
        m_shard = p_shard
    repl = NamedSharding(mesh, P())
    return {"params": p_shard,
            "opt": {"m": m_shard, "v": m_shard, "count": repl},
            "step": repl}


def batch_shardings(batch_abstract, mesh):
    return jax.tree.map(
        lambda a: NamedSharding(mesh, shd.data_spec(a.shape, mesh)),
        batch_abstract)


def build_train_step(model: Model, opts: TrainOptions, mesh=None,
                     rules=None) -> Callable:
    dist = make_dist(mesh)

    def loss_fn(params, batch):
        inputs = {k: v for k, v in batch.items() if k != "labels"}
        logits, _, aux = model.apply(params, inputs, mode="train", dist=dist,
                                     remat_policy=opts.remat_policy,
                                     scan_unroll=opts.scan_unroll)
        ce = cross_entropy(logits, batch["labels"])
        return ce + aux, {"loss": ce, "aux_loss": aux}

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def microbatched_grads(params, batch):
        k = opts.microbatches
        if k == 1:
            (_, metrics), grads = grad_fn(params, batch)
            return grads, metrics
        split = jax.tree.map(
            lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:]), batch)

        def body(carry, mb):
            acc, _ = carry
            (_, metrics), grads = grad_fn(params, mb)
            acc = jax.tree.map(lambda a, g: a + g / k, acc, grads)
            return (acc, metrics), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, metrics), _ = jax.lax.scan(
            body, (zeros, {"loss": jnp.zeros(()),
                           "aux_loss": jnp.zeros(())}), split)
        return grads, metrics

    def train_step(state, batch):
        grads, metrics = microbatched_grads(state["params"], batch)
        lr = warmup_cosine(state["step"], peak_lr=opts.opt.lr,
                           warmup_steps=opts.warmup_steps,
                           total_steps=opts.total_steps)
        new_p, new_opt, opt_metrics = adamw.apply_updates(
            state["params"], grads, state["opt"], opts.opt, lr=lr)
        new_state = {"params": new_p, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, {**metrics, **opt_metrics}

    return train_step


def jit_train_step(model: Model, opts: TrainOptions, mesh, batch_abstract,
                   rules=None):
    """pjit'd train step with explicit in/out shardings (dry-run entry)."""
    step_fn = build_train_step(model, opts, mesh, rules)
    st_sh = state_shardings(model, mesh, opts, rules)
    b_sh = batch_shardings(batch_abstract, mesh)
    return jax.jit(step_fn,
                   in_shardings=(st_sh, b_sh),
                   out_shardings=(st_sh, None),
                   donate_argnums=(0,))
