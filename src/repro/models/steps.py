"""Pure step functions: train / prefill / decode.

These close over (model, plan, optimizer); `runtime/trainer.py` executes
the train step.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.model import LM
from repro.optim.adamw import AdamW
from repro.optim.compression import error_feedback_update
from repro.sharding.partition import (MeshPlan, NULL_PLAN, param_specs, ws)

AUX_WEIGHT = 0.01


def _constrain_like_params(tree, params, plan: MeshPlan, cfg):
    """Pin gradients/accumulators to the parameter layout.

    Without this the microbatch grad accumulator has no layout and XLA is
    free to replicate f32 gradients across the data axes (for FSDP'd params
    that is dp_size x the memory and an all-reduce instead of a
    reduce-scatter).
    """
    if plan is None or plan.mesh is None:
        return tree
    import jax as _jax
    from jax.sharding import NamedSharding
    specs = param_specs(params, plan, cfg)
    return _jax.tree.map(
        lambda x, s: _jax.lax.with_sharding_constraint(
            x, NamedSharding(plan.mesh, s)), tree, specs)


def make_loss_fn(model: LM, cfg: ArchConfig, plan: MeshPlan):
    V, Vp = cfg.vocab_size, cfg.vocab_padded

    def loss_fn(params, batch):
        logits, _, aux = model.forward(params, batch, plan)
        lf = logits.astype(jnp.float32)
        iota = jnp.arange(Vp)
        lf = jnp.where(iota < V, lf, -1e30)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        tl = jnp.sum(jnp.where(iota == batch["targets"][..., None], lf, 0.0),
                     axis=-1)
        nll = jnp.mean(lse - tl)
        return nll + AUX_WEIGHT * aux, nll

    return loss_fn


def _micro_split(batch, m: int, plan: MeshPlan):
    """(GB, ...) -> (m, GB/m, ...) with an explicit post-reshape layout."""
    def split(x):
        assert x.shape[0] % m == 0, (x.shape, m)
        y = x.reshape(m, x.shape[0] // m, *x.shape[1:])
        b_ax = plan.batch_axes if plan else None
        return ws(y, plan, None, b_ax, *([None] * (y.ndim - 2)))
    return jax.tree.map(split, batch)


def make_train_step(model: LM, cfg: ArchConfig, plan: MeshPlan,
                    optimizer: AdamW):
    loss_fn = make_loss_fn(model, cfg, plan)
    M = max(cfg.parallel.microbatches, 1)
    accum_dtype = jnp.dtype(cfg.parallel.accum_dtype)

    def train_step(params, opt_state, batch):
        if M == 1:
            (loss, nll), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
            grads = _constrain_like_params(grads, params, plan, cfg)
        elif cfg.parallel.accum_via_scan_grad:
            # grad-of-scan: autodiff accumulates parameter grads across the
            # microbatch loop internally -> one cross-dp reduction per step
            mb = _micro_split(batch, M, plan)

            def total_loss(params):
                def body(carry, one):
                    l, nll = loss_fn(params, one)
                    return carry + l / M, nll
                tot, nlls = jax.lax.scan(
                    jax.checkpoint(body, prevent_cse=False),
                    jnp.float32(0.0), mb)
                return tot, jnp.mean(nlls)

            (loss, nll), grads = jax.value_and_grad(
                total_loss, has_aux=True)(params)
            grads = _constrain_like_params(grads, params, plan, cfg)
        else:
            mb = _micro_split(batch, M, plan)

            def body(acc, one):
                (l, nll), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, one)
                g = _constrain_like_params(g, params, plan, cfg)
                acc = jax.tree.map(lambda a, gg: a + gg.astype(accum_dtype),
                                   acc, g)
                acc = _constrain_like_params(acc, params, plan, cfg)
                return acc, nll
            acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype),
                                params)
            acc0 = _constrain_like_params(acc0, params, plan, cfg)
            grads, nlls = jax.lax.scan(body, acc0, mb)
            grads = jax.tree.map(lambda g: g / M, grads)
            nll = jnp.mean(nlls)

        if cfg.parallel.grad_compression and "ef" in opt_state:
            pairs = jax.tree.map(error_feedback_update, grads,
                                 opt_state["ef"])
            grads = jax.tree.map(lambda p: p[0], pairs,
                                 is_leaf=lambda x: isinstance(x, tuple))
            new_ef = jax.tree.map(lambda p: p[1], pairs,
                                  is_leaf=lambda x: isinstance(x, tuple))
        else:
            new_ef = opt_state.get("ef")

        new_params, new_adam, gnorm = optimizer.update(grads,
                                                       opt_state["adam"], params)
        new_opt = {"adam": new_adam}
        if new_ef is not None:
            new_opt["ef"] = new_ef
        metrics = {"loss": nll, "grad_norm": gnorm,
                   "step": new_adam["step"].astype(jnp.float32)}
        return new_params, new_opt, metrics

    return train_step


def init_opt_state(cfg: ArchConfig, optimizer: AdamW, params):
    state = {"adam": optimizer.init(params)}
    if cfg.parallel.grad_compression:
        state["ef"] = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                   params)
    return state


def make_prefill_step(model: LM, cfg: ArchConfig, plan: MeshPlan):
    def prefill_step(params, batch):
        return model.prefill(params, batch, plan)
    return prefill_step


def make_decode_step(model: LM, cfg: ArchConfig, plan: MeshPlan):
    def decode_step(params, caches, batch, pos):
        return model.decode_step(params, caches, batch, pos, plan)
    return decode_step
