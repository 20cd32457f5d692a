"""Explicit (shard_map) backend: the UPIR sync schedule realized by hand.

The GSPMD backend lets XLA place collectives from shardings; this backend
executes the *same optimized UPIR program* with explicit ``jax.lax``
collectives, one per SyncOp — including:

  * post vs pipelined gradient reduction (the arrive-compute/wait-release
    split of the overlap pass): 'post' accumulates local grads and reduces
    once after the microbatch loop; 'pipelined' issues a psum per microbatch
    inside the loop (arrive) — the schedule difference is observable in the
    compiled HLO (collective count/placement) and tested for numerical
    equivalence against the GSPMD backend;
  * optional int8+error-feedback compressed reduction (compression.py).

Used on small meshes (tests/benchmarks) — it is the C2 witness: one IR, two
lowering backends, identical numerics.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..configs.base import ArchConfig
from ..core.lower import LoweredPlan
from ..models import api
from ..optim import clip_by_global_norm, cosine_warmup, make_optimizer
from . import compression as comp


def make_explicit_train_step(cfg: ArchConfig, plan: LoweredPlan, mesh: Mesh,
                             *, peak_lr: float = 3e-4, warmup_steps: int = 100,
                             total_steps: int = 10000, grad_clip: float = 1.0,
                             data_axis: str = "data") -> Callable:
    """Data-parallel explicit train step (params replicated inside shard_map;
    grads reduced by hand per the UPIR sync schedule)."""
    _, opt_update = make_optimizer(cfg.optimizer)
    mb = plan.microbatches
    pipelined = plan.grad_reduce == "pipelined"
    compress = plan.compression == "int8"

    def loss(params, batch):
        return api.loss_fn(cfg, params, batch, remat=plan.remat)

    def shard_body(state, batch, residual):
        params = state["params"]

        def grads_of(b):
            (l, _aux), g = jax.value_and_grad(loss, has_aux=True)(params, b)
            return l, g

        if mb > 1:
            def split(x):
                return x.reshape(mb, x.shape[0] // mb, *x.shape[1:])
            mbb = jax.tree.map(split, batch)

            def body(carry, b):
                gsum, lsum = carry
                l, g = grads_of(b)
                if pipelined:
                    # arrive-compute: reduce THIS microbatch's grads now,
                    # overlapping with the next microbatch's compute
                    g = jax.tree.map(
                        lambda x: jax.lax.psum(x, data_axis), g)
                gsum = jax.tree.map(lambda a, x: a + x.astype(jnp.float32),
                                    gsum, g)
                return (gsum, lsum + l), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            (gsum, lsum), _ = jax.lax.scan(body, (zeros, jnp.float32(0)), mbb)
            grads = jax.tree.map(lambda g: g / mb, gsum)
            loss_val = jax.lax.pmean(lsum / mb, data_axis)
        else:
            loss_val, grads = grads_of(batch)
            loss_val = jax.lax.pmean(loss_val, data_axis)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        if not pipelined:
            # wait-release only: one reduction after the loop
            if compress:
                codes, scales, residual = comp.ef_compress_tree(grads, residual)
                # int8 codes summed in int32 across the axis, scales averaged
                summed = jax.tree.map(
                    lambda c: jax.lax.psum(c.astype(jnp.int32), data_axis),
                    codes)
                scales = jax.tree.map(
                    lambda s: jax.lax.pmean(s, data_axis), scales)
                grads = jax.tree.map(
                    lambda c, s: c.astype(jnp.float32) * s, summed, scales)
            else:
                grads = jax.tree.map(lambda g: jax.lax.psum(g, data_axis),
                                     grads)
        n_data = jax.lax.axis_size(data_axis)
        grads = jax.tree.map(lambda g: g / n_data, grads)

        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        lr = cosine_warmup(state["opt"].count, peak_lr=peak_lr,
                           warmup_steps=warmup_steps, total_steps=total_steps)
        updates, opt = opt_update(grads, state["opt"], params, lr=lr)
        new_params = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
            params, updates)
        metrics = {"loss": loss_val, "grad_norm": gnorm, "lr": lr}
        return {"params": new_params, "opt": opt}, metrics, residual

    rep = P()
    batch_spec = P(data_axis)

    def batch_specs_for(batch):
        return jax.tree.map(lambda _: batch_spec, batch)

    def step(state, batch, residual):
        body = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: rep, state),
                      batch_specs_for(batch),
                      jax.tree.map(lambda _: rep, residual)),
            out_specs=(jax.tree.map(lambda _: rep, state),
                       {"loss": rep, "grad_norm": rep, "lr": rep},
                       jax.tree.map(lambda _: rep, residual)),
            check_vma=False)
        return body(state, batch, residual)

    return jax.jit(step)
