"""Explicit-FSDP train step: shard_map with a manual 'data' axis (§Perf T3).

Why this exists: under pure GSPMD, the layer-scan transpose's stacked-dW carry
settles on *replicated* in XLA's sharding fixpoint — full f32 per-layer weight
gradients, all-reduced over 'data' every layer (measured: 57% of llama3-405b's
collective bytes and the dominant peak-memory term). Constraints outside or
inside the loop are satisfied trivially by post-loop reshards (§Perf T0).

The structural fix: make the FSDP gathers EXPLICIT. Parameters enter a
``jax.shard_map`` whose 'data' axis is manual ('model' stays auto/GSPMD for
TP); each scanned layer's shards are gathered at their use site
(``fsdp_gather_block``: tiled all_gather over 'data'), so autodiff produces a
tiled psum_scatter of each layer's gradient — per-layer dW is born sharded, in
bf16. This is exactly the ZeRO arrive/release schedule the UPIR ``fuse_sync``
pass emits (reduce_scatter + all_gather), lowered by hand — the paper's
"unified transformation" realized through the explicit backend at scale.

The optimizer update runs outside the shard_map on the (sharded) grads —
unchanged from the GSPMD trainer.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..core.act_sharding import activation_shardings
from ..core.lower import LoweredPlan, path_str
from ..models import api
from ..optim import clip_by_global_norm, cosine_warmup, make_optimizer

SCANNED_SUBTREES = ("blocks", "mamba", "enc_blocks", "dec_blocks")


def _data_only(spec: P) -> P:
    """Keep only 'data' components of a spec (manual axis placement)."""
    return P(*[("data" if e == "data" or
                (isinstance(e, tuple) and "data" in e) else None)
               for e in spec])


def _fsdp_dim(spec: P) -> Optional[int]:
    for i, e in enumerate(spec):
        if e == "data" or (isinstance(e, tuple) and "data" in e):
            return i
    return None


def _gather_info(plan: LoweredPlan, cfg: ArchConfig):
    """(per-subtree per-layer gather dims, in_specs pytree for all params)."""
    pspecs = api.param_specs(cfg)
    info: Dict[str, Any] = {}
    in_specs = {}
    for key, sub in pspecs.items():
        if key in SCANNED_SUBTREES:
            def leaf_dim(path, _l, key=key):
                spec = plan.spec(f"params/{key}/" + path_str(path))
                d = _fsdp_dim(spec)
                return None if d is None else d - 1   # drop stacked L dim
            info[key + "_fsdp"] = jax.tree_util.tree_map_with_path(
                leaf_dim, sub)
        in_specs[key] = jax.tree_util.tree_map_with_path(
            lambda path, _l, key=key: _data_only(
                plan.spec(f"params/{key}/" + path_str(path))), sub)
    return info, in_specs, pspecs


def _manual_act_specs(cfg: ArchConfig, mesh, gather_info):
    """Activation constraints valid inside manual-'data' shard_map: only
    auto ('model') axes may appear."""
    sp = "model"
    specs = {
        "hidden": NamedSharding(mesh, P(None, sp, None)),
        "logits": NamedSharding(mesh, P(None, None,
                                        sp if cfg.vocab % 16 == 0 else None)),
        "kv": NamedSharding(mesh, P(None, None, None, None)),
        "heads4": NamedSharding(mesh, P(None, None,
                                        sp if cfg.n_heads % 16 == 0 else None,
                                        None)),
    }
    specs.update(gather_info)
    return specs


def make_fsdp_train_step(cfg: ArchConfig, plan: LoweredPlan, mesh, *,
                         peak_lr: float = 3e-4, warmup_steps: int = 100,
                         total_steps: int = 10000, grad_clip: float = 1.0):
    """Returns (jitted step, (state_specs, batch_specs), shardings)."""
    from .trainer import state_specs as _state_specs
    _, opt_update = make_optimizer(cfg.optimizer)
    gather_info, param_in_specs, pspecs = _gather_info(plan, cfg)
    act_specs = _manual_act_specs(cfg, mesh, gather_info)

    def loss(params, batch):
        with activation_shardings(act_specs):
            # gather the non-scanned leaves once per step (embed/head/norms);
            # scanned subtrees gather per layer inside the scan bodies
            gathered = {}
            for key, sub in params.items():
                if key in SCANNED_SUBTREES:
                    gathered[key] = sub
                    continue
                def g(path, x, key=key):
                    d = _fsdp_dim(plan.spec(f"params/{key}/" + path_str(path)))
                    if d is None:
                        return x
                    return jax.lax.all_gather(x, "data", axis=d, tiled=True)
                gathered[key] = jax.tree_util.tree_map_with_path(g, sub)
            l, _aux = api.loss_fn(cfg, gathered, batch, remat=plan.remat)
            return l

    def grads_body(params, batch):
        l, g = jax.value_and_grad(loss)(params, batch)
        return jax.lax.pmean(l, "data"), \
            jax.tree.map(lambda x: jnp.asarray(x), g)

    batch_spec_manual = P("data")

    def train_step(state, batch):
        params = state["params"]
        # only 'data' is manual; 'model' stays automatic (GSPMD)
        loss_val, grads = jax.shard_map(
            grads_body, mesh=mesh,
            in_specs=(param_in_specs,
                      jax.tree.map(lambda _: batch_spec_manual, batch)),
            out_specs=(P(), param_in_specs),
            axis_names={"data"}, check_vma=False,
        )(params, batch)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        lr = cosine_warmup(state["opt"].count, peak_lr=peak_lr,
                           warmup_steps=warmup_steps, total_steps=total_steps)
        updates, opt = opt_update(grads, state["opt"], params, lr=lr)
        new_params = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
            params, updates)
        return {"params": new_params, "opt": opt}, \
            {"loss": loss_val, "grad_norm": gnorm, "lr": lr}

    sspecs = _state_specs(cfg)
    state_sh = plan.sharding_tree(mesh, sspecs)
    batch_specs = {
        name.split("/", 1)[1]: jax.ShapeDtypeStruct(shape, jnp.dtype(dt))
        for name, (shape, dt) in plan.program.symbols
        if name.startswith("in/")}
    batch_sh = plan.sharding_tree(mesh, batch_specs, prefix="in")
    rep = NamedSharding(mesh, P())
    fn = jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                 out_shardings=(state_sh, {"loss": rep, "grad_norm": rep,
                                           "lr": rep}),
                 donate_argnums=(0,))
    return fn, (sspecs, batch_specs), (state_sh, batch_sh)
