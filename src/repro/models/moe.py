"""Mixture-of-experts layer: top-k router + capacity-based dispatch.

Two execution paths share one router:

  * **GSPMD path** (default, used by the dry-run): one-hot dispatch/combine
    einsums over a [tokens, experts, capacity] tensor, chunked over the sequence
    so the dispatch tensor stays bounded for 32k prefill. Chunk sizing is
    weight-amortization-bound, not dispatch-bound: every chunk re-reads all
    expert weights, so small chunks LOSE (grok: chunk 512 doubled the memory
    term vs 2048; 8192 is near the dispatch~weights crossover — §Perf M1). With the expert dim
    sharded over the ``model`` axis (phi3.5: 16 experts <-> 16 shards) XLA lowers
    dispatch/combine into all-to-alls — expert parallelism.
  * **explicit path** (``moe_apply_ep``): shard_map with hand-written
    ``lax.all_to_all``, matching the UPIR ``sync all_to_all`` node, used by the
    equivalence tests and the §Perf comparison.

Router: softmax over experts, top-k, load-balancing auxiliary loss (Switch-style).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def router_topk(logits, k: int):
    """logits: [T, E] -> (gates [T,k], idx [T,k], aux_loss scalar)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance loss: E * sum_e f_e * p_e
    E = logits.shape[-1]
    me = probs.mean(axis=0)                               # mean router prob per expert
    ce = jnp.zeros((E,), jnp.float32).at[idx[:, 0]].add(1.0) / idx.shape[0]
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def _dispatch_combine(x, w1, w3, w2, gates, idx, capacity: int, act_fn, glu: bool):
    """Capacity-based one-hot dispatch (GShard style) for one token chunk.

    x: [T, D]; w1/w3: [E, D, F]; w2: [E, F, D]; gates/idx: [T, k].
    """
    T, D = x.shape
    E = w1.shape[0]
    k = idx.shape[1]
    # position of each (token, choice) within its expert's capacity buffer
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)              # [T,k,E]
    flat = onehot.reshape(T * k, E)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(T, k, E)
    pos = (pos_in_expert * onehot).sum(-1)                        # [T,k]
    keep = pos < capacity
    gates = jnp.where(keep, gates, 0.0)

    disp = (jax.nn.one_hot(idx, E, dtype=x.dtype)[..., None]
            * jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity + 1,
                             dtype=x.dtype)[..., :capacity][:, :, None, :])
    disp = disp.sum(1)                                            # [T,E,C]
    # combine weights are the dispatch pattern with per-choice gates folded in
    combine = (jax.nn.one_hot(idx, E, dtype=x.dtype)[..., None]
               * jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity + 1,
                                dtype=x.dtype)[..., :capacity][:, :, None, :]
               * gates[..., None, None].astype(x.dtype))
    combine = combine.sum(1)                                      # [T,E,C]

    xe = jnp.einsum("td,tec->ecd", x, disp)                       # [E,C,D]
    h = jnp.einsum("ecd,edf->ecf", xe, w1)
    h = act_fn(h)
    if glu:
        h = h * jnp.einsum("ecd,edf->ecf", xe, w3)
    ye = jnp.einsum("ecf,efd->ecd", h, w2)                        # [E,C,D]
    return jnp.einsum("ecd,tec->td", ye, combine)


def moe_apply(p, x, *, top_k: int, capacity_factor: float, act, glu: bool,
              dtype, chunk: int = 4096) -> Tuple[jax.Array, jax.Array]:
    """GSPMD MoE. p: router [D,E], w1/w3 [E,D,F], w2 [E,F,D]; x: [B,S,D]."""
    from .layers import _act
    B, S, D = x.shape
    E = p["router"].shape[-1]
    x2 = x.reshape(B * S, D).astype(dtype)
    T = x2.shape[0]
    chunk = min(chunk, T)
    n_chunks = T // chunk
    rest = T - n_chunks * chunk
    if S == 1:       # decode: dropless (capacity = all tokens)
        capacity = T
    else:
        capacity = max(int(capacity_factor * chunk * top_k / E), 1)
    act_fn = lambda h: _act(h, act)
    w1 = p["w1"].astype(dtype)
    w3 = p.get("w3")
    w3 = w3.astype(dtype) if w3 is not None else w1
    w2 = p["w2"].astype(dtype)
    router = p["router"].astype(dtype)

    def run_chunk(xc):
        logits = xc @ router
        gates, idx, aux = router_topk(logits, top_k)
        y = _dispatch_combine(xc, w1, w3, w2, gates, idx, capacity, act_fn, glu)
        return y, aux

    if n_chunks > 1:
        xc = x2[: n_chunks * chunk].reshape(n_chunks, chunk, D)
        ys, auxs = jax.lax.map(run_chunk, xc)
        y = ys.reshape(n_chunks * chunk, D)
        aux = auxs.mean()
        if rest:
            y_r, aux_r = run_chunk(x2[n_chunks * chunk:])
            y = jnp.concatenate([y, y_r], axis=0)
            aux = (aux * n_chunks + aux_r) / (n_chunks + 1)
    else:
        y, aux = run_chunk(x2)
    return y.reshape(B, S, D).astype(x.dtype), aux


def moe_apply_ep(p, x, *, top_k: int, capacity_factor: float, act, glu: bool,
                 dtype, axis: str = "model"):
    """Explicit expert-parallel MoE inside shard_map: all_to_all dispatch.

    Must run inside shard_map with ``axis`` mapped. Experts are sharded over
    ``axis``; tokens are bucketed locally then exchanged with all_to_all — the
    lowering of the UPIR ``sync all_to_all`` node.
    """
    from .layers import _act
    n_shards = jax.lax.axis_size(axis)
    B, S, D = x.shape
    E_local = p["w1"].shape[0]            # experts per shard
    E = E_local * n_shards
    x2 = x.reshape(B * S, D).astype(dtype)
    T = x2.shape[0]
    capacity = max(int(capacity_factor * T * top_k / E), 1)

    logits = x2 @ p["router"].astype(dtype)     # router replicated: [D, E]
    gates, idx, aux = router_topk(logits, top_k)

    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)
    flat = onehot.reshape(T * top_k, E)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(T, top_k, E)
    pos = (pos_in_expert * onehot).sum(-1)
    keep = pos < capacity
    gates = jnp.where(keep, gates, 0.0)
    disp = (jax.nn.one_hot(idx, E, dtype=x2.dtype)[..., None]
            * jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity + 1,
                             dtype=x2.dtype)[..., :capacity][:, :, None, :]).sum(1)
    combine = (jax.nn.one_hot(idx, E, dtype=jnp.float32)[..., None]
               * jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity + 1,
                                dtype=jnp.float32)[..., :capacity][:, :, None, :]
               * gates[..., None, None]).sum(1).astype(x2.dtype)

    xe = jnp.einsum("td,tec->ecd", x2, disp)          # [E, C, D] local buckets
    # exchange: [E, C, D] -> [E_local, n_shards*C, D] on each shard
    xe = xe.reshape(n_shards, E_local, capacity, D)
    xe = jax.lax.all_to_all(xe, axis, split_axis=0, concat_axis=2, tiled=False)
    xe = xe.reshape(E_local, n_shards * capacity, D)

    h = jnp.einsum("ecd,edf->ecf", xe, p["w1"].astype(dtype))
    h = _act(h, act)
    if glu:
        h = h * jnp.einsum("ecd,edf->ecf", xe, p["w3"].astype(dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dtype))

    ye = ye.reshape(E_local, n_shards, capacity, D)
    ye = jax.lax.all_to_all(ye, axis, split_axis=1, concat_axis=0, tiled=False)
    ye = ye.reshape(E, capacity, D)
    y = jnp.einsum("ecd,tec->td", ye, combine)
    return y.reshape(B, S, D).astype(x.dtype), aux
