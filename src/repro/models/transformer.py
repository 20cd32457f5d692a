"""Decoder-only LM assembly for all assigned families.

One module covers dense / moe / hybrid (Mamba2 + shared attention) / ssm (xLSTM)
/ vlm (dense backbone + patch-embed stub). The encoder-decoder (whisper) lives in
``encdec.py`` and reuses these blocks.

Conventions:
  * params are nested dicts; per-layer tensors are stacked on a leading L dim and
    the layer loop is ``lax.scan`` (keeps HLO size O(1 layer) — essential for the
    405B dry-run) except for xLSTM, whose 24 heterogeneous blocks are unrolled;
  * forwards take an optional remat policy (none | selective | full), chosen by
    the UPIR memory pass;
  * decode carries an explicit cache pytree (KV / conv+ssm state / xLSTM state),
    donated by the serving step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.act_sharding import (anchor_block_grads, constrain,
                                 fsdp_gather_block)
from . import mamba2, moe as moe_lib, xlstm as xlstm_lib
from .layers import (apply_rope, attention_chunked, attention_decode,
                     attention_decode_live, attention_full,
                     attention_prefill_chunk, block_pages, cache_insert,
                     cache_insert_chunk, cache_insert_paged,
                     cache_insert_paged_chunk, decode_block_ladder,
                     embed_lookup, gather_kv_pages, live_block_list,
                     mlp_apply, norm)

CHUNKED_ATTN_THRESHOLD = 8192


def is_shape(s) -> bool:
    """Leaf predicate: a shape is a tuple of ints (dicts/tuples of dicts are not)."""
    return isinstance(s, tuple) and all(isinstance(x, int) for x in s)


# ---------------------------------------------------------------- param shapes


def _attn_shapes(cfg: ArchConfig) -> Dict[str, tuple]:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    return {"ln1": (D,), "wq": (D, H * hd), "wk": (D, KV * hd),
            "wv": (D, KV * hd), "wo": (H * hd, D)}


def _mlp_shapes(cfg: ArchConfig) -> Dict[str, tuple]:
    D, F = cfg.d_model, cfg.d_ff
    s = {"w1": (D, F), "w2": (F, D)}
    if cfg.glu:
        s["w3"] = (D, F)
    return s


def _moe_shapes(cfg: ArchConfig) -> Dict[str, tuple]:
    D, E, F = cfg.d_model, cfg.moe.num_experts, cfg.moe.d_ff
    s = {"router": (D, E), "w1": (E, D, F), "w2": (E, F, D)}
    if cfg.glu:
        s["w3"] = (E, D, F)
    return s


def param_shapes(cfg: ArchConfig) -> Dict[str, Any]:
    """Nested dict of shape tuples for the full parameter tree."""
    D, V = cfg.d_model, cfg.vocab
    out: Dict[str, Any] = {"embed": (V, D), "final_norm": (D,)}
    if not cfg.tied_embeddings:
        out["lm_head"] = (D, V)

    if cfg.family == "ssm":                       # xLSTM: unrolled blocks
        blocks = []
        x = cfg.xlstm
        for i in range(cfg.n_layers):
            if i % x.slstm_every == 0:
                blocks.append(xlstm_lib.slstm_params_shapes(
                    D, cfg.n_heads, x.proj_factor_slstm))
            else:
                blocks.append(xlstm_lib.mlstm_params_shapes(
                    D, cfg.n_heads, x.proj_factor_mlstm))
        out["blocks"] = tuple(blocks)
        return out

    if cfg.family == "hybrid":                    # zamba2: scanned mamba + shared
        per = mamba2.mamba_params_shapes(D, cfg.ssm)
        out["mamba"] = {k: (cfg.n_layers,) + v for k, v in per.items()}
        shared = dict(_attn_shapes(cfg))
        shared["ln2"] = (D,)
        shared["mlp"] = _mlp_shapes(cfg)
        out["shared"] = shared
        return out

    per: Dict[str, Any] = dict(_attn_shapes(cfg))
    per["ln2"] = (D,)
    if cfg.moe is not None:
        per["moe"] = _moe_shapes(cfg)
    else:
        per["mlp"] = _mlp_shapes(cfg)
    out["blocks"] = jax.tree.map(lambda s: (cfg.n_layers,) + s, per,
                                 is_leaf=is_shape)
    return out


def param_specs(cfg: ArchConfig):
    dt = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dt), param_shapes(cfg),
                        is_leaf=is_shape)


def init_params(cfg: ArchConfig, key) -> Any:
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=is_shape)
    keys = jax.random.split(key, len(flat))
    leaves = []
    dt = jnp.dtype(cfg.param_dtype)
    for (path, shape), k in zip(flat, keys):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        leaves.append(_init_one(name, shape, k, dt, cfg))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _init_one(name: str, shape, key, dt, cfg: ArchConfig):
    base = name.rsplit("/", 1)[-1]
    if base == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dt)
    if base == "dt_bias":
        dtv = jax.random.uniform(key, shape, jnp.float32, 1e-3, 0.1)
        return jnp.log(jnp.expm1(dtv)).astype(dt)
    if base == "b_if":                            # mLSTM gate biases (i low, f high)
        half = shape[0] // 2
        return jnp.concatenate([jnp.full((half,), -1.0), jnp.full((half,), 2.0)]
                               ).astype(dt)
    if base in ("ln", "ln1", "ln2", "out_norm", "final_norm", "D_skip") or \
            "norm" in base:
        return jnp.ones(shape, dt)
    if base == "b":                               # sLSTM gate bias
        return jnp.zeros(shape, dt)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 0.02 if base == "embed" else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)


# -------------------------------------------------------------------- blocks


def _attention(cfg: ArchConfig, p, x, positions, dtype, *, window: int = 0):
    """Full-sequence attention (train/prefill). Returns (out, (k, v))."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    xr = norm(x, p["ln1"], cfg.norm).astype(dtype)
    q = jnp.einsum("bsd,dh->bsh", xr, p["wq"].astype(dtype)).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,dh->bsh", xr, p["wk"].astype(dtype)).reshape(B, S, KV, hd)
    v = jnp.einsum("bsd,dh->bsh", xr, p["wv"].astype(dtype)).reshape(B, S, KV, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "heads4")   # scores shard on the q-head dim under TP
    if S > CHUNKED_ATTN_THRESHOLD:
        o = attention_chunked(q, k, v, causal=True, window=window)
    else:
        o = attention_full(q, k, v, causal=True, window=window)
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * hd).astype(dtype),
                     p["wo"].astype(dtype))
    return out, (k, v)


def _mlp_or_moe(cfg: ArchConfig, p, x, dtype):
    """Returns (out, aux_loss)."""
    xr = norm(x, p["ln2"], cfg.norm).astype(dtype)
    if cfg.moe is not None and "moe" in p:
        y, aux = moe_lib.moe_apply(
            p["moe"], xr, top_k=cfg.moe.top_k,
            capacity_factor=cfg.moe.capacity_factor, act=cfg.act, glu=cfg.glu,
            dtype=dtype)
        return y, aux
    return mlp_apply(p["mlp"], xr, cfg.act, cfg.glu, dtype), jnp.float32(0)


def _dense_block(cfg: ArchConfig, p, x, positions, dtype):
    a, _kv = _attention(cfg, p, x, positions, dtype)
    x = x + a.astype(x.dtype)
    m, aux = _mlp_or_moe(cfg, p, x, dtype)
    return x + m.astype(x.dtype), aux


def _remat(fn, policy: str):
    if policy == "full":
        return jax.checkpoint(fn)
    if policy == "selective":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


# ------------------------------------------------------------------- forward


def forward(cfg: ArchConfig, params, tokens, *, extra_embeds=None,
            remat: str = "none", positions=None):
    """Token ids -> final hidden states [B,S,D]."""
    dtype = jnp.dtype(cfg.compute_dtype)
    B, S = tokens.shape
    x = embed_lookup(params["embed"], tokens, dtype)
    if extra_embeds is not None:
        n = extra_embeds.shape[1]
        x = x.at[:, :n].add(extra_embeds.astype(dtype))
    x = constrain(x, "hidden")
    if positions is None:
        positions = jnp.arange(S)[None, :]

    if cfg.family == "ssm":
        xl = cfg.xlstm
        for i, bp in enumerate(params["blocks"]):
            if i % xl.slstm_every == 0:
                x, _ = xlstm_lib.slstm_block(bp, x, cfg.n_heads, cfg.act, dtype)
            else:
                x, _ = xlstm_lib.mlstm_block(bp, x, cfg.n_heads, dtype,
                                             chunk=xl.chunk)
            x = constrain(x, "hidden")
        return norm(x, params["final_norm"], cfg.norm), jnp.float32(0)

    if cfg.family == "hybrid":
        period = cfg.hybrid_attn_period
        shared = params["shared"]

        def body(carry, xs_l):
            x, = carry
            p_l, idx = xs_l
            p_l = anchor_block_grads(p_l, "mamba_grads")
            shr = anchor_block_grads(shared, "shared_grads")
            def mamba_fn(x):
                out, _ = mamba2.mamba_block(p_l, x, cfg.ssm, dtype)
                return x + out
            x = _remat(mamba_fn, remat)(x)
            def with_attn(x):
                a, _ = _attention(cfg, shr, x, positions, dtype,
                                  window=cfg.attn_window)
                x = x + a.astype(x.dtype)
                m, _ = _mlp_or_moe(cfg, shr, x, dtype)
                return x + m.astype(x.dtype)
            x = jax.lax.cond(idx % period == 0, _remat(with_attn, remat),
                             lambda x: x, x)
            return (constrain(x, "hidden"),), None

        (x,), _ = jax.lax.scan(body, (x,),
                               (params["mamba"], jnp.arange(cfg.n_layers)))
        return norm(x, params["final_norm"], cfg.norm), jnp.float32(0)

    # dense / moe / vlm: scan over stacked blocks
    def body(carry, p_l):
        x, aux = carry
        p_l = fsdp_gather_block(p_l, "blocks")
        p_l = anchor_block_grads(p_l, "blocks_grads")
        blk = functools.partial(_dense_block, cfg, p_l, positions=positions,
                                dtype=dtype)
        x, a = _remat(lambda x: blk(x), remat)(x)
        return (constrain(x, "hidden"), aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)), params["blocks"])
    return norm(x, params["final_norm"], cfg.norm), aux / cfg.n_layers


def logits_fn(cfg: ArchConfig, params, hidden):
    dtype = jnp.dtype(cfg.compute_dtype)
    head = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", hidden.astype(dtype), head.astype(dtype))
    return constrain(logits, "logits")


def loss_fn(cfg: ArchConfig, params, tokens, targets, *, extra_embeds=None,
            remat: str = "none"):
    hidden, aux = forward(cfg, params, tokens, extra_embeds=extra_embeds,
                          remat=remat)
    logits = logits_fn(cfg, params, hidden).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    correct = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - correct).mean()
    loss = nll + 0.01 * aux
    return loss, {"nll": nll, "aux": aux}


# -------------------------------------------------------------------- decode


def cache_shapes(cfg: ArchConfig, B: int, S_max: int) -> Dict[str, Any]:
    """Shape dict for the decode cache (per family)."""
    KV, hd = cfg.n_kv_heads, cfg.hd
    if cfg.family == "ssm":
        shapes: list = []
        xl = cfg.xlstm
        D = cfg.d_model
        for i in range(cfg.n_layers):
            if i % xl.slstm_every == 0:
                shapes.append({"h": (B, D), "c": (B, D), "n": (B, D), "m": (B, D)})
            else:
                di = int(D * xl.proj_factor_mlstm)
                dk = di // cfg.n_heads
                shapes.append({"C": (B, cfg.n_heads, dk, dk),
                               "n": (B, cfg.n_heads, dk), "m": (B, cfg.n_heads)})
        return {"blocks": tuple(shapes)}
    if cfg.family == "hybrid":
        L = cfg.n_layers
        s = cfg.ssm
        n_inv = L // cfg.hybrid_attn_period
        W = min(cfg.attn_window or S_max, S_max)
        return {
            "conv": (L, B, s.conv_kernel - 1, s.d_inner),
            "ssm": (L, B, s.n_heads, s.head_dim, s.state_dim),
            "k": (n_inv, B, W, KV, hd), "v": (n_inv, B, W, KV, hd),
        }
    L = cfg.n_layers
    return {"k": (L, B, S_max, KV, hd), "v": (L, B, S_max, KV, hd)}


def cache_specs(cfg: ArchConfig, B: int, S_max: int):
    dt = jnp.dtype(cfg.compute_dtype)
    f32 = jnp.float32

    def leaf(path_name, s):
        # xLSTM / SSM states are f32 (log-space stabilizers); KV caches bf16
        return jax.ShapeDtypeStruct(s, f32 if cfg.family == "ssm" or
                                    path_name in ("ssm",) else dt)
    shapes = cache_shapes(cfg, B, S_max)
    return jax.tree_util.tree_map_with_path(
        lambda p, s: leaf(str(p[-1].key) if hasattr(p[-1], "key") else "", s),
        shapes, is_leaf=is_shape)


def init_cache(cfg: ArchConfig, B: int, S_max: int):
    specs = cache_specs(cfg, B, S_max)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
    if cfg.family == "ssm":  # m-stabilizers start at -inf
        blocks = []
        for blk in cache["blocks"]:
            b = dict(blk)
            if "m" in b:
                b["m"] = jnp.full_like(b["m"], xlstm_lib.NEG)
            blocks.append(b)
        cache = {"blocks": tuple(blocks)}
    return cache


def decode_step(cfg: ArchConfig, params, cache, tokens, pos, *,
                encoder_memory=None):
    """One decode step. tokens [B,1], pos [B]. Returns (logits [B,1,V], cache)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    B = tokens.shape[0]
    x = constrain(embed_lookup(params["embed"], tokens, dtype), "hidden")
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    if cfg.family == "ssm":
        xl = cfg.xlstm
        new_blocks = []
        for i, (bp, st) in enumerate(zip(params["blocks"], cache["blocks"])):
            if i % xl.slstm_every == 0:
                state = (st["h"], st["c"], st["n"], st["m"])
                x, state = xlstm_lib.slstm_block(bp, x, cfg.n_heads, cfg.act,
                                                 dtype, state=state, decode=True)
                new_blocks.append(dict(h=state[0], c=state[1], n=state[2],
                                       m=state[3]))
            else:
                state = (st["C"], st["n"], st["m"])
                x, state = xlstm_lib.mlstm_block(bp, x, cfg.n_heads, dtype,
                                                 state=state, decode=True)
                new_blocks.append(dict(C=state[0], n=state[1], m=state[2]))
        hidden = norm(x, params["final_norm"], cfg.norm)
        return logits_fn(cfg, params, hidden), {"blocks": tuple(new_blocks)}

    if cfg.family == "hybrid":
        period = cfg.hybrid_attn_period
        shared = params["shared"]
        W = cache["k"].shape[2]

        def attn_decode_shared(x, k_c, v_c):
            xr = norm(x, shared["ln1"], cfg.norm).astype(dtype)
            q = jnp.einsum("bsd,dh->bsh", xr, shared["wq"].astype(dtype)) \
                .reshape(B, 1, H, hd)
            k = jnp.einsum("bsd,dh->bsh", xr, shared["wk"].astype(dtype)) \
                .reshape(B, 1, KV, hd)
            v = jnp.einsum("bsd,dh->bsh", xr, shared["wv"].astype(dtype)) \
                .reshape(B, 1, KV, hd)
            q = apply_rope(q, pos[:, None], cfg.rope_theta)
            k = apply_rope(k, pos[:, None], cfg.rope_theta)
            k_c = cache_insert(k_c, k, pos, window=W)
            v_c = cache_insert(v_c, v, pos, window=W)
            o = attention_decode(q, k_c, v_c, pos, window=W)
            a = jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, H * hd).astype(dtype),
                           shared["wo"].astype(dtype))
            x = x + a.astype(x.dtype)
            m, _ = _mlp_or_moe(cfg, shared, x, dtype)
            return x + m.astype(x.dtype), k_c, v_c

        def body(carry, xs_l):
            x, kc, vc, inv = carry
            p_l, conv_l, ssm_l, idx = xs_l
            out, (conv_l, ssm_l) = mamba2.mamba_block(
                p_l, x, cfg.ssm, dtype, conv_state=conv_l, ssm_state=ssm_l,
                decode=True)
            x = x + out

            def do_attn(args):
                x, kc, vc, inv = args
                k_l = jax.lax.dynamic_index_in_dim(kc, inv, 0, keepdims=False)
                v_l = jax.lax.dynamic_index_in_dim(vc, inv, 0, keepdims=False)
                x, k_l, v_l = attn_decode_shared(x, k_l, v_l)
                kc = jax.lax.dynamic_update_index_in_dim(kc, k_l, inv, 0)
                vc = jax.lax.dynamic_update_index_in_dim(vc, v_l, inv, 0)
                return x, kc, vc, inv + 1

            x, kc, vc, inv = jax.lax.cond(
                idx % period == 0, do_attn, lambda a: a, (x, kc, vc, inv))
            return (x, kc, vc, inv), (conv_l, ssm_l)

        (x, kc, vc, _), (conv_new, ssm_new) = jax.lax.scan(
            body, (x, cache["k"], cache["v"], 0),
            (params["mamba"], cache["conv"], cache["ssm"],
             jnp.arange(cfg.n_layers)))
        hidden = norm(x, params["final_norm"], cfg.norm)
        new_cache = {"conv": conv_new, "ssm": ssm_new, "k": kc, "v": vc}
        return logits_fn(cfg, params, hidden), new_cache

    # dense / moe / vlm — the cache is scanned READ-ONLY (xs); updates are
    # deferred to one post-scan scatter (in-loop insert copies the whole
    # stacked cache every token: see EXPERIMENTS.md §Perf D2)
    def body(x, xs_l):
        p_l, k_c, v_c = xs_l
        xr = norm(x, p_l["ln1"], cfg.norm).astype(dtype)
        q = jnp.einsum("bsd,dh->bsh", xr, p_l["wq"].astype(dtype)) \
            .reshape(B, 1, H, hd)
        k = jnp.einsum("bsd,dh->bsh", xr, p_l["wk"].astype(dtype)) \
            .reshape(B, 1, KV, hd)
        v = jnp.einsum("bsd,dh->bsh", xr, p_l["wv"].astype(dtype)) \
            .reshape(B, 1, KV, hd)
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
        # deferred insert: cache is read-only in-loop; the new K/V merges into
        # the softmax here and is scattered into the cache once, post-scan
        o = attention_decode(q, k_c, v_c, pos, new_kv=(k, v))
        a = jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, H * hd).astype(dtype),
                       p_l["wo"].astype(dtype))
        x = x + a.astype(x.dtype)
        m, _ = _mlp_or_moe(cfg, p_l, x, dtype)
        return constrain(x + m.astype(x.dtype), "hidden"), (k, v)

    x, (k_steps, v_steps) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"]))
    # single batched insert of all layers' new K/V ([L,B,1,KV,hd]) in place
    ins = jax.vmap(lambda c, n: cache_insert(c, n, pos))
    new_cache = {"k": ins(cache["k"], k_steps), "v": ins(cache["v"], v_steps)}
    hidden = norm(x, params["final_norm"], cfg.norm)
    return logits_fn(cfg, params, hidden), new_cache


def prefill(cfg: ArchConfig, params, tokens, *, extra_embeds=None, s_max=None):
    """Prefill: forward pass + build the KV cache (dense families).

    Returns (last-position logits [B,1,V], cache). ``s_max`` sizes the cache for
    subsequent decode (defaults to S). For state families the cache is produced
    by running the recurrence (same forward, states carried out).
    """
    dtype = jnp.dtype(cfg.compute_dtype)
    B, S = tokens.shape
    s_max = s_max or S
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    positions = jnp.arange(S)[None, :]
    x = embed_lookup(params["embed"], tokens, dtype)
    if extra_embeds is not None:
        n = extra_embeds.shape[1]
        x = x.at[:, :n].add(extra_embeds.astype(dtype))
    x = constrain(x, "hidden")

    if cfg.family == "ssm":
        xl = cfg.xlstm
        new_blocks = []
        for i, bp in enumerate(params["blocks"]):
            if i % xl.slstm_every == 0:
                x, st = xlstm_lib.slstm_block(bp, x, cfg.n_heads, cfg.act, dtype)
                new_blocks.append(dict(h=st[0], c=st[1], n=st[2], m=st[3]))
            else:
                x, st = xlstm_lib.mlstm_block(bp, x, cfg.n_heads, dtype,
                                              chunk=xl.chunk)
                new_blocks.append(dict(C=st[0], n=st[1], m=st[2]))
        hidden = norm(x, params["final_norm"], cfg.norm)
        logits = logits_fn(cfg, params, hidden[:, -1:])
        return logits, {"blocks": tuple(new_blocks)}

    if cfg.family == "hybrid":
        period = cfg.hybrid_attn_period
        shared = params["shared"]
        W = min(cfg.attn_window or s_max, s_max)

        def body(carry, xs_l):
            x, = carry
            p_l, idx = xs_l
            out, (conv_l, ssm_l) = mamba2.mamba_block(p_l, x, cfg.ssm, dtype)
            x = x + out

            def with_attn(x):
                xr = norm(x, shared["ln1"], cfg.norm).astype(dtype)
                q = jnp.einsum("bsd,dh->bsh", xr, shared["wq"].astype(dtype)) \
                    .reshape(B, S, H, hd)
                k = jnp.einsum("bsd,dh->bsh", xr, shared["wk"].astype(dtype)) \
                    .reshape(B, S, KV, hd)
                v = jnp.einsum("bsd,dh->bsh", xr, shared["wv"].astype(dtype)) \
                    .reshape(B, S, KV, hd)
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
                if S > CHUNKED_ATTN_THRESHOLD:
                    o = attention_chunked(q, k, v, window=cfg.attn_window)
                else:
                    o = attention_full(q, k, v, causal=True,
                                       window=cfg.attn_window)
                a = jnp.einsum("bsh,hd->bsd",
                               o.reshape(B, S, H * hd).astype(dtype),
                               shared["wo"].astype(dtype))
                xa = x + a.astype(x.dtype)
                m, _ = _mlp_or_moe(cfg, shared, xa, dtype)
                # cache the last min(W,S) positions in rolling layout
                # (slot = pos % W): if W >= S slots are 0..S-1 (pad right);
                # else position S-W+i lives at slot (S+i) % W -> roll by S % W
                if W >= S:
                    kw = jnp.pad(k, ((0, 0), (0, W - S), (0, 0), (0, 0)))
                    vw = jnp.pad(v, ((0, 0), (0, W - S), (0, 0), (0, 0)))
                else:
                    kw = jnp.roll(k[:, -W:], S % W, axis=1)
                    vw = jnp.roll(v[:, -W:], S % W, axis=1)
                return xa + m.astype(xa.dtype), kw, vw

            def no_attn(x):
                z = jnp.zeros((B, W, KV, hd), dtype)
                return x, z, z

            x, kw, vw = jax.lax.cond(idx % period == 0, with_attn, no_attn, x)
            return (x,), (conv_l, ssm_l, kw, vw)

        (x,), (conv_new, ssm_new, k_all, v_all) = jax.lax.scan(
            body, (x,), (params["mamba"], jnp.arange(cfg.n_layers)))
        # keep only the rows where attention actually ran (idx % period == 0)
        sel = np.arange(cfg.n_layers) % period == 0
        idxs = jnp.asarray(np.nonzero(sel)[0])
        new_cache = {"conv": conv_new, "ssm": ssm_new,
                     "k": k_all[idxs], "v": v_all[idxs]}
        hidden = norm(x, params["final_norm"], cfg.norm)
        return logits_fn(cfg, params, hidden[:, -1:]), new_cache

    def body(carry, p_l):
        x, aux = carry
        a, (k, v) = _attention(cfg, p_l, x, positions, dtype)
        x = x + a.astype(x.dtype)
        m, al = _mlp_or_moe(cfg, p_l, x, dtype)
        return (constrain(x + m.astype(x.dtype), "hidden"), aux + al), \
            (constrain(k, "kv"), constrain(v, "kv"))

    (x, _aux), (k_all, v_all) = jax.lax.scan(
        body, (x, jnp.float32(0)), params["blocks"])
    if s_max > S:
        pad = ((0, 0), (0, 0), (0, s_max - S), (0, 0), (0, 0))
        k_all = jnp.pad(k_all, pad)
        v_all = jnp.pad(v_all, pad)
    hidden = norm(x, params["final_norm"], cfg.norm)
    logits = logits_fn(cfg, params, hidden[:, -1:])
    return logits, {"k": k_all, "v": v_all}


# ------------------------------------------------------------------ paged KV

PAGED_FAMILIES = ("dense", "moe", "vlm")


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Decode-kernel knobs, validated once (at engine construction) instead of
    leaking through every ``decode_step_paged`` call signature."""

    attn_impl: str = "xla"         # xla (gather) | pallas (paged-attention)
    interpret: bool = False        # Pallas interpreter (CPU-only runs)

    def __post_init__(self):
        if self.attn_impl not in ("xla", "pallas"):
            raise ValueError(f"attn_impl must be 'xla' or 'pallas', "
                             f"got {self.attn_impl!r}")


def _check_dense_kv(cfg: ArchConfig, what: str) -> None:
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"{what} needs a dense per-layer K/V cache; family "
            f"'{cfg.family}' keeps recurrent/rolling state (ROADMAP)")


def _check_paged(cfg: ArchConfig) -> None:
    _check_dense_kv(cfg, "paged KV cache")


def paged_cache_shapes(cfg: ArchConfig, num_pages: int,
                       page_size: int) -> Dict[str, Tuple[int, ...]]:
    """Physical KV pool: ``num_pages`` allocatable pages + 1 reserved null
    page (physical page 0) that unmapped page-table entries point at."""
    _check_paged(cfg)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    shape = (L, num_pages + 1, page_size, KV, hd)
    return {"k_pages": shape, "v_pages": shape}


def paged_cache_specs(cfg: ArchConfig, num_pages: int, page_size: int):
    dt = jnp.dtype(cfg.compute_dtype)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dt),
                        paged_cache_shapes(cfg, num_pages, page_size),
                        is_leaf=is_shape)


def init_paged_cache(cfg: ArchConfig, num_pages: int, page_size: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_cache_specs(cfg, num_pages, page_size))


def decode_step_paged(cfg: ArchConfig, params, pool, page_table, tokens, pos,
                      *, kernel: Optional[KernelSpec] = None):
    """One decode step against the paged pool. tokens [B,1], pos [B],
    page_table [B,P] int32 (logical page -> physical page; null rows for
    inactive slots). Returns (logits [B,1,V], pool).

    Structure mirrors the dense ``decode_step``: the pool is scanned
    READ-ONLY per layer, attention gathers K/V through the page table, and
    the new token's K/V is scattered into its page once, post-scan. The XLA
    attention gathers only the batch's live blocks of ``block_pages`` pages
    (128 tokens): the step builds the list of (row, block, pages) from
    ``page_table`` and ``pos`` once (``live_block_list``), and every layer
    gathers the smallest width of ``decode_block_ladder`` that holds it
    (``attention_decode_live``), not all ``P`` pages of every row.
    ``kernel.attn_impl='pallas'`` streams the physical pages of whole rows
    in the Pallas kernel instead. The gather and attention, the MLP and the
    scatter run under the named scopes ``attention``, ``mlp`` and
    ``kv_write``, so a device profile can attribute the step's time to them.
    """
    _check_paged(cfg)
    kernel = kernel or KernelSpec()
    dtype = jnp.dtype(cfg.compute_dtype)
    B = tokens.shape[0]
    x = constrain(embed_lookup(params["embed"], tokens, dtype), "hidden")
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    if kernel.attn_impl == "xla":
        ps, P = pool["k_pages"].shape[2], page_table.shape[1]
        c = block_pages(ps, P)
        widths = decode_block_ladder(B, -(-P // c))
        blocks = live_block_list(page_table, pos, n_blocks=widths[-1],
                                 block_pages=c, page_size=ps)

    def body(x, xs_l):
        p_l, k_pg, v_pg = xs_l
        xr = norm(x, p_l["ln1"], cfg.norm).astype(dtype)
        q = jnp.einsum("bsd,dh->bsh", xr, p_l["wq"].astype(dtype)) \
            .reshape(B, 1, H, hd)
        k = jnp.einsum("bsd,dh->bsh", xr, p_l["wk"].astype(dtype)) \
            .reshape(B, 1, KV, hd)
        v = jnp.einsum("bsd,dh->bsh", xr, p_l["wv"].astype(dtype)) \
            .reshape(B, 1, KV, hd)
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
        with jax.named_scope("attention"):
            if kernel.attn_impl == "pallas":
                from ..kernels.paged_attention import paged_attention_decode
                o = paged_attention_decode(q, k_pg, v_pg, page_table, pos,
                                           new_kv=(k, v),
                                           interpret=kernel.interpret)
            else:
                o = attention_decode_live(q, k_pg, v_pg, blocks, pos, (k, v),
                                          widths)
        a = jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, H * hd).astype(dtype),
                       p_l["wo"].astype(dtype))
        x = x + a.astype(x.dtype)
        with jax.named_scope("mlp"):
            m, _ = _mlp_or_moe(cfg, p_l, x, dtype)
        return constrain(x + m.astype(x.dtype), "hidden"), (k, v)

    x, (k_steps, v_steps) = jax.lax.scan(
        body, x, (params["blocks"], pool["k_pages"], pool["v_pages"]))
    with jax.named_scope("kv_write"):
        new_pool = {
            "k_pages": cache_insert_paged(pool["k_pages"], k_steps,
                                          page_table, pos),
            "v_pages": cache_insert_paged(pool["v_pages"], v_steps,
                                          page_table, pos),
        }
    hidden = norm(x, params["final_norm"], cfg.norm)
    return logits_fn(cfg, params, hidden), new_pool


def prefill_chunk(cfg: ArchConfig, params, pool, page_row, tokens, offset):
    """One chunk of a chunked prefill for a single sequence.

    tokens [1,C] (positions ``offset .. offset+C-1``); page_row [P] int32 —
    the sequence's page-table row, whose already-written pages hold the
    previous chunks' K/V. Returns (last-position logits [1,1,V],
    (k_chunk, v_chunk) [L,1,C,KV,hd]) — the caller scatters the chunk K/V
    into its pages (``cache_write_pages``) before the next chunk runs.
    """
    _check_paged(cfg)
    dtype = jnp.dtype(cfg.compute_dtype)
    B, C = tokens.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    offset = jnp.asarray(offset, jnp.int32)
    positions = offset + jnp.arange(C)[None, :]
    off_b = jnp.broadcast_to(offset[None], (B,))
    x = constrain(embed_lookup(params["embed"], tokens, dtype), "hidden")

    def body(x, xs_l):
        p_l, k_pg, v_pg = xs_l
        xr = norm(x, p_l["ln1"], cfg.norm).astype(dtype)
        q = jnp.einsum("bsd,dh->bsh", xr, p_l["wq"].astype(dtype)) \
            .reshape(B, C, H, hd)
        k = jnp.einsum("bsd,dh->bsh", xr, p_l["wk"].astype(dtype)) \
            .reshape(B, C, KV, hd)
        v = jnp.einsum("bsd,dh->bsh", xr, p_l["wv"].astype(dtype)) \
            .reshape(B, C, KV, hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        with jax.named_scope("attention"):
            k_ctx = gather_kv_pages(k_pg, page_row[None, :])   # [1,P*PS,..]
            v_ctx = gather_kv_pages(v_pg, page_row[None, :])
            o = attention_prefill_chunk(q, k_ctx, v_ctx, k, v, off_b)
        a = jnp.einsum("bsh,hd->bsd", o.reshape(B, C, H * hd).astype(dtype),
                       p_l["wo"].astype(dtype))
        x = x + a.astype(x.dtype)
        with jax.named_scope("mlp"):
            m, _ = _mlp_or_moe(cfg, p_l, x, dtype)
        return constrain(x + m.astype(x.dtype), "hidden"), (k, v)

    x, (k_steps, v_steps) = jax.lax.scan(
        body, x, (params["blocks"], pool["k_pages"], pool["v_pages"]))
    hidden = norm(x, params["final_norm"], cfg.norm)
    return logits_fn(cfg, params, hidden[:, -1:]), (k_steps, v_steps)


# ------------------------------------------------------- speculative verify


def _verify_qkv(cfg: ArchConfig, p_l, x, positions, dtype):
    """Projections + RoPE for a verify chunk at per-row positions [B,C]."""
    B, C, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    xr = norm(x, p_l["ln1"], cfg.norm).astype(dtype)
    q = jnp.einsum("bsd,dh->bsh", xr, p_l["wq"].astype(dtype)) \
        .reshape(B, C, H, hd)
    k = jnp.einsum("bsd,dh->bsh", xr, p_l["wk"].astype(dtype)) \
        .reshape(B, C, KV, hd)
    v = jnp.einsum("bsd,dh->bsh", xr, p_l["wv"].astype(dtype)) \
        .reshape(B, C, KV, hd)
    return (apply_rope(q, positions, cfg.rope_theta),
            apply_rope(k, positions, cfg.rope_theta), v)


def verify_chunk(cfg: ArchConfig, params, cache, tokens, pos):
    """Speculative verify against the dense cache: score all C = k+1 chunk
    tokens (the last emitted token + k draft proposals) in one batched call.

    tokens [B,C] at per-row absolute positions ``pos .. pos+C-1``;
    cache [L,B,S,KV,hd] holds context positions ``< pos`` per row. The chunk
    attends to cached context plus itself causally
    (``attention_prefill_chunk``) and its K/V is written at its positions in
    one post-scan insert, mirroring ``decode_step``'s read-only layer scan.
    Returns (logits [B,C,V], cache) — the rejection sampler picks the
    accepted prefix from the logits; rejected positions stay masked by
    ``pos`` until the next chunk overwrites them.
    """
    _check_dense_kv(cfg, "speculative verify")
    dtype = jnp.dtype(cfg.compute_dtype)
    B, C = tokens.shape
    positions = pos[:, None] + jnp.arange(C)[None, :]
    x = constrain(embed_lookup(params["embed"], tokens, dtype), "hidden")

    def body(x, xs_l):
        p_l, k_c, v_c = xs_l
        q, k, v = _verify_qkv(cfg, p_l, x, positions, dtype)
        o = attention_prefill_chunk(q, k_c, v_c, k, v, pos)
        a = jnp.einsum("bsh,hd->bsd",
                       o.reshape(B, C, cfg.n_heads * cfg.hd).astype(dtype),
                       p_l["wo"].astype(dtype))
        x = x + a.astype(x.dtype)
        m, _ = _mlp_or_moe(cfg, p_l, x, dtype)
        return constrain(x + m.astype(x.dtype), "hidden"), (k, v)

    x, (k_steps, v_steps) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"]))
    ins = jax.vmap(lambda c, n: cache_insert_chunk(c, n, pos))
    new_cache = {"k": ins(cache["k"], k_steps), "v": ins(cache["v"], v_steps)}
    hidden = norm(x, params["final_norm"], cfg.norm)
    return logits_fn(cfg, params, hidden), new_cache


def verify_chunk_paged(cfg: ArchConfig, params, pool, page_table, tokens,
                       pos):
    """Speculative verify against the paged pool: same contract as
    :func:`verify_chunk` but context is gathered through the page table and
    the chunk K/V is scattered into its covering pages
    (``cache_insert_paged_chunk``). ``page_table`` [B,P] must map every page
    covering ``pos .. pos+C-1`` (the engine allocates the lookahead ahead of
    the step and rolls the tail back on rejection); it may be column-sliced
    to the pages actually in use — context past ``pos`` is masked anyway.
    """
    _check_dense_kv(cfg, "speculative verify")
    dtype = jnp.dtype(cfg.compute_dtype)
    B, C = tokens.shape
    positions = pos[:, None] + jnp.arange(C)[None, :]
    x = constrain(embed_lookup(params["embed"], tokens, dtype), "hidden")

    def body(x, xs_l):
        p_l, k_pg, v_pg = xs_l
        q, k, v = _verify_qkv(cfg, p_l, x, positions, dtype)
        k_ctx = gather_kv_pages(k_pg, page_table)
        v_ctx = gather_kv_pages(v_pg, page_table)
        o = attention_prefill_chunk(q, k_ctx, v_ctx, k, v, pos)
        a = jnp.einsum("bsh,hd->bsd",
                       o.reshape(B, C, cfg.n_heads * cfg.hd).astype(dtype),
                       p_l["wo"].astype(dtype))
        x = x + a.astype(x.dtype)
        m, _ = _mlp_or_moe(cfg, p_l, x, dtype)
        return constrain(x + m.astype(x.dtype), "hidden"), (k, v)

    x, (k_steps, v_steps) = jax.lax.scan(
        body, x, (params["blocks"], pool["k_pages"], pool["v_pages"]))
    new_pool = {
        "k_pages": cache_insert_paged_chunk(pool["k_pages"], k_steps,
                                            page_table, pos),
        "v_pages": cache_insert_paged_chunk(pool["v_pages"], v_steps,
                                            page_table, pos),
    }
    hidden = norm(x, params["final_norm"], cfg.norm)
    return logits_fn(cfg, params, hidden), new_pool
