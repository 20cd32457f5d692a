"""Dense decoder with grouped-query attention and a GLU MLP (granite-3 as
this repository serves it): layout, float32 reference and cost model.

Per layer: ``x += attn(rmsnorm(x))``; ``x += mlp(rmsnorm(x))``; RoPE on q and
k; tied embeddings. Sizes come from the configuration file's ``model``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import common

BF16 = 2


def _hd(m):
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def layout(m):
    """The served weight tree: ``{path: (shape, kind, fan_in)}``."""
    L, D, H, KV, F, V = (m["n_layers"], m["d_model"], m["n_heads"],
                         m["n_kv_heads"], m["d_ff"], m["vocab"])
    hd = _hd(m)
    blk = {"ln1": ((L, D), "norm", 0), "ln2": ((L, D), "norm", 0),
           "wq": ((L, D, H * hd), "matrix", D),
           "wk": ((L, D, KV * hd), "matrix", D),
           "wv": ((L, D, KV * hd), "matrix", D),
           "wo": ((L, H * hd, D), "matrix", H * hd),
           "mlp": {"w1": ((L, D, F), "matrix", D),
                   "w3": ((L, D, F), "matrix", D),
                   "w2": ((L, F, D), "matrix", F)}}
    out = {"embed": ((V, D), "embed", 0), "final_norm": ((D,), "norm", 0),
           "blocks": blk}
    if not m.get("tied_embeddings", True):
        out["lm_head"] = ((D, V), "matrix", D)
    return out


# ----------------------------------------------------------------- reference


@functools.partial(jax.jit, static_argnames=("mk", "fp8"))
def _block(x, p, pos, mk, fp8):
    m = common.thaw(mk)
    x = x + common.attention(common.rmsnorm(x, p["ln1"]), p, m, pos, fp8)
    return x + common.mlp(common.rmsnorm(x, p["ln2"]), p["mlp"], m["act"], fp8)


@functools.partial(jax.jit, static_argnames=("fp8",))
def _head(x, final_norm, head, fp8):
    return common.mm(common.rmsnorm(x, final_norm), head, fp8)


def reference_logits(m, w, tokens, fp8: bool = False):
    """Float32 logits [S, V] of ``tokens`` [S], layer by layer; ``fp8``
    computes every matmul from float8 operands (the control)."""
    mk = common.freeze(m)
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(jnp.float32)
    for i in range(m["n_layers"]):
        x = _block(x, common.layer(w["blocks"], i), pos, mk, fp8)
    head = w["lm_head"] if "lm_head" in w else w["embed"].T
    return _head(x, w["final_norm"], head.astype(jnp.float32), fp8)


# ---------------------------------------------------------------- cost model


def param_counts(m):
    """Parameters by kind."""
    L, D, H, KV, F, V = (m["n_layers"], m["d_model"], m["n_heads"],
                         m["n_kv_heads"], m["d_ff"], m["vocab"])
    hd = _hd(m)
    emb = V * D * (1 if m.get("tied_embeddings", True) else 2)
    return {"embed": emb,
            "attention": L * (2 * D * H * hd + 2 * D * KV * hd),
            "mlp": L * 3 * D * F,
            "norms": (2 * L + 1) * D}


def param_bytes(m):
    return {k: v * BF16 for k, v in param_counts(m).items()}


def kv_bytes_per_token(m):
    return m["n_layers"] * 2 * m["n_kv_heads"] * _hd(m) * BF16


def _matmul_params_per_token(m):
    c = param_counts(m)
    return c["attention"] + c["mlp"]


def _attn_flops(m, queries_times_keys):
    """QK^T and PV over ``queries_times_keys`` (query, key) pairs, all layers."""
    return 4 * m["n_layers"] * m["n_heads"] * _hd(m) * queries_times_keys


def decode_cost(m, ctx):
    """(FLOPs, bytes) one decode step requires; ``ctx`` lists, per active
    slot, the positions its new token attends to (itself included)."""
    B = len(ctx)
    head = 2 * m["d_model"] * m["vocab"]
    flops = B * (2 * _matmul_params_per_token(m) + head) \
        + _attn_flops(m, sum(ctx))
    kv = kv_bytes_per_token(m)
    nbytes = sum(param_bytes(m).values()) + kv * (sum(ctx) - B) + kv * B
    return flops, nbytes


def prefill_cost(m, offset, n):
    """(FLOPs, bytes) a prefill forward of ``n`` prompt tokens at positions
    ``offset .. offset+n-1`` requires, attending causally to all earlier
    positions; logits for the last position only."""
    pairs = n * offset + n * (n + 1) // 2
    flops = n * 2 * _matmul_params_per_token(m) \
        + 2 * m["d_model"] * m["vocab"] + _attn_flops(m, pairs)
    kv = kv_bytes_per_token(m)
    nbytes = sum(param_bytes(m).values()) + kv * offset + kv * n
    return flops, nbytes
