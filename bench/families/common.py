"""Plain float32 building blocks shared by the reference forwards.

Every matmul runs at ``Precision.HIGHEST``. ``quant`` rounds a matmul operand
to float8 e4m3 with a per-tensor scale: the control's precision step.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6          # the RMSNorm epsilon the served model uses


def quant(x, fp8: bool):
    """``x`` as float8 e4m3 would hold it, with a per-tensor absmax scale."""
    if not fp8:
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * scale


def mm(x, w, fp8: bool = False):
    return jnp.matmul(quant(x, fp8), quant(w.astype(jnp.float32), fp8),
                      precision=HI)


def rmsnorm(x, w):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * w.astype(jnp.float32)


def rope(x, pos, theta: float):
    """Rotary embedding of ``x`` [S, heads, hd] at positions ``pos`` [S]:
    the two halves of each head rotate as one complex number."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, p, m, pos, fp8: bool, window: int = 0):
    """Causal grouped-query self-attention of ``x`` [S, D] with RoPE."""
    S = x.shape[0]
    H, KV = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    q = mm(x, p["wq"], fp8).reshape(S, H, hd)
    k = mm(x, p["wk"], fp8).reshape(S, KV, hd)
    v = mm(x, p["wv"], fp8).reshape(S, KV, hd)
    q = rope(q, pos, m["rope_theta"])
    k = rope(k, pos, m["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", quant(q, fp8), quant(k, fp8),
                   precision=HI) / np.sqrt(hd)
    ok = pos[None, :] <= pos[:, None]
    if window:
        ok &= pos[None, :] > pos[:, None] - window
    s = jnp.where(ok[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", quant(a, fp8), quant(v, fp8),
                   precision=HI).reshape(S, H * hd)
    return mm(o, p["wo"], fp8)


def mlp(x, p, act: str, fp8: bool):
    h = mm(x, p["w1"], fp8)
    h = jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h, approximate=True)
    h = h * mm(x, p["w3"], fp8)
    return mm(h, p["w2"], fp8)


def layer(tree, i):
    """Layer ``i`` of a stacked ``{name: [L, ...]}`` tree, as float32."""
    return jax.tree.map(lambda a: a[i].astype(jnp.float32), tree)


def gaps(logits, tokens):
    """For each row: the reference's best logit minus the logit of the
    served token, and the token the reference puts first."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(logits, axis=-1)


def freeze(m) -> str:
    """A hashable form of the sizes dict, for jit's static arguments."""
    return json.dumps(m, sort_keys=True)


def thaw(key: str):
    return json.loads(key)
