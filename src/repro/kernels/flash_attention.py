"""Flash attention (forward) Pallas kernel — the model hot-spot kernel.

Online-softmax over KV blocks with running (m, l, acc) in VMEM scratch.
Grid: (batch*heads, q_blocks, kv_blocks); kv is the innermost (sequential) dim
so the q tile and accumulators stay VMEM-resident while K/V tiles stream.
Causal masking is positional; fully-masked kv blocks are skipped via pl.when
(the compiler still schedules their loads — the TPU win comes from the mosaic
pipeline, not from control flow).

This is the Pallas counterpart of models/layers.attention_chunked (XLA) and is
validated against kernels/ref.flash_attention in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, bq, bk, kv_steps):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # kv block strictly after the q block -> fully masked
        run = (ik * bk) <= (iq * bq + bq - 1)

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0]                              # [bq, hd]
        k = k_ref[0]                              # [bk, hd]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == kv_steps - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 256,
                    bk: int = 256, interpret: bool = False):
    """q/k/v: [B, S, H, hd] (same head count: expand GQA upstream)."""
    B, S, H, hd = q.shape
    bq, bk = min(bq, S), min(bk, S)
    assert S % bq == 0 and S % bk == 0
    scale = 1.0 / np.sqrt(hd)
    # layout: fold batch and heads into one grid dim; [BH, S, hd]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kv_steps = S // bk

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, kv_steps=kv_steps),
        grid=(B * H, S // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
