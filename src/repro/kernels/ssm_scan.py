"""SSD chunk-recurrence Pallas kernel (Mamba2 hot spot).

One grid step processes one (batch, head-block) pair's chunk sequence: the
state [P, N] block lives in VMEM scratch across the chunk-grid dimension while
x/dt/B/C chunk tiles stream through. Computes, per chunk:

  intra: M[t,s] = (C_t . B_s) * exp(cum_t - cum_s) * dt_s  (s <= t), Y += M X
  inter: Y_t += exp(cum_t) * C_t . h;   h <- exp(cum_Q) h + sum decayed inputs

This is the per-(B,H) slice of models/mamba2.ssd_chunked (G=1), validated
against kernels/ref.ssm_chunk_scan in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *, chunk):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)              # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)            # [Q]
    A = a_ref[0]                                  # scalar (per head)
    Bm = b_ref[0].astype(jnp.float32)             # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)             # [Q, N]

    a = dt * A                                    # [Q], negative
    cum = jnp.cumsum(a)
    seg = cum[:, None] - cum[None, :]             # [t, s]
    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(causal, jnp.exp(seg), 0.0)
    CB = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)   # [t, s]
    M = CB * L * dt[None, :]
    y_intra = jnp.dot(M, x, preferred_element_type=jnp.float32)  # [Q, P]

    h = h_ref[...]                                # [P, N]
    y_inter = jnp.exp(cum)[:, None] * jnp.dot(Cm, h.T,
                                              preferred_element_type=jnp.float32)
    w = jnp.exp(cum[-1] - cum) * dt               # [Q]
    dstate = jnp.dot((x * w[:, None]).T, Bm,
                     preferred_element_type=jnp.float32)          # [P, N]
    h_ref[...] = jnp.exp(cum[-1]) * h + dstate
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)


def ssm_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    """x [B,S,H,P]; dt [B,S,H]; A [H]; Bm/Cm [B,S,N] (G=1).

    Returns y [B,S,H,P] (state output is kept in-kernel; the jnp reference
    path returns it for the decode hand-off — kernels/ops exposes both).
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    # layout: one grid row per (b, h): x -> [B*H, S, P]; dt -> [B*H, S]
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S)
    af = jnp.tile(A.astype(jnp.float32), B)                   # [B*H]
    bf = jnp.repeat(Bm, H, axis=0).reshape(B, H, S, N) if False else \
        jnp.broadcast_to(Bm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    cf = jnp.broadcast_to(Cm[:, None], (B, H, S, N)).reshape(B * H, S, N)

    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk), lambda g, c: (g, c)),
            pl.BlockSpec((1,), lambda g, c: (g,)),
            pl.BlockSpec((1, chunk, N), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda g, c: (g, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda g, c: (g, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xf, dtf, af, bf, cf)
    return y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
