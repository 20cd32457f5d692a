"""Matrix-vector Pallas kernel — the paper's MV benchmark.

Row-block tiling: each grid step loads a (bm x bk) tile of A and the matching
x block into VMEM and accumulates the bm partial dot products in f32; the k
loop is innermost so y tiles stay VMEM-resident. MV is memory-bound — the tile
shape choice is about HBM streaming, not MXU occupancy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mv_kernel(a_ref, x_ref, o_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], x_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matvec(a, x, *, bm: int = 512, bk: int = 1024, interpret: bool = False):
    """y[M] = A[M,K] @ x[K]."""
    M, K = a.shape
    bm, bk = min(bm, M), min(bk, K)
    assert M % bm == 0 and K % bk == 0, (M, K, bm, bk)
    k_steps = K // bk
    return pl.pallas_call(
        functools.partial(_mv_kernel, k_steps=k_steps),
        grid=(M // bm, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk,), lambda i, k: (k,)),
        ],
        out_specs=pl.BlockSpec((bm,), lambda i, k: (i,)),
        out_shape=jax.ShapeDtypeStruct((M,), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm,), jnp.float32)],
        interpret=interpret,
    )(a, x)
