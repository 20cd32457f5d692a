"""5-point 2D stencil Pallas kernel — the paper's stencil benchmark.

TPU adaptation: instead of CUDA shared-memory halos, each grid step reads a
(bm+2 x bn+2) haloed window via element-offset dynamic slices of the padded
input (adjacent windows overlap by the 1-element halo), computes the interior,
and writes the (bm x bn) output tile. Zero boundary handled by pre-padding the
input once in HBM.

Note: block index maps can't express overlapping tiles on this jax version,
so the padded input is passed as one whole block and the halo windows are
dslice loads from it — fine for the interpret-mode benchmarks this repo runs;
a compiled TPU (Mosaic) build would want the input in ANY memory space with
per-tile DMA instead of a whole-array VMEM block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stencil_kernel(u_ref, o_ref, *, bm, bn, w_center, w_side):
    i, j = pl.program_id(0), pl.program_id(1)
    # haloed (bm+2 x bn+2) read at element offset (i*bm, j*bn): block index
    # maps can't express overlapping tiles, so the halo is a dslice load
    u = u_ref[pl.ds(i * bm, bm + 2), pl.ds(j * bn, bn + 2)]
    o_ref[...] = (w_center * u[1:-1, 1:-1]
                  + w_side * (u[:-2, 1:-1] + u[2:, 1:-1]
                              + u[1:-1, :-2] + u[1:-1, 2:])).astype(o_ref.dtype)


def stencil2d(u, *, w_center: float = -4.0, w_side: float = 1.0,
              bm: int = 256, bn: int = 256, interpret: bool = False):
    """u: [M, N]; zero boundary."""
    M, N = u.shape
    bm, bn = min(bm, M), min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    up = jnp.pad(u, 1)  # zero halo in HBM

    return pl.pallas_call(
        functools.partial(_stencil_kernel, bm=bm, bn=bn,
                          w_center=w_center, w_side=w_side),
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec(up.shape, lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), u.dtype),
        interpret=interpret,
    )(up)
