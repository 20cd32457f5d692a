"""AXPY Pallas kernel — the paper's Fig. 8 kernel as a TPU VPU kernel.

Adaptation note (DESIGN.md §2): the paper's CUDA AXPY maps `i` to grid*block
threads; on TPU the same UPIR worksharing loop lowers to a 1-D pallas grid whose
BlockSpec tiles live in VMEM and are processed by the 8x128 VPU lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _axpy_kernel(a_ref, x_ref, y_ref, o_ref):
    o_ref[...] = a_ref[0] * x_ref[...] + y_ref[...]


def axpy(a, x, y, *, block: int = 1024, interpret: bool = False):
    """a: scalar; x/y: [N]. Block must divide N (pad upstream otherwise)."""
    n = x.shape[0]
    block = min(block, n)
    assert n % block == 0, (n, block)
    a_arr = jnp.asarray(a, x.dtype).reshape(1)
    return pl.pallas_call(
        _axpy_kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(a_arr, x, y)
