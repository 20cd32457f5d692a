"""Public jit'd wrappers for the Pallas kernels + the UPIR kernel registry.

The UPIR ``simd`` loop-parallelization lowers through this registry: a kernel
program whose loop carries a ``Simd`` parallelization resolves its ``KernelOp.fn``
here with ``backend='pallas'``; a ``Worksharing``-parallelized program resolves
to the jnp oracle (``ref.py``) which XLA shards over the SPMD units. That is the
paper's separation of canonical loop from parallelization strategy, made
executable.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax

from . import axpy as _axpy_mod
from . import flash_attention as _fa_mod
from . import matmul as _mm_mod
from . import matvec as _mv_mod
from . import ref
from . import ssm_scan as _ssm_mod
from . import stencil2d as _st_mod

# jit'd pallas entry points. ``interpret`` is a static argument of each:
# False (the default) compiles to Mosaic for the TPU; True runs the Pallas
# interpreter, which is how CPU-only tests exercise the same kernels.

axpy = jax.jit(_axpy_mod.axpy, static_argnames=("block", "interpret"))
matmul = jax.jit(_mm_mod.matmul, static_argnames=("bm", "bn", "bk",
                                                  "interpret"))
matvec = jax.jit(_mv_mod.matvec, static_argnames=("bm", "bk", "interpret"))
stencil2d = jax.jit(_st_mod.stencil2d,
                    static_argnames=("w_center", "w_side", "bm", "bn",
                                     "interpret"))
flash_attention = jax.jit(_fa_mod.flash_attention,
                          static_argnames=("causal", "bq", "bk", "interpret"))
ssm_scan = jax.jit(_ssm_mod.ssm_scan, static_argnames=("chunk", "interpret"))


PALLAS: Dict[str, Callable] = {
    "axpy": axpy,
    "matmul": matmul,
    "matvec": matvec,
    "stencil2d": stencil2d,
    "flash_attention": flash_attention,
    "ssm_scan": ssm_scan,
}

REFERENCE: Dict[str, Callable] = {
    "axpy": jax.jit(ref.axpy),
    "matmul": jax.jit(ref.matmul),
    "matvec": jax.jit(ref.matvec),
    "stencil2d": jax.jit(ref.stencil2d),
    "flash_attention": jax.jit(ref.flash_attention,
                               static_argnames=("causal",)),
}


def resolve(fn: str, backend: str = "reference", *,
            interpret: bool = False) -> Callable:
    """UPIR KernelOp resolution: 'pallas' (simd) or 'reference' (worksharing).
    ``interpret`` selects the Pallas interpreter for the 'pallas' backend."""
    table = PALLAS if backend == "pallas" else REFERENCE
    if fn not in table:
        raise KeyError(f"kernel '{fn}' not registered for backend '{backend}'")
    if backend == "pallas":
        return functools.partial(table[fn], interpret=interpret)
    return table[fn]
