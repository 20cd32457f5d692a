"""Seeded weights that are exact in bfloat16.

Every value is a small integer times a power of two, so the bf16 value the
program serves and the float32 value the reference computes with are the same
number, whichever program builds them. A leaf is drawn in one ``randint`` call
from ``fold_in(key, leaf index)``; the whole tree is made in one jitted call on
the device.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# kind -> (low, high, exponent or None): value = randint(low, high) * 2**e
# (None: the exponent follows the fan-in so that the std is ~1/sqrt(fan_in))
KINDS = {
    "matrix": (-127, 128, None),
    "embed": (-127, 128, -12),        # std ~0.018
    "norm": (96, 161, -7),            # 0.75 .. 1.25
}


def matrix_exponent(fan_in: int) -> int:
    # randint(-127, 127) has std ~73.3 = 2**6.2
    return -round(6.2 + 0.5 * math.log2(fan_in))


def leaf(key, shape, kind: str, fan_in: int = 0, dtype=jnp.bfloat16):
    lo, hi, e = KINDS[kind]
    if e is None:
        e = matrix_exponent(fan_in)
    ints = jax.random.randint(key, shape, lo, hi, jnp.int32)
    return (ints.astype(jnp.float32) * (2.0 ** e)).astype(dtype)


def make_tree(layout, seed: int, dtype=jnp.bfloat16):
    """Build the tree ``layout`` describes: a nested dict whose leaves are
    ``(shape, kind, fan_in)``; leaf ``i`` in sorted path order draws from
    ``fold_in(key(seed), i)``."""
    flat = sorted(_flatten(layout))

    def build(key):
        out = {}
        for i, (path, (shape, kind, fan_in)) in enumerate(flat):
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = leaf(jax.random.fold_in(key, i), shape, kind,
                                  fan_in, dtype)
        return out

    # the key is an argument, not a constant: one program serves every seed,
    # so the persistent cache holds it after a cell's first run
    return jax.jit(build)(jax.random.key(seed))


def _flatten(layout, prefix=()):
    for k, v in layout.items():
        if isinstance(v, dict):
            yield from _flatten(v, prefix + (k,))
        else:
            yield prefix + (k,), v
