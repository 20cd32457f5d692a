"""The cost model's counts against the arrays the program's own init builds,
at smoke widths."""
import dataclasses

import jax
import numpy as np

from bench import harness
from bench.tests import smoke
from repro.models import api


def _bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_param_bytes_and_layout_match_the_program():
    c = smoke.cell(smoke.DENSE)
    cfg = dataclasses.replace(harness.arch_config(c.config),
                              param_dtype="bfloat16")
    specs = api.param_specs(cfg)
    assert sum(c.family.param_bytes(c.model).values()) == _bytes(specs)
    shapes = jax.tree.map(lambda s: s.shape, specs)
    layout = jax.tree.map(lambda v: v[0], c.family.layout(c.model),
                          is_leaf=lambda v: isinstance(v, tuple))
    assert layout == shapes


def test_dense_kv_bytes_match_the_paged_pool():
    c = smoke.cell(smoke.DENSE)
    cfg = dataclasses.replace(harness.arch_config(c.config),
                              compute_dtype="bfloat16")
    pages, ps = 8, 16
    pool = api.paged_cache_specs(cfg, pages, ps)
    per_token = _bytes(pool) / ((pages + 1) * ps)
    assert c.family.kv_bytes_per_token(c.model) == per_token


def test_costs_grow_with_context_and_tokens():
    c = smoke.cell(smoke.DENSE)
    fam, m = c.family, c.model
    f1, b1 = fam.decode_cost(m, [10, 20])
    f2, b2 = fam.decode_cost(m, [10, 40])
    assert f2 > f1 and b2 > b1
    pf1, pb1 = fam.prefill_cost(m, 0, 32)
    pf2, _ = fam.prefill_cost(m, 32, 32)
    assert pf2 > pf1 > 0 and pb1 > sum(fam.param_bytes(m).values())
    # required FLOPs per decoded token are at least twice the matmul weights
    per_tok = (f1 - fam.decode_cost(m, [10])[0])
    assert per_tok >= 2 * (sum(fam.param_counts(m).values())
                           - fam.param_counts(m)["embed"]) * 0.9


def test_weights_are_exact_in_bfloat16():
    from bench import weights
    t = weights.make_tree({"a": ((64, 32), "matrix", 64),
                           "n": ((32,), "norm", 0)}, 3)
    for leaf in jax.tree.leaves(t):
        f32 = np.asarray(leaf.astype("float32"))
        assert np.array_equal(f32, np.asarray(
            leaf.astype("float32").astype("bfloat16").astype("float32")))
    again = weights.make_tree({"a": ((64, 32), "matrix", 64),
                               "n": ((32,), "norm", 0)}, 3)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(t), jax.tree.leaves(again)))
