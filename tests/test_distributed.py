"""Multi-device tests (subprocess with XLA host devices): C2 backend
equivalence, pipelined-vs-post schedules, compression, GSPMD sharded training,
and pipeline parallelism via collective_permute."""
import pytest

COMMON = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import smoke_config, ShapeCfg
from repro.core import plans
from repro.data import DataConfig, ShardedLMDataset
from repro.runtime import trainer
cfg = smoke_config("tinyllama-1.1b")
shape = ShapeCfg("smoke", "train", 32, 8)
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
plan = plans.make_plan(cfg, shape)
state = trainer.init_state(cfg, jax.random.key(0))
ds = ShardedLMDataset(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8))
batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
rep = NamedSharding(mesh, P()); dsh = NamedSharding(mesh, P("data"))
"""


def test_gspmd_equals_explicit_backend(subproc):
    subproc(COMMON + """
from repro.runtime.explicit import make_explicit_train_step
from repro.runtime.compression import init_residual
gs = jax.jit(trainer.make_train_step(cfg, plan),
             in_shardings=(jax.tree.map(lambda _: rep, state),
                           jax.tree.map(lambda _: dsh, batch)))
st_a, m_a = gs(state, batch)
ex = make_explicit_train_step(cfg, plan, mesh)
st_b, m_b, _ = ex(state, batch, init_residual(state["params"]))
np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]), rtol=1e-5)
err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32))))
          for a, b in zip(jax.tree.leaves(st_a["params"]),
                          jax.tree.leaves(st_b["params"])))
assert err < 1e-4, err
print("OK")
""")


def test_pipelined_equals_post_schedule(subproc):
    """arrive/wait split (overlap pass) is numerically identical to the
    synchronous schedule — the paper's two-step unification claim."""
    subproc(COMMON + """
import dataclasses
from repro.runtime.explicit import make_explicit_train_step
from repro.runtime.compression import init_residual
# per-shard batch of 4 so a 4-way microbatch split is possible
ds = ShardedLMDataset(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=32))
batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
res = init_residual(state["params"])
plan_pipe = dataclasses.replace(plan, grad_reduce="pipelined", microbatches=4)
plan_post = dataclasses.replace(plan, grad_reduce="post", microbatches=4)
a = make_explicit_train_step(cfg, plan_pipe, mesh)(state, batch, res)
b = make_explicit_train_step(cfg, plan_post, mesh)(state, batch, res)
np.testing.assert_allclose(float(a[1]["loss"]), float(b[1]["loss"]), rtol=1e-5)
err = max(float(jnp.max(jnp.abs(x.astype(jnp.float32)-y.astype(jnp.float32))))
          for x, y in zip(jax.tree.leaves(a[0]["params"]),
                          jax.tree.leaves(b[0]["params"])))
assert err < 1e-4, err
print("OK")
""")


def test_compressed_reduction_close(subproc):
    subproc(COMMON + """
import dataclasses
from repro.runtime.explicit import make_explicit_train_step
from repro.runtime.compression import init_residual
res = init_residual(state["params"])
plan_post = dataclasses.replace(plan, grad_reduce="post", microbatches=1)
plan_c = dataclasses.replace(plan_post, compression="int8")
a = make_explicit_train_step(cfg, plan_post, mesh)(state, batch, res)
b = make_explicit_train_step(cfg, plan_c, mesh)(state, batch, res)
# int8-compressed reduction perturbs the step only slightly
np.testing.assert_allclose(float(a[1]["loss"]), float(b[1]["loss"]), rtol=1e-4)
rel = [float(jnp.mean(jnp.abs(x - y)) / (jnp.mean(jnp.abs(x)) + 1e-9))
       for x, y in zip(jax.tree.leaves(a[0]["params"]),
                       jax.tree.leaves(b[0]["params"]))]
assert max(rel) < 0.05, max(rel)
print("OK")
""")


def test_gspmd_2d_mesh_train_and_loss_decreases(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config, ShapeCfg
from repro.core import plans
from repro.data import DataConfig, ShardedLMDataset
from repro.runtime import trainer
cfg = smoke_config("tinyllama-1.1b")
shape = ShapeCfg("smoke", "train", 32, 8)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
plan = plans.make_plan(cfg, shape)
with mesh:
    step, (sspecs, bspecs), (state_sh, batch_sh) = \\
        trainer.jit_train_step(cfg, plan, mesh)
    state = trainer.init_state(cfg, jax.random.key(0))
    state = jax.device_put(state, state_sh)
    ds = ShardedLMDataset(DataConfig(vocab=cfg.vocab, seq_len=32,
                                     global_batch=8))
    losses = []
    for i in range(8):
        batch = jax.device_put({k: jnp.asarray(v)
                                for k, v in ds.batch_at(i).items()}, batch_sh)
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
assert losses[-1] < losses[0], losses
print("OK", losses[0], losses[-1])
""", devices=8)


def test_pipeline_parallel_ppermute(subproc):
    """UPIR task-parallel stages: GPipe-style pipeline over collective_permute
    matches the sequential model (PP as upir.task with depend edges)."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("stage",))
L, D, B, MB = 4, 16, 8, 4     # 4 stages, 4 microbatches
key = jax.random.key(0)
Ws = jax.random.normal(key, (L, D, D)) * 0.3

def seq_model(x):
    for l in range(L):
        x = jnp.tanh(x @ Ws[l])
    return x

def stage_fn(w, x):
    return jnp.tanh(x @ w[0])

def pipelined(w_stage, x_mb):
    # w_stage: [1,D,D] per stage; x_mb: [MB//? ...] microbatches on stage 0
    def step(carry, _):
        buf, out, t = carry
        y = stage_fn(w_stage, buf)
        buf = jax.lax.ppermute(y, "stage",
                               [(i, (i + 1) % 4) for i in range(4)])
        idx = t - 3
        out = jax.lax.cond(idx >= 0,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, jax.lax.ppermute(y, "stage", [(3, 0)]), jnp.maximum(idx, 0), 0),
            lambda o: o, out)
        return (buf, out, t + 1), None
    # feed microbatches: steps = MB + L - 1
    xs = x_mb  # [MB, B//MB, D] resident on stage 0
    def run(xs):
        out = jnp.zeros_like(xs)
        buf = jnp.zeros_like(xs[0])
        t = 0
        for m in range(MB + L - 1):
            inject = m < MB
            stage_id = jax.lax.axis_index("stage")
            cur = jnp.where((stage_id == 0) & inject,
                            xs[jnp.minimum(m, MB - 1)], buf)
            y = stage_fn(w_stage, cur)
            nxt = jax.lax.ppermute(y, "stage",
                                   [(i, i + 1) for i in range(3)])
            done = jax.lax.ppermute(y, "stage", [(3, 0)])
            idx = m - (L - 1)
            out = jnp.where(idx >= 0,
                            jax.lax.dynamic_update_index_in_dim(
                                out, done, jnp.maximum(idx, 0), 0), out)
            buf = nxt
        return out
    return run(xs)

x = jax.random.normal(jax.random.key(1), (B, D)) * 0.5
x_mb = x.reshape(MB, B // MB, D)
f = jax.shard_map(pipelined, mesh=mesh, in_specs=(P("stage"), P()),
                  out_specs=P(), check_vma=False)
out = f(Ws.reshape(4, 1, D, D), x_mb)
ref = seq_model(x).reshape(MB, B // MB, D)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
print("OK")
""", devices=4)
