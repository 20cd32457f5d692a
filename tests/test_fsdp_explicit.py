"""Explicit-FSDP (shard_map, manual 'data' axis) trainer: the T3 structural
fix — per-layer gradients born sharded via the AD of tiled all_gather."""


def test_fsdp_step_compiles_with_reduce_scatter(subproc):
    out = subproc("""
import jax, re
from repro.configs import ShapeCfg, smoke_config
from repro.core import plans
from repro.runtime.fsdp import make_fsdp_train_step
cfg = smoke_config("tinyllama-1.1b")
shape = ShapeCfg("t", "train", 64, 16)
plan = plans.make_plan(cfg, shape, microbatches=1)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
with mesh:
    step, (ss, bs), _ = make_fsdp_train_step(cfg, plan, mesh)
    compiled = step.lower(ss, bs).compile()
hlo = compiled.as_text()
rs = len(re.findall(r" reduce-scatter", hlo))
assert rs > 0, "per-layer grads must be reduce-scattered (born sharded)"
print("OK rs=", rs)
""", devices=8)
    assert "OK" in out


def test_fsdp_step_trains_and_matches_gspmd(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ShapeCfg, smoke_config
from repro.core import plans
from repro.data import DataConfig, ShardedLMDataset
from repro.runtime import trainer
from repro.runtime.fsdp import make_fsdp_train_step
cfg = smoke_config("tinyllama-1.1b")
shape = ShapeCfg("t", "train", 64, 16)
plan = plans.make_plan(cfg, shape, microbatches=1)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
ds = ShardedLMDataset(DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=16))
batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
with mesh:
    fstep, _, (state_sh, batch_sh) = make_fsdp_train_step(cfg, plan, mesh)
    gstep, _, _ = trainer.jit_train_step(cfg, plan, mesh)
    state = jax.device_put(trainer.init_state(cfg, jax.random.key(0)), state_sh)
    state2 = jax.device_put(trainer.init_state(cfg, jax.random.key(0)), state_sh)
    b = jax.device_put(batch, batch_sh)
    sa, ma_ = fstep(state, b)
    sb, mb_ = gstep(state2, b)
np.testing.assert_allclose(float(ma_["loss"]), float(mb_["loss"]), rtol=1e-4)
err = max(float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
          for x, y in zip(jax.tree.leaves(sa["params"]),
                          jax.tree.leaves(sb["params"])))
assert err < 5e-3, err
print("OK loss", float(ma_["loss"]), "err", err)
""", devices=8)
    assert "OK" in out
