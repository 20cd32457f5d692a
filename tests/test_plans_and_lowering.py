"""Planner + lowering tests: plan fields per (arch x shape), divisibility
behavior (EP vs expert-TP), skip logic, partition-spec construction, and the
end-to-end fault-tolerant training loop with recovery determinism."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, SHAPES, ShapeCfg, cell_supported, config, \
    smoke_config
from repro.core import ir, plans
from repro.core.lower import partition_spec
from jax.sharding import PartitionSpec as P


def test_partition_spec_from_distribution():
    a = ir.DataAttr(symbol="w", distribution=(
        ir.DataDist(dim=1, axis="data"), ir.DataDist(dim=2, axis="model")))
    assert partition_spec(a, 3) == P(None, "data", "model")
    b = ir.DataAttr(symbol="t", distribution=(
        ir.DataDist(dim=0, axis="pod+data"),))
    assert partition_spec(b, 2) == P(("pod", "data"))
    assert partition_spec(ir.DataAttr(symbol="r"), 2) == P()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_plan_builds_for_all_cells(arch):
    cfg = config(arch)
    for shape in SHAPES.values():
        ok, _ = cell_supported(cfg, shape)
        if not ok:
            continue
        plan = plans.make_plan(cfg, shape)
        assert plan.batch_axes or shape.global_batch < 16
        if shape.kind == "train":
            assert plan.remat in ("none", "selective", "full")
            assert plan.microbatches >= 1
            assert plan.zero          # FSDP default
            assert plan.donate_symbol("state")
        else:
            assert plan.microbatches == 1
            if shape.kind == "decode":
                assert plan.seq_axis == "model"
                assert plan.donate_symbol("cache")


def test_moe_ep_vs_expert_tp():
    """phi3.5 (16 experts) shards experts over model (EP); grok (8 experts)
    falls through to d_ff sharding (expert-TP) — divisibility-driven."""
    phi = plans.make_plan(config("phi3.5-moe-42b-a6.6b"), SHAPES["train_4k"])
    grok = plans.make_plan(config("grok-1-314b"), SHAPES["train_4k"])
    phi_w1 = phi.spec("params/blocks/moe/w1")       # [L, E, D, F]
    grok_w1 = grok.spec("params/blocks/moe/w1")
    assert phi_w1[1] == "model", phi_w1              # EP
    assert grok_w1[1] is None and grok_w1[3] == "model", grok_w1  # expert-TP
    assert grok_w1[2] == "data"                      # FSDP on D


def test_granite_vocab_fallback():
    plan = plans.make_plan(config("granite-3-2b"), SHAPES["train_4k"])
    spec = plan.spec("params/embed")                 # vocab 49155 is odd
    assert spec[0] is None and spec[1] in ("data", "model"), spec


def test_long500k_skips():
    long = SHAPES["long_500k"]
    for arch in ARCH_IDS:
        ok, why = cell_supported(config(arch), long)
        if config(arch).sub_quadratic:
            assert ok, arch
        else:
            assert not ok and "sub-quadratic" in why, arch


def test_multipod_batch_axes():
    plan = plans.make_plan(config("tinyllama-1.1b"), SHAPES["train_4k"],
                           multi_pod=True)
    assert plan.batch_axes == ("pod", "data")
    spec = plan.spec("in/tokens")
    assert spec == P(("pod", "data"))


def test_pass_trace_records_pipeline():
    trace = []
    plans.make_plan(config("tinyllama-1.1b"), SHAPES["train_4k"], trace=trace)
    names = [t["pass"] for t in trace]
    assert names == ["normalize", "propagate_data_attrs",
                     "eliminate_redundant_sync", "fuse_sync",
                     "split_arrive_wait", "plan_memory"]
    # propagate completed data attrs for the whole state tree
    assert trace[1]["after"]["data_attrs"] > trace[1]["before"]["data_attrs"]


def test_zero_rewrite_visible_in_ir():
    prog = plans.build_program(config("tinyllama-1.1b"), SHAPES["train_4k"])
    from repro.core.passes import run_pipeline
    opt = run_pipeline(prog)
    names = [s.name for s in ir.find_all(opt, ir.SyncOp)]
    assert "reduce_scatter" in names and "all_gather" in names  # ZeRO
    assert "allreduce" not in names


def test_no_fsdp_keeps_allreduce():
    prog = plans.build_program(config("tinyllama-1.1b"), SHAPES["train_4k"],
                               fsdp=False)
    from repro.core.passes import run_pipeline
    opt = run_pipeline(prog)
    names = [s.name for s in ir.find_all(opt, ir.SyncOp)]
    assert "allreduce" in names and "reduce_scatter" not in names


def test_overlap_pass_splits_grad_reduction():
    cfg = config("tinyllama-1.1b")                   # small arch: mb > 1
    trace = []
    plan = plans.make_plan(cfg, SHAPES["train_4k"], trace=trace)
    assert plan.grad_reduce == "pipelined"
    steps = [s.step for s in plan.collectives if s.name in
             ("reduce_scatter", "all_gather", "allreduce")]
    assert "arrive-compute" in steps and "wait-release" in steps


def test_printer_renders_model_plan():
    from repro.core import printer
    from repro.core.passes import run_pipeline
    prog = run_pipeline(plans.build_program(config("tinyllama-1.1b"),
                                            SHAPES["train_4k"]))
    text = printer.to_mlir(prog)
    assert "upir.spmd" in text and "mesh(data:16 x model:16)" in text
    assert "taskloop" in text                          # microbatching
    assert "upir.sync" in text
    assert "distribute(dim(" in text                   # data distributions


def test_device_peaks_table_has_no_default():
    from repro.core.devices import PEAKS, TARGET_KIND, peaks
    from repro.launch import roofline
    v5e = peaks("TPU v5 lite")
    assert v5e is PEAKS[TARGET_KIND]
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.hbm_bytes) == \
        (197e12, 819e9, 16 * 2**30)
    # every consumer reads the one table
    assert plans.HBM_BYTES == v5e.hbm_bytes
    assert (roofline.PEAK_FLOPS, roofline.HBM_BW, roofline.LINK_BW) == \
        (v5e.flops_bf16, v5e.hbm_bw, v5e.link_bw)
    with pytest.raises(KeyError, match="no peak rates"):
        peaks("cpu")
