"""Multi-pod dry-run: lower + compile every (architecture x input shape) on the
production meshes, record memory/cost/roofline evidence.

MUST be run as a module: ``PYTHONPATH=src python -m repro.launch.dryrun --all``.
``main()`` sets ``XLA_FLAGS`` to 512 placeholder host devices before its first
jax import, so they exist only inside dry-run processes: importing this module
changes no environment, and no test, benchmark or chip run ever sees them.
This is CPU-only analysis; the chip path never imports it.

Per cell this produces experiments/dryrun/<arch>__<shape>__<mesh>.json with:
  * compiled.memory_analysis()  — per-device bytes (proves the cell fits HBM);
  * compiled.cost_analysis()    — XLA's flops/bytes (loop bodies counted once);
  * roofline terms              — while-scaled flops / HBM bytes / collective
                                  bytes from the post-optimization HLO text;
  * the collective schedule     — op kind -> fabric bytes;
  * UPIR pass trace             — node statistics per pass.

``--all`` sweeps every supported cell in subprocesses (isolation: one cell's OOM
or crash cannot take down the sweep — poor-man's fault tolerance for the sweep
driver itself).
"""
import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
import traceback
from pathlib import Path

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"
HOST_DEVICES = "--xla_force_host_platform_device_count=512"


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             fsdp: bool = True, overlap: bool = True, save: bool = True,
             variant: str = "") -> dict:
    import jax
    import jax.numpy as jnp

    from ..configs import SHAPES, cell_supported, config, input_specs
    from ..core import plans
    from ..launch import roofline as rl
    from ..launch.mesh import make_production_mesh
    from ..models import api
    from ..runtime import server, trainer

    cfg = config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "kind": shape.kind,
        "variant": variant, "fsdp": fsdp, "overlap": overlap,
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        if save:
            _save(rec, variant)
        return rec

    chips = 512 if multi_pod else 256
    t0 = time.time()
    trace: list = []
    plan = plans.make_plan(cfg, shape, multi_pod=multi_pod, fsdp=fsdp,
                           overlap=overlap, trace=trace)
    mesh = make_production_mesh(multi_pod=multi_pod)
    batch_specs = input_specs(cfg, shape)

    with mesh:
        if shape.kind == "train":
            step, (sspecs, bspecs), (state_sh, batch_sh) = \
                trainer.jit_train_step(cfg, plan, mesh)
            lowered = step.lower(sspecs, batch_specs)
        elif shape.kind == "prefill":
            step, (pspecs, bspecs), (param_sh, batch_sh) = \
                server.jit_prefill_step(cfg, plan, mesh, shape)
            lowered = step.lower(pspecs, bspecs)
        else:
            step, (pspecs, cspecs, bspecs), shs = \
                server.jit_decode_step(cfg, plan, mesh, shape)
            lowered = step.lower(pspecs, cspecs, bspecs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    hlo = compiled.as_text()
    costs = rl.analyze_hlo(hlo)
    terms = rl.roofline_terms(costs, chips)
    dom = rl.dominant_term(terms)
    mf = rl.model_flops(cfg, shape)
    ideal_s = mf / chips / rl.PEAK_FLOPS
    step_s = max(terms.values())

    rec.update(
        status="ok",
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory_analysis=None if ma is None else {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_est": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        cost_analysis={"flops": ca.get("flops"),
                       "bytes_accessed": ca.get("bytes accessed")},
        roofline={
            "flops_per_device": costs.flops,
            "dot_flops_per_device": costs.dot_flops,
            "hbm_bytes_per_device": costs.hbm_bytes,
            "collective_bytes_per_device": costs.coll_bytes,
            "collective_by_kind": dict(costs.coll_by_kind),
            "collective_count": costs.coll_count,
            **{k: v for k, v in terms.items()},
            "dominant": dom,
            "model_flops": mf,
            "useful_flops_ratio": mf / max(costs.flops * chips, 1.0),
            "ideal_step_s": ideal_s,
            "roofline_fraction": ideal_s / max(step_s, 1e-12),
        },
        plan={
            "microbatches": plan.microbatches, "remat": plan.remat,
            "zero": plan.zero, "grad_reduce": plan.grad_reduce,
            "batch_axes": list(plan.batch_axes), "seq_axis": plan.seq_axis,
        },
        pass_trace=trace,
    )
    if save:
        _save(rec, variant)
    return rec


def _save(rec: dict, variant: str = ""):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    suffix = f"__{variant}" if variant else ""
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json"
    (RESULTS_DIR / name).write_text(json.dumps(rec, indent=1, default=str))


def sweep(meshes=("single", "multi"), archs=None, shapes=None,
          jobs: int = 1) -> None:
    """Run every cell in an isolated subprocess; skip ones already recorded."""
    from ..configs import ARCH_IDS, SHAPES
    archs = archs or list(ARCH_IDS)
    shapes = shapes or list(SHAPES)
    todo = []
    for mesh in meshes:
        for arch in archs:
            for shape in shapes:
                out = RESULTS_DIR / f"{arch}__{shape}__{mesh}.json"
                if out.exists():
                    print(f"[skip] {out.name} exists")
                    continue
                todo.append((arch, shape, mesh))
    print(f"{len(todo)} cells to run")
    for i, (arch, shape, mesh) in enumerate(todo):
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--mesh", mesh]
        print(f"[{i + 1}/{len(todo)}] {arch} x {shape} x {mesh} ...",
              flush=True)
        t0 = time.time()
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=3600,
                           env={**os.environ, "XLA_FLAGS": HOST_DEVICES})
        dt = time.time() - t0
        if r.returncode != 0:
            print(f"  FAILED ({dt:.0f}s):\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
            rec = {"arch": arch, "shape": shape, "mesh": mesh,
                   "status": "error", "error": r.stderr[-4000:]}
            _save(rec)
        else:
            print(f"  ok ({dt:.0f}s)")


def main():
    if "jax" in sys.modules:
        raise RuntimeError("dryrun must set XLA_FLAGS before jax is imported; "
                           "run it as `python -m repro.launch.dryrun`")
    os.environ["XLA_FLAGS"] = HOST_DEVICES
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-overlap", action="store_true")
    ap.add_argument("--variant", default="")
    args = ap.parse_args()

    if args.all:
        sweep(archs=[args.arch] if args.arch else None,
              shapes=[args.shape] if args.shape else None)
        return

    rec = run_cell(args.arch, args.shape, args.mesh == "multi",
                   fsdp=not args.no_fsdp, overlap=not args.no_overlap,
                   variant=args.variant)
    if rec["status"] == "ok":
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "compile_s",
                           "memory_analysis", "roofline", "plan")},
                         indent=1, default=str))
    else:
        print(json.dumps(rec, indent=1, default=str))


if __name__ == "__main__":
    main()
