#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,... --seconds <s> [--control]

For each seed, in one process: the cell's set-up and a short window at the
cell's own load, then the comparison a run makes (the widest gap of a served
token's logit below the float32 reference's best, over the same sample of
finished requests) and, with ``--control``, the control's reading on the same
prompts and served tokens: the reference computed from float8 operands, read
as the gap of the token it puts first. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    from bench import harness
    cell = harness.load_cell(args.workload)
    devs = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    units = {n: "" for n in cell.end_to_end}
    for seed in (int(x) for x in args.seeds.split(",")):
        t = time.perf_counter()
        s = harness.Session(cell, seed, args.seconds,
                            device_kind=devs[0].device_kind, counter=counter)
        rec = harness.serve(s)
        e2e, _ = harness.read_metrics(rec, cell.end_to_end, units)
        wseed = s.wseed
        s.free()
        sample = harness.sample_finished(rec, seed)
        t_ref = time.perf_counter()
        gaps = harness.reference_gaps(cell, wseed, sample)
        t_ref = time.perf_counter() - t_ref
        row = {"seed": seed, "readings": harness.readings(gaps),
               "widest": [float(g.max()) for g in gaps],
               "mean": [float(g.mean()) for g in gaps],
               "sample": [[q["bucket"], len(q["prompt"]), len(q["tokens"])]
                          for q in sample],
               "reference_s": t_ref,
               "metrics": {k: v["value"] for k, v in e2e.items()},
               "notes": rec.notes}
        if args.control:
            ctl = harness.reference_gaps(cell, wseed, sample, control=True)
            row.update(control=harness.readings(ctl),
                       control_widest=[float(g.max()) for g in ctl],
                       control_mean=[float(g.mean()) for g in ctl])
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
