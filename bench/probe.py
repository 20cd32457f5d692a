#!/usr/bin/env python3
"""Look at one chip trace by hand, and record the event list the reduction
tests read.

    python3 bench/probe.py --workload <name> --seed <n> --seconds <s> --out <dir>

Runs the cell's traced window, then writes to ``<dir>``: ``planes.json`` (every
plane and line of the raw profile with its event count and the commonest event
names), ``<workload>.trace.json`` (the reduced trace of the sub-window's first
second with the costs of the dispatches it holds, the data of
``bench/tests/test_reduction.py``) and the metrics the readers give.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cut", type=float, default=1.0)
    args = ap.parse_args()
    from bench import harness, tracing
    out = Path(args.out)
    raw = out / f"{args.workload}-raw"
    raw.mkdir(parents=True, exist_ok=True)
    cell = harness.load_cell(args.workload)
    devs = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    s = harness.Session(cell, args.seed, args.seconds,
                        device_kind=devs[0].device_kind)
    rec = harness.serve(s, trace=True, trace_dir=str(raw))
    from jax.profiler import ProfileData
    pb = sorted(raw.rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(pb))
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            sample = [{"name": e.name, "start_ns": e.start_ns,
                       "dur_ns": e.duration_ns,
                       "stats": [[str(k), str(v)] for k, v in e.stats][:8]}
                      for e in evs[:3]]
            lines.append({"name": line.name, "events": len(evs),
                          "top": names.most_common(12), "sample": sample})
        planes.append({"name": plane.name, "lines": lines})
    (out / f"{args.workload}-planes.json").write_text(json.dumps(planes, indent=1))
    tr = rec.trace.cut(args.cut)
    nd = len(tr.of_role("decode"))
    npf = len(tr.of_role("prefill"))
    data = {"trace": tr.to_json(), "model": cell.model,
            "family": cell.config["family"], "device_kind": devs[0].device_kind,
            "decode_costs": rec.traced_decode[:nd],
            "prefill_costs": rec.traced_prefill[:npf],
            "stats": {"batch_occupancy": rec.stats.get("batch_occupancy"),
                      "decode_steps": rec.stats.get("decode_steps")},
            "step_ms": rec.step_ms[:50]}
    (out / f"{args.workload}.trace.json").write_text(json.dumps(data))
    units = {n: "" for n in cell.per_layer + cell.end_to_end}
    try:
        full, missing = harness.read_metrics(rec, cell.per_layer, units)
    except ValueError as e:            # a reader that found the trace at odds
        full, missing = {"error": str(e)}, []
    e2e, _ = harness.read_metrics(rec, cell.end_to_end, units)
    summary = {"per_layer": full, "missing": missing, "end_to_end": e2e,
               "notes": rec.notes, "modules": sorted({m[0] for m in rec.trace.modules}),
               "breakdown": tracing.breakdown(rec.trace),
               "window_s": rec.trace.window_s, "busy_s": rec.trace.busy_s(),
               "n_decode_events": len(rec.trace.of_role("decode")),
               "n_decode_costs": len(rec.traced_decode),
               "n_prefill_events": len(rec.trace.of_role("prefill")),
               "n_prefill_costs": len(rec.traced_prefill),
               "memory": devs[0].memory_stats().get("peak_bytes_in_use")}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
