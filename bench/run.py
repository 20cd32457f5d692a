#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): weights from the seed in one jitted call on the
device, the cell's engine, and a warm-up of exactly the shapes its traffic
uses. A lead-in of arrivals brings the engine to steady state; the window then
measures for ``--seconds``. ``--trace 1`` also profiles a short steady
sub-window and reports the cell's per-layer metrics; ``--trace 0`` reports its
end-to-end metrics. After the window the served tokens of a sample of finished
requests are compared with the float32 reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: every number compared with its limit.
Counts and notes go on earlier lines. Without a TPU, or with fewer chips than
the cell needs, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, tracing
    try:
        cell = harness.load_cell(args.workload)
        devs = harness.require_chips(cell.chips)
    except harness.BenchError as e:
        harness.say(f"error: {e}")
        return 2
    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    s = harness.Session(cell, args.seed, args.seconds,
                        device_kind=devs[0].device_kind, t_start=T_START,
                        counter=counter)
    print(f"setup: {s.setup_s:.3f} s, parts "
          + json.dumps({k: round(v, 3) for k, v in s.setup_parts.items()})
          + f", persistent cache {dict(counter.cache)}, warm buckets "
          f"{s.warm_buckets}, requests {len(s.arrivals)}", flush=True)
    rec = harness.serve(s, trace=bool(args.trace))
    print("notes: " + json.dumps(rec.notes), flush=True)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    names = cell.per_layer if args.trace else cell.end_to_end
    metrics, missing = harness.read_metrics(rec, names, units)
    if missing:
        seen = sorted({m[0] for m in rec.trace.modules}) if rec.trace else []
        harness.say(f"error: no events for {missing}; module names in the "
                    f"trace: {seen}")
        return 3

    stats = devs[0].memory_stats() or {}
    print("memory: " + json.dumps({k: stats.get(k) for k in (
        "peak_bytes_in_use", "bytes_in_use", "bytes_limit")}), flush=True)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    result = {"correct": False,
              "attempted": sum(1 for q in rec.requests if q["in_window"]),
              "failed": sum(1 for q in rec.requests
                            if q["in_window"] and not q["times"]),
              "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = rec.trace.busy_s()
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = tracing.breakdown(rec.trace)

    wseed = s.wseed
    s.free()
    sample = harness.sample_finished(rec, args.seed)
    gaps = harness.reference_gaps(cell, wseed, sample)
    read = harness.readings(gaps)
    print(f"compared {len(sample)} requests, "
          f"{sum(len(q['tokens']) for q in sample)} served tokens; "
          f"per request widest {[float(g.max()) for g in gaps]}, "
          f"mean {[float(g.mean()) for g in gaps]}; readings "
          + json.dumps(read), flush=True)
    checks = harness.checks(cell, rec, read)
    result["correct"] = harness.passed(checks)
    result["checks"] = checks
    for k, v in checks.items():
        harness.say(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
