#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: the highest mean rate at which the
backlog at the window's end is no longer than at its start.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> --rates 2,3,4,5

Runs the cell's traffic mix at each rate in one process (weights made once)
and prints one JSON line per rate: the requests waiting for a first token at
the window's start and end, the end-to-end metrics and the batch occupancy.
The traffic file then holds 0.8 x knee as its fixed ``rate``.
"""
from __future__ import annotations

import argparse
import copy
import gc
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
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    from bench import harness
    cell = harness.load_cell(args.workload)
    devs = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    units = {n: "" for n in cell.end_to_end}
    params = None
    for rate in (float(x) for x in args.rates.split(",")):
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"]["rate"] = rate
        s = harness.Session(cell, args.seed, args.seconds, mix=mix,
                            params=params, device_kind=devs[0].device_kind,
                            counter=counter)
        params = s.params
        rec = harness.serve(s, tail=False)
        e2e, _ = harness.read_metrics(rec, cell.end_to_end, units)
        print(json.dumps({"rate": rate,
                          "queue_at_start": rec.notes["queue_at_start"],
                          "queue_at_end": rec.notes["queue_at_end"],
                          "batch_occupancy": rec.stats.get("batch_occupancy"),
                          "evictions": rec.stats.get("evictions"),
                          "tail_s": rec.notes["tail_s"],
                          "metrics": {k: v["value"] for k, v in e2e.items()}}),
              flush=True)
        s.engine = None
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
