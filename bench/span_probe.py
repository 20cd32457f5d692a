#!/usr/bin/env python3
"""Run one cell's traced window and read the engine's own spans and scopes
(``bench/engine_trace.py``) beside the cell's declared metrics.

    python3 bench/span_probe.py --workload <name> --seed <n> --seconds <s> \\
        --out <dir> [--untraced 1] [--cut 1.0]

Prints one JSON line: the cell's declared per-layer and end-to-end metrics of
the traced run, the four span readings, each decode scope's share of the
decode program's device time, the first-token latency split into the
harness's submit lag, the queue wait and the rest, the scheduler's wait
counters, the breakdown with idle gaps labelled by ``bench.*`` and
``engine.*`` spans, and the host cost of a span with no profile running.
``--untraced 1`` then serves the same seed again without the profiler and
adds its end-to-end metrics and ``host_step_ms``: the profiler's cost.

The raw profile goes to a temporary directory that is removed once read.
Writes to ``<dir>``: ``<workload>-ops.json`` (the stats the device ops of
one decode step carry, to see where a scope can be read),
``<workload>-decode.hlo.txt`` (the decode program's compiled HLO) and
``<workload>.spans.trace.json`` (the first ``--cut`` seconds of the reduced
trace with what the readings need: the data of
``bench/tests/test_engine_trace.py``). Needs the cell's chips, as
``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def _p75(values):
    from bench.stats import percentile
    return percentile(values, 75) if values else None


def _span_cost_us(n: int = 200_000) -> float:
    from repro.runtime.telemetry import span
    t = time.perf_counter()
    for _ in range(n):
        with span("engine.step"):
            pass
    return (time.perf_counter() - t) / n * 1e6


def _op_sample(raw: Path, scopes: dict, limit: int = 60) -> list:
    """Name, stats and scope of the device ops inside the first decode
    module event of the profile."""
    from jax.profiler import ProfileData

    from bench import engine_trace, tracing
    pb = sorted(raw.rglob("*.xplane.pb"))[-1]
    decode = {n for n, r in tracing.roles().items() if r == "decode"}
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if not tracing.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = [e for e in lines.get("XLA Modules", [])
                if tracing.module_name(e.name) in decode]
        if not mods:
            continue
        s, t = mods[0].start_ns, mods[0].end_ns
        for e in lines.get("XLA Ops", []):
            if s <= e.start_ns and e.end_ns <= t and len(out) < limit:
                out.append({"name": e.name[:240], "dur_ns": e.duration_ns,
                            "scope": scopes.get(engine_trace.op_name(e.name)),
                            "stats": {str(k): str(v)[:240]
                                      for k, v in e.stats}})
    return out


def _admission(rec, reqs) -> None:
    """Each request record gains ``submitted`` and ``admitted``: seconds on
    the engine's clock from the window's start, which is estimated as the
    least lag between a request's due time and its submission; and
    ``submit_ms``, the host time of its ``Engine.submit`` call."""
    origin = min(reqs[q["rid"]].t_submit - q["due"] for q in rec.requests)
    for q in rec.requests:
        r = reqs[q["rid"]]
        q["submit_ms"] = r.submit_ms
        q["submitted"] = r.t_submit - origin
        q["admitted"] = r.t_admitted - origin if r.t_admitted else None


def _span_ms(tr) -> dict:
    """Per span name: count, mean, p50 and max duration in ms."""
    import numpy as np
    out = {}
    for name in sorted({n for n, _, _ in tr.engine}):
        d = np.array([e - s for n, s, e in tr.engine if n == name]) * 1e3
        out[name] = [len(d), float(d.mean()), float(np.median(d)),
                     float(d.max())]
    return out


def _first_token_split(rec) -> dict:
    win = [q for q in rec.requests
           if q["in_window"] and q["times"] and q["admitted"] is not None]
    return {"submit_lag_p75_ms": _p75(
                [(q["submitted"] - q["due"]) * 1e3 for q in win]),
            "queue_wait_p75_ms": _p75(
                [(q["admitted"] - q["submitted"]) * 1e3 for q in win]),
            "admitted_to_first_p75_ms": _p75(
                [(q["times"][0] - q["admitted"]) * 1e3 for q in win]),
            "ttft_p75_ms": _p75(
                [(q["times"][0] - q["due"]) * 1e3 for q in win]),
            "submit_call_p75_ms": _p75([q["submit_ms"] for q in win]),
            "requests": len(win)}


def probe(cell, seed: int, seconds: float, out: Path, device_kind: str,
          untraced: bool = False, cut: float = 1.0) -> dict:
    from bench import engine_trace, harness, tracing
    out.mkdir(parents=True, exist_ok=True)
    raw = Path(tempfile.mkdtemp(prefix="span-probe-"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    for n in cell.per_layer + cell.end_to_end:
        units.setdefault(n, "")

    s = harness.Session(cell, seed, seconds, device_kind=device_kind)
    eng, reqs, seen = s.engine, {}, []
    submit, decode = eng.submit, eng._decode

    def record_submit(spec):
        t = time.perf_counter()
        r = submit(spec)
        r.submit_ms = (time.perf_counter() - t) * 1e3
        reqs[r.rid] = r
        return r

    def record_decode(*args):
        if not seen:
            seen.append(engine_trace.arg_specs(args))
        return decode(*args)

    eng.submit, eng._decode = record_submit, record_decode
    rec = harness.serve(s, trace=True, trace_dir=str(raw))
    hlo = decode.lower(*seen[0]).compile().as_text() if seen else ""
    tr = engine_trace.load(str(raw), hlo)
    rec.trace = tr
    _admission(rec, reqs)

    layer, missing = harness.read_metrics(rec, cell.per_layer, units)
    e2e, _ = harness.read_metrics(rec, cell.end_to_end, units)
    per = engine_trace.scope_seconds(tr)
    dec_s = sum(e - b for _, b, e in tr.of_role("decode"))
    steps = [h for h in tr.host if h[0] == "bench.step"
             and h[1] >= 0.0 and h[2] <= tr.window_s]
    result = {
        "per_layer": layer, "missing": missing, "end_to_end": e2e,
        "spans": {
            "host_self_ms": engine_trace.host_self_ms(tr),
            "host_wait_ms": engine_trace.host_wait_ms(tr),
            "paged_attention_roofline":
                engine_trace.paged_attention_roofline(rec),
            "queue_wait_p75_ms":
                engine_trace.queue_wait_p75_ms(rec.requests)},
        "scope_share_of_decode": {
            sc: sum(p[sc] for p in per) / dec_s if dec_s else None
            for sc in engine_trace.SCOPES},
        "decode_events": len(per), "decode_s": dec_s,
        "hlo_op_scopes": len(engine_trace.op_scopes(hlo)),
        "traced_step_ms": (1e3 * sum(e - b for _, b, e in steps)
                           / len(steps) if steps else None),
        "engine_spans_per_step": (len(tr.engine) / len(steps)
                                  if steps else None),
        "span_ms": _span_ms(tr), "scoped_ops": len(tr.scoped),
        "first_token": _first_token_split(rec),
        "counters": {k: rec.stats.get(k) for k in (
            "admit_wait_slots", "admit_wait_pages", "batch_occupancy",
            "decode_steps", "peak_pages", "evictions")},
        "notes": rec.notes,
        "breakdown": tracing.breakdown(tr),
        "window_s": tr.window_s, "busy_s": tr.busy_s(),
        "span_cost_us_profiler_off": _span_cost_us(),
    }
    (out / f"{cell.name}-ops.json").write_text(
        json.dumps(_op_sample(raw, engine_trace.op_scopes(hlo)), indent=1))
    (out / f"{cell.name}-decode.hlo.txt").write_text(hlo)
    shutil.rmtree(raw, ignore_errors=True)
    small = tr.cut(cut)
    nd, npf = len(small.of_role("decode")), len(small.of_role("prefill"))
    (out / f"{cell.name}.spans.trace.json").write_text(json.dumps({
        "trace": small.to_json(), "model": cell.model,
        "family": cell.config["family"], "device_kind": device_kind,
        "decode_costs": rec.traced_decode[:nd],
        "prefill_costs": rec.traced_prefill[:npf],
        "requests": [{k: q[k] for k in ("rid", "due", "in_window",
                                        "submitted", "admitted")}
                     for q in rec.requests]}))

    if untraced:
        params = s.params
        s.free()
        s2 = harness.Session(cell, seed, seconds, device_kind=device_kind,
                             params=params)
        rec2 = harness.serve(s2)
        result["untraced"] = {
            "end_to_end": harness.read_metrics(rec2, cell.end_to_end,
                                               units)[0],
            "host_step_ms": harness.read_metrics(
                rec2, ["host_step_ms"], units)[0]["host_step_ms"]["value"],
            "counters": {k: rec2.stats.get(k) for k in (
                "admit_wait_slots", "admit_wait_pages")}}
        s2.free()
    else:
        s.free()
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--untraced", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cut", type=float, default=1.0)
    args = ap.parse_args()
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        devs = harness.require_chips(cell.chips)
    except harness.BenchError as e:
        harness.say(f"error: {e}")
        return 2
    harness.enable_compile_cache()
    result = probe(cell, args.seed, args.seconds, Path(args.out),
                   devs[0].device_kind, untraced=bool(args.untraced),
                   cut=args.cut)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
