"""The trace reduction on event lists recorded on the chip
(``bench/probe.py``): every per-layer metric ``BENCHMARK.json`` declares for
a cell comes out finite, and every share lies in (0, 105]."""
import json
import math
import types
from pathlib import Path

import pytest

from bench import harness, peaks, tracing

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def _record(data):
    p = peaks.peaks(data["device_kind"])
    return types.SimpleNamespace(
        trace=tracing.Trace.from_json(data["trace"]),
        traced_decode=[tuple(c) for c in data["decode_costs"]],
        traced_prefill=[tuple(c) for c in data["prefill_costs"]],
        stats=data["stats"], step_ms=data["step_ms"], peaks=p,
        least_time=lambda f, b: peaks.least_time(f, b, p))


@pytest.mark.parametrize("cell", CELLS)
def test_every_declared_metric_reads_from_the_recorded_trace(cell):
    path = DATA / f"{cell}.trace.json"
    data = json.loads(path.read_text())
    assert path.stat().st_size < 1_000_000
    rec = _record(data)
    declared = [m for m in SPEC["per_layer"]
                if "workloads" not in m or cell in m["workloads"]]
    units = {m["name"]: m["unit"] for m in declared}
    got, missing = harness.read_metrics(rec, list(units), units)
    assert not missing
    for name, v in got.items():
        assert math.isfinite(v["value"]), name
        if units[name] == "%":
            assert 0.0 < v["value"] <= 105.0, (name, v)


@pytest.mark.parametrize("cell", CELLS)
def test_recorded_modules_are_in_the_role_table(cell):
    data = json.loads((DATA / f"{cell}.trace.json").read_text())
    names = {m[0] for m in data["trace"]["modules"]}
    table = tracing.roles()
    assert "jit_step" in names and table["jit_step"] == "decode"
    assert any(table.get(n) == "prefill" for n in names)


def test_breakdown_labels_idle_gaps_with_host_spans():
    tr = tracing.Trace(
        window_s=1.0,
        modules=[("jit_step", 0.0, 0.3), ("jit_pre", 0.5, 0.6)],
        busy=[(0.0, 0.3), (0.5, 0.6)],
        host=[("bench.window", 0.0, 1.0), ("bench.step", 0.25, 0.45),
              ("bench.wait_arrival", 0.6, 1.0)])
    b = tracing.breakdown(tr)
    assert b["device_ops"][0][0] == "jit_step"
    assert b["device_ops"][0][1] == pytest.approx(0.3)
    assert b["idle_gaps"][0] == ["bench.wait_arrival", pytest.approx(0.4)]
    assert b["idle_gaps"][1] == ["bench.step", pytest.approx(0.2)]
    assert tr.busy_s() == pytest.approx(0.4)
