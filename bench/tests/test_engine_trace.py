"""The engine's spans and scopes in a trace (``bench/engine_trace.py``): the
readings on hand-made traces, on a trace recorded on the chip, and on a
smoke run on the CPU; the existing reduction reads as before."""
import json
import types
from pathlib import Path

import pytest

from bench import engine_trace, harness, peaks, span_probe, stats, tracing
from bench.tests import smoke

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
RECORDED = DATA / "granite-chat.spans.trace.json"


def _hand_made():
    """Two steps; the first dispatches for 0.2 s and reads back for 0.1 s,
    the second only dispatches for 0.1 s. A submit reads back a key while
    the device idles."""
    return engine_trace.SpanTrace(
        window_s=2.0,
        modules=[("jit_step", 0.0, 0.5), ("jit_step", 0.6, 1.2)],
        busy=[(0.0, 0.5), (0.6, 1.2)],
        host=[("bench.window", 0.0, 2.0), ("bench.submit", 0.45, 0.65),
              ("bench.step", 0.0, 0.4), ("bench.step", 0.7, 1.0),
              ("bench.wait_arrival", 1.2, 2.0)],
        engine=[("engine.step", 0.0, 0.4), ("engine.decode", 0.1, 0.4),
                ("engine.dispatch", 0.1, 0.3),
                ("engine.readback", 0.3, 0.4),
                ("engine.submit", 0.45, 0.65),
                ("engine.readback", 0.5, 0.6),
                ("engine.step", 0.7, 1.0), ("engine.dispatch", 0.8, 0.9)],
        scoped=[("attention", 0.0, 0.3), ("mlp", 0.3, 0.4),
                ("attention", 0.6, 0.9), ("attention", 0.8, 1.0),
                ("sample", 1.1, 1.2), ("attention", 1.3, 1.4)])


def test_host_readings_split_each_step():
    tr = _hand_made()
    assert engine_trace.host_wait_ms(tr) == pytest.approx(
        1e3 * (0.3 + 0.1) / 2)
    assert engine_trace.host_self_ms(tr) == pytest.approx(
        1e3 * (0.1 + 0.2) / 2)


def test_scopes_are_summed_per_decode_event():
    per = engine_trace.scope_seconds(_hand_made())
    assert per[0]["attention"] == pytest.approx(0.3)
    assert per[0]["mlp"] == pytest.approx(0.1)
    assert per[1]["attention"] == pytest.approx(0.4)   # overlap counted once
    assert per[1]["sample"] == pytest.approx(0.1)
    assert per[1]["kv_write"] == 0.0


def test_idle_gaps_name_the_engine_span():
    b = tracing.breakdown(_hand_made())
    assert b["idle_gaps"] == [
        ["bench.wait_arrival", pytest.approx(0.8)],
        ["bench.submit/engine.readback", pytest.approx(0.1)]]


def test_attention_roofline_from_the_dispatched_cost():
    m = smoke.DENSE["model"]
    from bench.families import dense
    ctx = [[5, 9], [7, 3, 4]]
    tr = _hand_made()
    p = peaks.peaks("TPU v5 lite")
    rec = types.SimpleNamespace(
        trace=tr, traced_decode=[dense.decode_cost(m, c) for c in ctx],
        cell=types.SimpleNamespace(model=m),
        least_time=lambda f, b: peaks.least_time(f, b, p))
    assert [engine_trace.live_positions(m, c) for c in rec.traced_decode] \
        == [14, 14]
    least = sum(peaks.least_time(*engine_trace.attention_cost(m, c), p)
                for c in ctx)
    assert engine_trace.paged_attention_roofline(rec) == pytest.approx(
        100.0 * least / 0.7)


def test_queue_wait_reads_requests_due_in_the_window():
    reqs = [{"in_window": True, "submitted": 1.0, "admitted": 1.0 + w}
            for w in (0.1, 0.2, 0.3, 0.4, 0.5)]
    reqs.append({"in_window": False, "submitted": 0.0, "admitted": 9.0})
    assert engine_trace.queue_wait_p75_ms(reqs) == pytest.approx(400.0)
    assert engine_trace.queue_wait_p75_ms(reqs[-1:]) is None


def test_json_round_trip_and_cut():
    tr = _hand_made()
    back = engine_trace.SpanTrace.from_json(json.loads(json.dumps(
        tr.to_json())))
    assert back.engine == tr.engine and back.scoped == tr.scoped
    short = tr.cut(0.5)
    assert all(e <= 0.5 for _, _, e in short.engine + short.scoped)
    assert engine_trace.host_wait_ms(short) == pytest.approx(300.0)


def test_the_plain_trace_reads_as_before():
    """The trace the harness records today: loaded as a ``SpanTrace`` it
    has no engine spans or scopes, the new readings find nothing, and every
    declared metric and idle-gap label read exactly as from a ``Trace``."""
    data = json.loads((DATA / "granite-chat.trace.json").read_text())
    plain = tracing.Trace.from_json(data["trace"])
    spans = engine_trace.SpanTrace.from_json(data["trace"])
    assert spans.engine == [] and spans.scoped == []
    assert tracing.breakdown(spans) == tracing.breakdown(plain)
    assert engine_trace.host_self_ms(plain) is None
    assert engine_trace.host_wait_ms(spans) is None
    names = [m["name"] for m in SPEC["per_layer"]
             if "granite-chat" in m.get("workloads", ["granite-chat"])]
    units = {n: "" for n in names}
    p = peaks.peaks(data["device_kind"])
    read = {}
    for tr in (plain, spans):
        rec = types.SimpleNamespace(
            trace=tr, traced_decode=[tuple(c) for c in data["decode_costs"]],
            traced_prefill=[tuple(c) for c in data["prefill_costs"]],
            stats=data["stats"], step_ms=data["step_ms"], peaks=p,
            cell=types.SimpleNamespace(model=data["model"]),
            least_time=lambda f, b: peaks.least_time(f, b, p))
        got, missing = harness.read_metrics(rec, names, units)
        assert not missing
        read[type(tr).__name__] = got
        assert engine_trace.paged_attention_roofline(rec) is None
    assert read["Trace"] == read["SpanTrace"]


def test_op_scopes_find_every_scope_in_the_decode_program():
    from repro.runtime.engine import Engine
    c = smoke.cell(smoke.DENSE)
    eng = Engine(harness.arch_config(c.config), harness.engine_config(
        c.config))
    seen = []
    decode = eng._decode

    def record(*args):
        if not seen:
            seen.append((decode.lower(*args).as_text(),
                         engine_trace.arg_specs(args)))
        return decode(*args)

    eng._decode = record
    from repro.runtime.engine import RequestSpec
    eng.submit(RequestSpec(prompt=(1, 2, 3), max_new_tokens=2))
    eng.step()
    text, specs = seen[0]
    # the stand-ins lower to the text the call itself lowered to, so the
    # compiled instruction names are those of the executable that ran
    assert decode.lower(*specs).as_text() == text
    scopes = engine_trace.op_scopes(decode.lower(*specs).compile().as_text())
    assert set(engine_trace.SCOPES) <= set(scopes.values())


def test_op_names_and_scopes_of_tpu_style_text():
    """A TPU trace names an op event by its whole instruction; a fusion
    whose own metadata lies in no scope takes the scope it fuses."""
    assert engine_trace.op_name("%fusion.161 = (s32[32]{0:T(128)}) fusion("
                                "s32[32,1]{0,1} %tokens.1), kind=kLoop, "
                                "calls=%fused_computation.261") == "fusion.161"
    assert engine_trace.op_name("copy.6") == "copy.6"
    hlo = "\n".join([
        "%fused_computation.261 (param_0: s32[32]) -> s32[32] {",
        "  %param_0 = s32[32]{0} parameter(0)",
        '  ROOT %add.1 = s32[32]{0} add(%param_0, %param_0), '
        'metadata={op_name="jit(step)/while/body/attention/add"}',
        "}",
        "ENTRY %main.9 (tokens.1: s32[32]) -> s32[32] {",
        "  %tokens.1 = s32[32]{0} parameter(0)",
        "  ROOT %fusion.161 = s32[32]{0} fusion(s32[32]{0} %tokens.1), "
        'kind=kLoop, calls=%fused_computation.261, metadata={op_name='
        '"jit(step)/while/body/squeeze"}',
        '  %copy.6 = s32[32]{0} copy(%tokens.1), metadata={op_name='
        '"jit(step)/sample/copy" source_file="x.py"}',
        "}"])
    scopes = engine_trace.op_scopes(hlo)
    assert scopes == {"add.1": "attention", "fusion.161": "attention",
                      "copy.6": "sample"}


def test_the_probe_reads_spans_of_a_smoke_run(tmp_path):
    """The probe end to end on the CPU: no device plane, so no module or
    scope, but the engine's spans, the admission stamps and the counters."""
    c = smoke.cell(smoke.DENSE)
    r = span_probe.probe(c, 2**33 + 5, 2.5, tmp_path, "TPU v5 lite", cut=0.5)
    sp = r["spans"]
    assert sp["host_self_ms"] > 0 and sp["host_wait_ms"] > 0
    assert sp["queue_wait_p75_ms"] >= 0
    assert sp["paged_attention_roofline"] is None
    assert r["counters"]["admit_wait_pages"] == 0
    assert r["hlo_op_scopes"] > 0
    data = json.loads((tmp_path / "dense-smoke.spans.trace.json").read_text())
    assert data["trace"]["engine"]
    assert all(q["admitted"] >= q["submitted"] for q in data["requests"]
               if q["admitted"] is not None)


def test_readings_of_the_recorded_chip_trace():
    data = json.loads(RECORDED.read_text())
    assert RECORDED.stat().st_size < 1_000_000
    tr = engine_trace.SpanTrace.from_json(data["trace"])
    p = peaks.peaks(data["device_kind"])
    m = data["model"]
    costs = [tuple(c) for c in data["decode_costs"]]
    rec = types.SimpleNamespace(
        trace=tr, traced_decode=costs, cell=types.SimpleNamespace(model=m),
        least_time=lambda f, b: peaks.least_time(f, b, p))
    # the same quantities, computed here step by step
    steps = [(s, e) for n, s, e in tr.engine if n == "engine.step"
             and s >= 0.0 and e <= tr.window_s]
    assert steps
    waits = [stats.union_length(
        (max(a, s), min(b, e)) for n, a, b in tr.engine
        if n in ("engine.dispatch", "engine.readback") and a < e and b > s)
        for s, e in steps]
    assert engine_trace.host_wait_ms(tr) == pytest.approx(
        1e3 * sum(waits) / len(steps))
    assert engine_trace.host_self_ms(tr) == pytest.approx(
        1e3 * sum(e - s - w for (s, e), w in zip(steps, waits)) / len(steps))
    decode = tr.of_role("decode")
    assert decode and tr.scoped
    attn = sum(stats.union_length((a, b) for n, a, b in tr.scoped
                                  if n == "attention" and s <= a and b <= e)
               for _, s, e in decode)
    kv = 2 * m["n_layers"] * m["n_kv_heads"] * (
        m.get("head_dim") or m["d_model"] // m["n_heads"]) * 2
    weights = costs[0][1] - kv * engine_trace.live_positions(m, costs[0])
    least = 0.0
    for f, b in costs[:len(decode)]:
        live = (b - weights) / kv
        least += peaks.least_time(
            4 * m["n_layers"] * m["n_heads"] * (
                m.get("head_dim") or m["d_model"] // m["n_heads"]) * live,
            kv * live, p)
    got = engine_trace.paged_attention_roofline(rec)
    assert got == pytest.approx(100.0 * least / attn)
    assert 0.0 < got <= 105.0
    assert engine_trace.queue_wait_p75_ms(data["requests"]) >= 0.0
