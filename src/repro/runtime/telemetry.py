"""Zero-sync request-lifecycle telemetry for the serving engine.

``EngineStats`` is aggregate counters only — it can say *how many* requests
completed, but not *why this request's TTFT was 300 ms*. This module adds the
missing per-request observability as three host-side pieces behind one
:class:`Telemetry` facade:

* **Lifecycle events** — every request produces an ordered event trace
  (``submitted -> admitted -> prefill_chunk*N -> first_token -> finished``,
  plus ``evicted / recycled / preempted / quarantined / retried / cow /
  prefix_hit / shed / failed / spilled / paged_in / kv_transfer`` from the
  paging, scheduling, fault, speculative, and tiered-KV layers), stamped
  with monotonic host timestamps
  (``time.perf_counter``) into a bounded ring buffer — steady-state memory is
  O(``max_events``), and overflow is counted, never raised.
* **Metric registry** — fixed-bucket histograms (queue delay, and the host
  dispatch times of the first token, of each token, of a prefill chunk and of
  a step: ``HISTOGRAM_HELP``) plus counters and gauges, summarized as
  p50/p95/p99 in ``EngineStats.telemetry`` and exportable as Prometheus
  text exposition (:meth:`Telemetry.to_prometheus_text`).
* **Trace export** — :meth:`Telemetry.to_chrome_trace` renders the event ring
  as Chrome ``trace_event`` JSON (one track per decode slot plus queue /
  allocator / scheduler tracks), viewable in Perfetto or ``chrome://tracing``.

Separately, :func:`span` marks the engine's own work (``SPAN_NAMES``) on the
``jax.profiler`` clock. Spans are always on and cost well under a
microsecond each while no profile is being taken; a profile shows them in
the same trace as the device ops they launch, which the event ring, on its
own ``perf_counter`` clock, cannot.

The contract that makes this safe to leave on in production: **no device
syncs**. Every emission is a host timestamp + a deque append; device-side
values (EOS, poisoned masks, accepted-draft counts) ride the engine's
*existing* poll cadence. Telemetry-on token streams are bitwise identical to
telemetry-off streams (tested across dense/paged/chunked/spec/prefix
configs), and a telemetry-enabled engine fingerprints apart in the PlanCache
via the ``mm(traced)`` annotation + ``upir.trace_emit`` op that
``core.plans.build_program(traced=True)`` renders into the program text.

Timing caveat: the hot loop is asynchronous — decode steps are *dispatched*,
not awaited — so the TTFT, ITL, prefill-chunk and step histograms measure
host dispatch, not when a token exists on the device or reaches a user. Under
``sync_per_step`` decode (and at natural sync points like EOS polls and run
end) dispatch cadence converges to device latency; either way the numbers
are deterministic in *count* and comparable run-to-run.
"""
from __future__ import annotations

import json
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Deque, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

# Fixed histogram bucket upper bounds, in milliseconds. Fixed (rather than
# adaptive) buckets keep observation O(1), make two runs' summaries directly
# comparable, and render into Prometheus ``le`` labels unchanged.
LATENCY_BUCKETS_MS: Tuple[float, ...] = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
    250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0)

# The histogram registry is fixed at construction — every engine exposes the
# same metric names, populated or not, so dashboards never chase keys.
# Only ``queue_delay_ms`` is a latency a user feels. The others time host
# dispatch: the hot loop does not wait for the device, so they read how fast
# the host issues work, not when a token exists (``sync_per_step`` aside).
HISTOGRAM_HELP: Dict[str, str] = {
    "ttft_ms": "submit to the step that dispatched the first token "
               "(host dispatch, not token delivery)",
    "itl_ms": "host wall time of a decode step per emitted token "
              "(dispatch cadence, not token delivery)",
    "queue_delay_ms": "submit to slot admission",
    "prefill_chunk_ms": "host time to dispatch one prefill chunk "
                        "(not its device time)",
    "step_ms": "host wall time of one engine step (dispatch, not device "
               "time)",
}
HISTOGRAM_NAMES: Tuple[str, ...] = tuple(HISTOGRAM_HELP)

# Host spans the engine opens on the profiler's clock (``span``), so a
# ``jax.profiler`` trace shows them beside the device ops they launch. The
# two leaf kinds mark where the host can block: everything else a step does
# is its own Python work. Emission sites after each name.
SPAN_NAMES: Tuple[str, ...] = (
    "engine.step",      # Engine.step: one whole iteration
    "engine.submit",    # Engine.submit: validation, PRNG key, enqueue
    "engine.admit",     # Engine._admit_into_free_slots / _admit_paged
    "engine.prefill",   # _prefill_tick, one-shot and suffix prefill
    "engine.pages",     # _ensure_pages, _device_page_table
    "engine.decode",    # Engine.step's decode block, finishes and polls
    "engine.dispatch",  # a call into a compiled program, uploads included
    "engine.readback",  # a device-to-host read
)


def span(name: str) -> TraceAnnotation:
    """A host span named from ``SPAN_NAMES``, recorded when a ``jax.profiler``
    trace is running and near free otherwise. It takes no arguments: they
    would cost time on every call and be encoded into the event's name."""
    return TraceAnnotation(name)


# Lifecycle event vocabulary (documented; emission sites in parentheses).
EVENT_NAMES: Tuple[str, ...] = (
    "submitted",      # engine._submit: request entered the queue
    "rejected",       # engine._reject: bounded queue overflow
    "admitted",       # engine._mark_admitted: request bound to a slot
    "recycled",       # engine._mark_admitted: slot reused without rebuild
    "prefill_chunk",  # engine._prefill_tick: one chunked-prefill dispatch
    "first_token",    # first decode token emitted (TTFT stamp)
    "finished",       # engine._finish: terminal DONE
    "failed",         # faults.note_failure: terminal FAILED
    "evicted",        # engine._evict_victim: pages reclaimed, requeued
    "preempted",      # scheduling.note_preemption: policy chose a victim
    "quarantined",    # faults.note_quarantine: slot poisoned/unwound
    "retried",        # faults.note_retry: quarantined request requeued
    "cow",            # engine._cow_tick: copy-on-write page duplication
    "prefix_hit",     # engine._admit_paged: prompt prefix pages aliased
    "shed",           # engine._shed_deadlines: dropped before admission
    "draft_prefill",  # speculative.prefill_slot: draft cache built
    "spilled",        # engine._reclaim_pages: cold prefix page -> host tier
    "paged_in",       # engine._prefix_probe: host page uploaded on a hit
    "kv_transfer",    # engine._admit_paged/_prefill_tick: prefill->decode
)


@dataclass(frozen=True)
class Event:
    """One lifecycle event: monotonic host timestamp + identity + payload.

    ``data`` is a canonically sorted tuple of ``(key, value)`` pairs so
    events are hashable and two runs' events compare field-for-field.
    """

    ts: float
    name: str
    rid: int = -1
    slot: int = -1
    data: Tuple[Tuple[str, Any], ...] = ()

    def normalized(self) -> Tuple[str, int, int, Tuple[Tuple[str, Any], ...]]:
        """The event minus its timestamp — what determinism tests compare."""
        return (self.name, self.rid, self.slot, self.data)


class Histogram:
    """Fixed-bucket histogram with O(1) observe and percentile summaries.

    Percentiles are bucket upper bounds (the standard Prometheus
    ``histogram_quantile`` semantics); the overflow bucket reports the
    observed max so a pathological tail is never silently clamped.
    """

    __slots__ = ("name", "bounds", "counts", "count", "total", "vmax")

    def __init__(self, name: str,
                 bounds: Tuple[float, ...] = LATENCY_BUCKETS_MS):
        self.name = name
        self.bounds = tuple(bounds)
        self.reset()

    def reset(self) -> None:
        self.counts = [0] * (len(self.bounds) + 1)
        self.count = 0
        self.total = 0.0
        self.vmax = 0.0

    def observe(self, value: float) -> None:
        v = float(value)
        i = 0
        for bound in self.bounds:
            if v <= bound:
                break
            i += 1
        self.counts[i] += 1
        self.count += 1
        self.total += v
        if v > self.vmax:
            self.vmax = v

    def percentile(self, q: float) -> float:
        if self.count == 0:
            return 0.0
        target = q * self.count
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= target:
                if i < len(self.bounds):
                    return min(self.bounds[i], self.vmax)
                return self.vmax
        return self.vmax

    def summary(self) -> Dict[str, Any]:
        if self.count == 0:
            return {"count": 0}
        return {"count": self.count,
                "mean": self.total / self.count,
                "p50": self.percentile(0.50),
                "p95": self.percentile(0.95),
                "p99": self.percentile(0.99),
                "max": self.vmax}


class Telemetry:
    """The engine's observability facade: event ring + metric registry.

    One instance per engine (``Engine.telemetry``, present iff
    ``EngineConfig.telemetry=True``). Reset semantics are uniform:
    :meth:`reset` clears the event ring, every counter and gauge, every
    fixed histogram, *and* every lazily-created per-class histogram in one
    call — ``Engine.reset_stats()`` delegates here, so warm-then-measure
    workflows never leak warmup observations into the measured run.
    """

    def __init__(self, slots: int = 4, max_events: int = 65536):
        if max_events < 1:
            raise ValueError(f"max_events must be >= 1, got {max_events}")
        self.slots = max(int(slots), 1)
        self.max_events = int(max_events)
        self.reset()

    # ------------------------------------------------------------- recording

    def reset(self) -> None:
        """Uniformly clear events, counters, gauges, and all histograms —
        including histograms created lazily (per-class TTFT) mid-run."""
        self.events: Deque[Event] = deque(maxlen=self.max_events)
        self.events_dropped = 0
        self.counters: Dict[str, int] = {}
        self.gauges: Dict[str, float] = {}
        self.hist: Dict[str, Histogram] = {
            name: Histogram(name) for name in HISTOGRAM_NAMES}
        self.ttft_by_class: Dict[int, Histogram] = {}
        self._t0: Optional[float] = None

    def event(self, name: str, rid: int = -1, slot: int = -1,
              **data: Any) -> None:
        """Record one lifecycle event (host timestamp, O(1), no syncs)."""
        ts = time.perf_counter()
        if self._t0 is None:
            self._t0 = ts
        if len(self.events) == self.max_events:
            self.events_dropped += 1
        self.events.append(Event(
            ts=ts, name=name, rid=int(rid), slot=int(slot),
            data=tuple(sorted(data.items()))))
        self.counters[name] = self.counters.get(name, 0) + 1

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = float(value)

    def observe(self, name: str, value_ms: float) -> None:
        self.hist[name].observe(value_ms)

    def observe_ttft(self, value_ms: float, priority_class: int = 0) -> None:
        """TTFT lands in the global histogram *and* a per-class one, so SLO
        reporting works per priority class without ``deadline_ms`` set."""
        self.hist["ttft_ms"].observe(value_ms)
        cls = int(priority_class)
        h = self.ttft_by_class.get(cls)
        if h is None:
            h = self.ttft_by_class[cls] = Histogram(f"ttft_class{cls}_ms")
        h.observe(value_ms)

    # ------------------------------------------------------------- summaries

    def section(self) -> Dict[str, Any]:
        """The ``EngineStats.telemetry`` section: everything summarized."""
        out: Dict[str, Any] = {
            "events": len(self.events),
            "events_dropped": self.events_dropped,
            "counters": dict(sorted(self.counters.items())),
        }
        if self.gauges:
            out["gauges"] = dict(sorted(self.gauges.items()))
        for name in HISTOGRAM_NAMES:
            out[name] = self.hist[name].summary()
        if self.ttft_by_class:
            out["ttft_by_class_ms"] = {
                cls: self.ttft_by_class[cls].summary()
                for cls in sorted(self.ttft_by_class)}
        return out

    def to_prometheus_text(self) -> str:
        """Prometheus text exposition of counters, gauges, and histograms."""
        lines: List[str] = []
        lines.append("# TYPE repro_engine_events_total counter")
        for name in sorted(self.counters):
            lines.append(f'repro_engine_events_total{{event="{name}"}} '
                         f"{self.counters[name]}")
        for name in sorted(self.gauges):
            lines.append(f"# TYPE repro_engine_{name} gauge")
            lines.append(f"repro_engine_{name} {self.gauges[name]:g}")
        hists = [(h.name, h) for h in self.hist.values()]
        hists += [(h.name, h) for _, h in sorted(self.ttft_by_class.items())]
        for name, h in hists:
            metric = f"repro_engine_{name}"
            help_ = HISTOGRAM_HELP.get(
                name, "submit to the step that dispatched the first token, "
                      "one priority class (host dispatch)")
            lines.append(f"# HELP {metric} {help_}, milliseconds")
            lines.append(f"# TYPE {metric} histogram")
            cum = 0
            for bound, c in zip(h.bounds, h.counts):
                cum += c
                lines.append(f'{metric}_bucket{{le="{bound:g}"}} {cum}')
            lines.append(f'{metric}_bucket{{le="+Inf"}} {h.count}')
            lines.append(f"{metric}_sum {h.total:g}")
            lines.append(f"{metric}_count {h.count}")
        return "\n".join(lines) + "\n"

    # ---------------------------------------------------------- trace export

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Render the event ring as Chrome ``trace_event`` JSON.

        Track layout (``pid`` 1): ``tid`` 0..slots-1 are the decode slots
        (a request's prefill and decode phases appear as complete ``X``
        spans on the slot that served it), ``tid`` slots is the admission
        queue (one ``queued`` span per submission->admission interval),
        slots+1 the allocator (evict/CoW/prefix-hit/spill/page-in/transfer
        instants), slots+2 the scheduler (preempt/shed/quarantine/retry
        instants), and — for tiered engines only — slots+3 a "host pool"
        counter track stamping host-tier occupancy. Timestamps are
        microseconds relative to the first event; events are sorted per
        track, so ``ts`` is monotone within every ``tid`` by construction
        (schema-checked by the BENCH_9 gate).
        """
        S = self.slots
        q_tid, alloc_tid, sched_tid, host_tid = S, S + 1, S + 2, S + 3
        t0 = self._t0 if self._t0 is not None else 0.0

        def us(ts: float) -> float:
            return round((ts - t0) * 1e6, 3)

        track_names = {i: f"slot {i}" for i in range(S)}
        track_names[q_tid] = "queue"
        track_names[alloc_tid] = "allocator"
        track_names[sched_tid] = "scheduler"
        # the host-pool counter track exists only for tiered engines —
        # spill/page-in events carry the post-op host_in_use occupancy
        if any(e.name in ("spilled", "paged_in") for e in self.events):
            track_names[host_tid] = "host pool"
        out: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": "repro-engine"}}]
        for tid in sorted(track_names):
            out.append({"name": "thread_name", "ph": "M", "pid": 1,
                        "tid": tid, "args": {"name": track_names[tid]}})

        instant_track = {
            "evicted": alloc_tid, "cow": alloc_tid, "prefix_hit": alloc_tid,
            "recycled": sched_tid, "preempted": sched_tid, "shed": sched_tid,
            "quarantined": sched_tid, "retried": sched_tid,
            "rejected": sched_tid, "draft_prefill": sched_tid,
            "spilled": alloc_tid, "paged_in": alloc_tid,
            "kv_transfer": alloc_tid,
        }
        spans: List[Dict[str, Any]] = []
        instants: List[Dict[str, Any]] = []
        q_open: Dict[int, float] = {}
        # rid -> [start_ts, slot, phase] for the open span on a slot track
        slot_open: Dict[int, List[Any]] = {}

        def close_queue(rid: int, ts: float, outcome: str) -> None:
            start = q_open.pop(rid, None)
            if start is None:
                return
            spans.append({"name": "queued", "ph": "X", "pid": 1,
                          "tid": q_tid, "ts": us(start),
                          "dur": max(us(ts) - us(start), 0.0),
                          "args": {"rid": rid, "outcome": outcome}})

        def close_slot(rid: int, ts: float, outcome: str) -> None:
            st = slot_open.pop(rid, None)
            if st is None:
                return
            start, slot, phase = st
            spans.append({"name": phase, "ph": "X", "pid": 1,
                          "tid": max(int(slot), 0),
                          "ts": us(start),
                          "dur": max(us(ts) - us(start), 0.0),
                          "args": {"rid": rid, "outcome": outcome}})

        for e in self.events:
            n = e.name
            if n == "submitted":
                q_open[e.rid] = e.ts
            elif n == "admitted":
                close_queue(e.rid, e.ts, "admitted")
                slot_open[e.rid] = [e.ts, e.slot, "prefill"]
            elif n == "first_token":
                st = slot_open.get(e.rid)
                slot = st[1] if st is not None else e.slot
                close_slot(e.rid, e.ts, "ok")
                slot_open[e.rid] = [e.ts, slot, "decode"]
            elif n in ("finished", "failed"):
                close_slot(e.rid, e.ts, n)
                close_queue(e.rid, e.ts, n)
            elif n in ("evicted", "quarantined"):
                close_slot(e.rid, e.ts, n)
            elif n == "shed":
                close_queue(e.rid, e.ts, "shed")
            elif n == "retried":
                q_open.setdefault(e.rid, e.ts)
            if n in instant_track:
                instants.append({
                    "name": n, "ph": "i", "s": "t", "pid": 1,
                    "tid": instant_track[n], "ts": us(e.ts),
                    "args": {"rid": e.rid, **dict(e.data)}})
            # host-pool occupancy counter track ("C" phase): every spill
            # and page-in stamps the post-op host_in_use value
            if n in ("spilled", "paged_in"):
                occ = dict(e.data).get("host_in_use")
                if occ is not None:
                    instants.append({
                        "name": "host_pages", "ph": "C", "pid": 1,
                        "tid": host_tid, "ts": us(e.ts),
                        "args": {"in_use": int(occ)}})
            # evicted / retried requests re-enter the queue at the front
            if n == "evicted":
                q_open[e.rid] = e.ts

        # spans still open when the ring was summarized (mid-run export)
        last_ts = self.events[-1].ts if self.events else t0
        for rid in sorted(slot_open):
            close_slot(rid, last_ts, "open")
        for rid in sorted(q_open):
            close_queue(rid, last_ts, "open")

        events = spans + instants
        events.sort(key=lambda d: (d.get("tid", -1), d["ts"],
                                   -d.get("dur", 0.0)))
        return {"traceEvents": out + events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_chrome_trace(), f, indent=1)


def normalized_events(tel: Telemetry, renumber_rids: bool = False
                      ) -> Tuple[Tuple[str, int, int, Tuple], ...]:
    """The event ring minus timestamps — the determinism-test view.

    Two identical greedy runs must produce identical normalized sequences.
    ``renumber_rids=True`` additionally renumbers request ids by first
    appearance (1, 2, ...), so a reset-then-rerun engine (whose rid counter
    keeps monotonically increasing across resets, by design — rids are
    globally unique handles) compares equal to a fresh engine.
    """
    if not renumber_rids:
        return tuple(e.normalized() for e in tel.events)
    remap: Dict[int, int] = {}
    out = []
    for e in tel.events:
        rid = e.rid
        if rid >= 0:
            if rid not in remap:
                remap[rid] = len(remap) + 1
            rid = remap[rid]
        out.append((e.name, rid, e.slot, e.data))
    return tuple(out)
