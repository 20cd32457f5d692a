"""The engine's own spans and scopes in a device trace, and what they read.

``bench/tracing.py`` keeps the XLA modules, the device's busy intervals and
the harness's ``bench.*`` spans of a profiled sub-window. The engine marks
its own work too, on the same profiler clock: ``engine.*`` host spans
(``SPAN_NAMES`` in ``runtime/telemetry.py``) and the named scopes of its
decode step (``attention``, ``mlp``, ``kv_write``, ``sample``) in the HLO
metadata of the ops. ``load`` keeps both beside everything ``tracing.load``
keeps, in a ``SpanTrace``, which the harness's readers take like any
``Trace``. The readings below turn them into four numbers:

- ``host_self_ms``: mean time per ``engine.step`` in which the host ran the
  engine's own Python, that is the step less its ``engine.dispatch`` and
  ``engine.readback`` spans;
- ``host_wait_ms``: mean time per ``engine.step`` inside those two, where
  the host can block on the device;
- ``paged_attention_roofline``: least time of the decode steps' attention
  work over the device time of their ops under ``attention``, in percent;
- ``queue_wait_p75_ms``: p75 over the requests due in the window of the
  time from submission to admission.

The harness does not call this module yet: ``bench/span_probe.py`` runs a
cell with it on the chip. ``PERF.md`` (Open questions) says which harness
files take which edit to make the four numbers per-layer metrics.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from . import stats, tracing

SCOPES = ("attention", "mlp", "kv_write", "sample")
WAIT_SPANS = ("engine.dispatch", "engine.readback")
_INSTR = re.compile(r'^\s*(ROOT\s+)?%?([^\s=]+)\s*=\s')
_PATH = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r'calls=%?([^\s,)]+)')
_COMP = re.compile(r'^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$')
# a device op event is named by its HLO instruction: ``fusion.3`` on the
# CPU, the whole instruction ``%fusion.3 = bf16[..] fusion(..), ..`` on a TPU
_OP_NAME = re.compile(r"^%?([^\s=]+)")


@dataclasses.dataclass
class SpanTrace(tracing.Trace):
    """A ``Trace`` with the engine's spans and the scoped decode ops."""
    engine: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)                    # engine.* host spans
    scoped: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)                    # (scope, start, end)

    def host_label(self, a: float, b: float) -> str:
        """The ``bench.*`` label of ``[a, b]``, then ``/`` and the innermost
        ``engine.*`` span that covers at least half of it (else the one
        that overlaps it most), or the ``bench.*`` label alone."""
        label = super().host_label(a, b)
        over = [(min(b, e) - max(a, s), e - s, n) for n, s, e in self.engine]
        over = [o for o in over if o[0] > 0]
        if not over:
            return label
        half = [o for o in over if o[0] >= 0.5 * (b - a)]
        name = min(half, key=lambda o: o[1])[2] if half \
            else max(over, key=lambda o: o[0])[2]
        return f"{label}/{name}"

    def to_json(self) -> dict:
        d = super().to_json()
        d["engine"] = [list(h) for h in self.engine]
        d["scoped"] = [list(o) for o in self.scoped]
        return d

    @classmethod
    def from_json(cls, d: dict) -> "SpanTrace":
        base = tracing.Trace.from_json(d)
        return cls(**vars(base),
                   engine=[tuple(h) for h in d.get("engine", [])],
                   scoped=[tuple(o) for o in d.get("scoped", [])])

    def cut(self, seconds: float) -> "SpanTrace":
        base = super().cut(seconds)
        keep = lambda s, e: s < seconds and e > 0.0   # noqa: E731
        return SpanTrace(**vars(base),
                         engine=[(n, s, min(e, seconds))
                                 for n, s, e in self.engine if keep(s, e)],
                         scoped=[o for o in self.scoped
                                 if keep(o[1], o[2]) and o[2] <= seconds])


def scope_of(path: str) -> Optional[str]:
    """The decode-step scope an HLO ``op_name`` path lies in, or None."""
    for part in path.split("/"):
        if part in SCOPES:
            return part
    return None


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> decode-step scope, from compiled HLO text: the
    scope of the instruction's own ``op_name`` metadata, else, for a fusion,
    the scope of the instructions it fuses (its root's first). XLA gives a
    fusion its root's metadata, and a root outside every scope (a reshape,
    a reduction the compiler added) would hide the scoped work it fuses."""
    out, calls, inner, comp = {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            c = _COMP.match(line)
            comp = c.group(1) if c else comp
            continue
        path = _PATH.search(line)
        sc = scope_of(path.group(1)) if path else None
        if sc:
            out[m.group(2)] = sc
            if m.group(1):
                inner[comp] = sc
            else:
                inner.setdefault(comp, sc)
        else:
            called = _CALLS.search(line)
            if called:
                calls[m.group(2)] = called.group(1)
    for name, c in calls.items():
        if c in inner:
            out[name] = inner[c]
    return out


def arg_specs(args):
    """Abstract stand-ins for the arguments of a call into a compiled
    program, from which ``lower(...).compile()`` finds the executable that
    call ran (the persistent cache's key is the lowered text). They carry no
    sharding: the engine's arguments are uncommitted, and a sharding would
    change the lowered text and so the compiled instruction names."""
    import jax
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, weak_type=a.weak_type), args)


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event."""
    return _OP_NAME.match(event_name).group(1)


def load(log_dir: str, decode_hlo: str = "") -> SpanTrace:
    """``tracing.load`` of the profile under ``log_dir``, with the engine's
    spans and, for every device op inside a decode module, its scope. The
    op events carry no path of their own on a TPU: the scope is read from
    the ``op_name`` metadata of ``decode_hlo`` (the decode program's
    compiled HLO text) under the op's instruction name."""
    from jax.profiler import ProfileData
    base = tracing.load(log_dir)
    pb = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(pb))
    scopes = op_scopes(decode_hlo)
    decode = {n for n, r in tracing.roles().items() if r == "decode"}
    t0, engine, ops, mods = None, [], [], []
    for plane in pd.planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += [(e.start_ns, e.end_ns) for e in line.events
                             if tracing.module_name(e.name) in decode]
                elif line.name == "XLA Ops":
                    ops += [(e, e.start_ns, e.end_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        engine.append((e.name, e.start_ns, e.end_ns))
                    elif e.name == "bench.window" and t0 is None:
                        t0 = e.start_ns
    sec = lambda ns: (ns - t0) * 1e-9          # noqa: E731
    mods.sort()
    scoped, j = [], 0
    for e, s, t in sorted(ops, key=lambda o: o[1]):
        while j < len(mods) and mods[j][1] < s:
            j += 1
        if j == len(mods) or not mods[j][0] <= s <= t <= mods[j][1]:
            continue
        sc = scopes.get(op_name(e.name))
        if sc:
            scoped.append((sc, sec(s), sec(t)))
    return SpanTrace(**vars(base),
                     engine=sorted(((n, sec(s), sec(e)) for n, s, e in engine),
                                   key=lambda h: h[1]),
                     scoped=scoped)


# ------------------------------------------------------------------ readings


def _steps(tr) -> List[Tuple[float, float]]:
    return [(s, e) for n, s, e in getattr(tr, "engine", ())
            if n == "engine.step" and s >= 0.0 and e <= tr.window_s]


def _waits(tr, s: float, e: float) -> float:
    return stats.union_length(
        (max(a, s), min(b, e)) for n, a, b in tr.engine
        if n in WAIT_SPANS and a < e and b > s)


def host_wait_ms(tr) -> Optional[float]:
    steps = _steps(tr)
    if not steps:
        return None
    return 1e3 * sum(_waits(tr, s, e) for s, e in steps) / len(steps)


def host_self_ms(tr) -> Optional[float]:
    steps = _steps(tr)
    if not steps:
        return None
    return 1e3 * sum((e - s) - _waits(tr, s, e) for s, e in steps) \
        / len(steps)


def scope_seconds(tr) -> List[Dict[str, float]]:
    """Per decode event of the trace, the device seconds its ops spent
    under each scope (union of their intervals)."""
    out = []
    for _, s, e in tr.of_role("decode"):
        out.append({sc: stats.union_length(
            (a, b) for n, a, b in getattr(tr, "scoped", ())
            if n == sc and a >= s and b <= e) for sc in SCOPES})
    return out


def live_positions(m, decode_cost: Sequence[float]) -> int:
    """The live positions a decode step attended to, summed over its slots,
    from the (FLOPs, bytes) the dense family's ``decode_cost`` gave it: the
    weights once plus every live position's K and V."""
    from bench.families import dense
    weights = sum(dense.param_bytes(m).values())
    return round((decode_cost[1] - weights) / dense.kv_bytes_per_token(m))


def attention_cost(m, ctx: Sequence[int]):
    """(FLOPs, bytes) the attention of one decode step requires; ``ctx``
    lists, per active slot, the positions its new token attends to. Per
    layer and slot, the K and V of those positions are read once, and
    scores and values take ``4 * H * hd`` FLOPs per position."""
    from bench.families import dense
    n = sum(ctx)
    return dense._attn_flops(m, n), dense.kv_bytes_per_token(m) * n


def paged_attention_roofline(rec) -> Optional[float]:
    """Least time of the attention work of the trace's decode steps over
    the device time of their ops under ``attention``, in percent; the k-th
    decode event is the k-th decode dispatch (``_share.matched``)."""
    from bench.metrics import _share
    tr = rec.trace
    if tr is None or not getattr(tr, "scoped", None):
        return None
    n = _share.matched(tr, "decode", rec.traced_decode)
    per = scope_seconds(tr)[:n]
    dev = sum(p["attention"] for p in per)
    m = rec.cell.model
    least = sum(rec.least_time(*attention_cost(m, [live_positions(m, c)]))
                for c in rec.traced_decode[:n])
    return 100.0 * least / dev if dev > 0 and least > 0 else None


def queue_wait_p75_ms(requests) -> Optional[float]:
    """p75 over the requests due in the window of their wait from
    submission (``submitted``) to admission (``admitted``)."""
    v = [(q["admitted"] - q["submitted"]) * 1e3 for q in requests
         if q["in_window"] and q.get("admitted") is not None]
    return stats.percentile(v, 75) if v else None
