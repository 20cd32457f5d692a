"""Device trace of a steady sub-window, and its reduction to a record.

The profiler runs with Python tracing off. ``load`` reads the ``.xplane.pb``
with ``jax.profiler.ProfileData`` and keeps only what the metrics read: the
XLA module executions on the device (named, with the role ``modules.json``
gives them), the union of the device's operation intervals, and the harness's
own ``bench.*`` host spans. Times are seconds from the start of the
``bench.window`` span.
"""
from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Tuple

from . import stats

HERE = Path(__file__).resolve().parent
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def roles() -> Dict[str, str]:
    table = json.loads((HERE / "modules.json").read_text())
    return {k: v for k, v in table.items() if not k.startswith("_")}


def module_name(event_name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0].strip()


@dataclasses.dataclass
class Trace:
    window_s: float
    modules: List[Tuple[str, float, float]]      # (name, start, end)
    busy: List[Tuple[float, float]]              # merged device op intervals
    host: List[Tuple[str, float, float]]         # bench.* spans
    chips: int = 1

    def of_role(self, role: str) -> List[Tuple[str, float, float]]:
        table = roles()
        return [e for e in self.modules if table.get(e[0], "other") == role]

    def busy_s(self) -> float:
        return stats.union_length(
            (max(s, 0.0), min(e, self.window_s)) for s, e in self.busy
            if e > 0.0 and s < self.window_s)

    def gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the device inside the window."""
        out, t = [], 0.0
        for s, e in stats.merge(self.busy):
            s, e = max(s, 0.0), min(e, self.window_s)
            if e <= 0.0 or s >= self.window_s:
                continue
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.window_s:
            out.append((t, self.window_s))
        return out

    def host_label(self, a: float, b: float) -> str:
        """The ``bench.*`` span that overlaps ``[a, b]`` the most."""
        best, label = 0.0, "none"
        for name, s, e in self.host:
            if name == "bench.window":
                continue
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, label = ov, name
        return label

    def to_json(self) -> dict:
        return {"window_s": self.window_s, "chips": self.chips,
                "modules": [list(m) for m in self.modules],
                "busy": [list(b) for b in self.busy],
                "host": [list(h) for h in self.host]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(d["window_s"], [tuple(m) for m in d["modules"]],
                   [tuple(b) for b in d["busy"]],
                   [tuple(h) for h in d["host"]], d.get("chips", 1))

    def cut(self, seconds: float) -> "Trace":
        """The first ``seconds`` of the window (for recorded test data)."""
        keep = lambda s, e: s < seconds and e > 0.0   # noqa: E731
        return Trace(seconds,
                     [m for m in self.modules if keep(m[1], m[2]) and m[2] <= seconds],
                     [(s, min(e, seconds)) for s, e in self.busy if keep(s, e)],
                     [(n, s, min(e, seconds)) for n, s, e in self.host
                      if keep(s, e)], self.chips)


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    modules, ops, host = [], [], []
    chips = 0
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            chips += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(module_name(e.name), e.start_ns, e.end_ns)
                                for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [(e.start_ns, e.end_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name.startswith("bench.")]
    win = [h for h in host if h[0] == "bench.window"]
    if not win:
        raise RuntimeError("trace has no bench.window span")
    t0, t1 = win[0][1], win[0][2]
    sec = lambda ns: (ns - t0) * 1e-9          # noqa: E731
    busy = stats.merge((sec(s), sec(e)) for s, e in (ops or
                                                     [(m[1], m[2]) for m in modules]))
    return Trace(window_s=(t1 - t0) * 1e-9,
                 modules=sorted(((n, sec(s), sec(e)) for n, s, e in modules),
                                key=lambda m: m[1]),
                 busy=busy,
                 host=sorted(((n, sec(s), sec(e)) for n, s, e in host),
                              key=lambda h: h[1]),
                 chips=max(chips, 1))


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device modules that took most time, and the longest idle gaps
    labelled with the ``bench.*`` span open on the host during each."""
    per: Dict[str, float] = {}
    for name, s, e in tr.modules:
        per[name] = per.get(name, 0.0) + (e - s)
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(tr.gaps(), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[tr.host_label(a, b), b - a] for a, b in gaps]}
