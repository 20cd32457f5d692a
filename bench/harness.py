"""One run of one cell: set-up, lead-in, the measured window, delivery and
the comparison with the plain reference.

Everything that belongs to one configuration, traffic mix or metric is found
by name: ``BENCHMARK.json`` names the cell's configuration file and traffic
mix, ``bench/families/<family>.py`` holds the family's layout, reference and
cost model, and ``bench/metrics/<metric>.py`` reads one metric from the run's
record.

The program under test is driven only through ``Engine.submit`` and
``Engine.step``. Tokens reach the host through a delivery thread that copies
each step's output vector (and each request's first token) to the host in
order and stamps the copy time: the time the user gets the token. The engine's
per-step sync stays off. The private engine attributes read here are listed in
``PERF.md`` (Open questions): ``_toklog``, ``_activated``, ``_prefilling``,
``Request._first_tok`` and ``Request._chunk_cursor``.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import queue
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import peaks as peaks_lib  # noqa: E402
from bench import tracing, traffic, weights  # noqa: E402
from bench.families import common  # noqa: E402

# a traced run must find every declared per-layer metric; an absent one is
# reported with the module names the trace held and the run fails
TAIL_LIMIT_S = 90.0        # the longest wait after the window for a first token
# the float8 control flips a served token on roughly 60% of ~100-token
# requests, so ten requests make a control that passes by chance rare
SAMPLE_TOKENS = 1000       # served tokens the comparison covers, at least
SAMPLE_MIN = 10            # requests the comparison covers, at least
SAMPLE_MAX = 12            # requests the comparison covers, at most
NONE_COMPARED = 1e30       # a reading when no request finished


class BenchError(RuntimeError):
    pass


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ the cell


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file, as run
    mix: dict              # the traffic mix
    end_to_end: List[str]
    per_layer: List[str]

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def family(self):
        return load_module(HERE / "families" / f"{self.config['family']}.py",
                           f"bench.families.{self.config['family']}")

    @property
    def limits(self) -> dict:
        return self.config["limits"]


def load_module(path: Path, name: str):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(Path(bench_file).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg["file"]).read_text())
    mix = traffic.load(HERE / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=[m["name"] for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m["name"] for m in spec["per_layer"]
                           if _applies(m, name)])


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    return ArchConfig(name=config["name"], param_dtype=config["dtype"],
                      compute_dtype=config["dtype"], **config["model"])


def engine_config(config: dict, **over):
    from repro.runtime.engine import EngineConfig
    e = dict(config["engine"])
    e["prompt_buckets"] = tuple(e["prompt_buckets"])
    e.update(over)
    return EngineConfig(**e)


def bucket_of(config: dict, n: int) -> int:
    return min(b for b in config["engine"]["prompt_buckets"] if b >= n)


def derived_seeds(seed: int):
    """(weight seed, traffic seed) from a run seed of any size."""
    ss = np.random.SeedSequence(int(seed))
    w, t = ss.generate_state(2, dtype=np.uint32)
    return int(w) & 0x7FFFFFFF, int(t)


# ------------------------------------------------------------------ devices


def require_chips(chips: int) -> list:
    """The accelerator devices, or an error: there is no CPU fallback."""
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise BenchError("JAX found no accelerator; the benchmark runs only "
                         "on the chip")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, with no
    size limit, whatever the environment names: a cache shared with another
    checkout, or one that evicts, would make ``setup_s`` depend on what ran
    before. Every program is cached, so only a cell's first run in a
    checkout compiles."""
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs lowered (a new shape or function: compiled or read
    from the persistent cache) from ``reset`` on, and the persistent cache's
    hits and misses since the process started."""

    def __init__(self):
        self.n = 0
        self.active = False
        self.cache = collections.Counter()
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)
        monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event.endswith("jaxpr_to_mlir_module_duration"):
            self.n += 1

    def _on_event(self, event: str, **_) -> None:
        if event.endswith(("/cache_hits", "/cache_misses")):
            self.cache[event.rsplit("/", 1)[1]] += 1

    def reset(self) -> None:
        self.n, self.active = 0, True


# ------------------------------------------------------------------ delivery


class Watcher(threading.Thread):
    """Copies token vectors to the host in dispatch order and stamps each
    copy: the time the user gets the tokens. A request replayed after an
    eviction re-emits tokens it already delivered; those are checked
    against the first delivery and not delivered twice."""

    def __init__(self):
        super().__init__(daemon=True)
        self.q: "queue.Queue" = queue.Queue()
        self.tokens: Dict[int, List[int]] = collections.defaultdict(list)
        self.times: Dict[int, List[float]] = collections.defaultdict(list)
        self.replay: Dict[int, int] = {}
        self.mismatch = 0

    def run(self) -> None:
        while True:
            item = self.q.get()
            if item is None:
                return
            kind, arr, meta = item
            host = np.asarray(arr)
            t = time.perf_counter()
            if kind == "first":
                if self.tokens[meta]:
                    self.replay[meta] = 0
                self._deliver(meta, int(host[0]), t)
            else:
                for slot, rid in enumerate(meta):
                    if rid >= 0:
                        self._deliver(rid, int(host[slot]), t)

    def _deliver(self, rid: int, tok: int, t: float) -> None:
        cur = self.replay.get(rid)
        if cur is not None and cur < len(self.tokens[rid]):
            self.mismatch += int(self.tokens[rid][cur] != tok)
            self.replay[rid] = cur + 1
            return
        self.tokens[rid].append(tok)
        self.times[rid].append(t)

    def close(self) -> None:
        self.q.put(None)
        self.join(timeout=600)
        if self.is_alive():
            raise BenchError("delivery thread did not finish")


# ------------------------------------------------------------------ the run


@dataclasses.dataclass
class Record:
    """What one run leaves for the metric readers."""
    cell: Cell
    peaks: object
    seconds: float
    setup_s: float = 0.0
    requests: List[dict] = dataclasses.field(default_factory=list)
    step_ms: List[float] = dataclasses.field(default_factory=list)
    stats: dict = dataclasses.field(default_factory=dict)
    trace: Optional[tracing.Trace] = None
    traced_decode: List[tuple] = dataclasses.field(default_factory=list)
    traced_prefill: List[tuple] = dataclasses.field(default_factory=list)
    notes: dict = dataclasses.field(default_factory=dict)

    def least_time(self, flops: float, nbytes: float) -> float:
        return peaks_lib.least_time(flops, nbytes, self.peaks)


class Session:
    """A cell's engine with its weights, warmed up, ready for a window."""

    def __init__(self, cell: Cell, seed: int, seconds: float, *,
                 device_kind: str, params=None, mix: Optional[dict] = None,
                 t_start: Optional[float] = None,
                 counter: Optional[CompileCounter] = None):
        from repro.runtime.engine import Engine
        self.t_start = t_start if t_start is not None else time.perf_counter()
        self.cell, self.seconds = cell, seconds
        self.mix = mix or cell.mix
        self.peaks = peaks_lib.peaks(device_kind)
        self.wseed, tseed = derived_seeds(seed)
        self.arrivals = traffic.generate(self.mix, seconds,
                                         cell.model["vocab"], tseed)
        self.cfg = arch_config(cell.config)
        self.ecfg = engine_config(cell.config)
        layout = cell.family.layout(cell.model)
        t = [time.perf_counter()]
        self.params = params if params is not None else \
            weights.make_tree(layout, self.wseed)
        jax.block_until_ready(self.params)
        t.append(time.perf_counter())
        self.engine = Engine(self.cfg, self.ecfg, params=self.params)
        t.append(time.perf_counter())
        self._warm_up()
        t.append(time.perf_counter())
        self.setup_parts = {"before": t[0] - self.t_start,
                            "weights": t[1] - t[0], "engine": t[2] - t[1],
                            "warm_up": t[3] - t[2]}
        self.counter = counter or CompileCounter()
        self.counter.reset()
        self.setup_s = time.perf_counter() - self.t_start

    def _warm_up(self) -> None:
        """Serve one short request per prompt bucket the traffic uses, so
        every program the window drives is compiled (or read from the
        persistent cache) here."""
        from repro.runtime.engine import RequestSpec
        eng = self.engine
        used = sorted({bucket_of(self.cell.config, len(a.prompt))
                       for a in self.arrivals})
        rng = np.random.default_rng(0)
        for b in used:
            eng.submit(RequestSpec(prompt=tuple(int(x) for x in rng.integers(
                0, self.cfg.vocab, size=b)), max_new_tokens=2))
        while _has_work(eng):
            eng.step()
        jax.block_until_ready(eng.tokens)
        eng.reset_stats()
        self.warm_buckets = used

    def free(self) -> None:
        self.engine = None
        self.params = None
        gc.collect()


def _has_work(eng) -> bool:
    return bool(eng.queue or eng._prefilling or
                any(r is not None for r in eng.slots_req))


def serve(s: Session, trace: bool = False,
          trace_dir: Optional[str] = None, tail: bool = True) -> Record:
    """Drive the engine through the lead-in, the window and the tail.
    ``trace_dir`` keeps the raw profile there instead of a temporary
    directory that is removed once it is read; ``tail=False`` stops at the
    window's end without waiting for late first tokens (the knee sweep)."""
    from jax.profiler import TraceAnnotation
    from repro.runtime.engine import RequestSpec
    cell, eng, m = s.cell, s.engine, s.cell.model
    fam = cell.family
    rec = Record(cell=cell, peaks=s.peaks, seconds=s.seconds,
                 setup_s=s.setup_s)
    chunk = s.ecfg.prefill_chunk
    arr = s.arrivals
    lead = float(s.mix["lead_in_s"])
    tr = s.mix.get("trace", {})
    tr_start = float(tr.get("start_s", 0.4 * s.seconds))
    tr_len = min(float(tr.get("seconds", 3.0)), s.seconds - tr_start)
    tr_dir = trace_dir or (tempfile.mkdtemp(prefix="bench-trace-")
                           if trace else None)
    window_ann = None
    reqs: Dict[int, object] = {}          # rid -> Request
    due: Dict[int, float] = {}            # rid -> due time (host clock)
    in_window: Dict[int, bool] = {}
    window_rids: List[int] = []
    watcher = Watcher()
    watcher.start()

    t0 = time.perf_counter() + lead       # the window opens after the lead-in
    end = t0 + s.seconds
    i = 0
    tracing_now = False
    tr_until = 0.0
    stats_read = False
    steps = 0

    def window_done() -> bool:
        return all(watcher.times.get(r) for r in window_rids) and i >= len(arr)

    while True:
        now = time.perf_counter()
        if now >= end and not stats_read:
            st = eng.stats()
            rec.stats = st.to_dict()
            rec.notes["queue_at_end"] = len(eng.queue) + len(eng._prefilling)
            stats_read = True
        if now >= end:
            if not tail or window_done() or now - end > TAIL_LIMIT_S:
                break
        if not stats_read and t0 <= now and "queue_at_start" not in rec.notes:
            eng.reset_stats()
            rec.notes["queue_at_start"] = len(eng.queue) + len(eng._prefilling)
        if trace and not tracing_now and window_ann is None \
                and now >= t0 + tr_start:
            jax.block_until_ready(eng.tokens)
            tracing.start(tr_dir)
            window_ann = TraceAnnotation("bench.window")
            window_ann.__enter__()
            tracing_now = True
            tr_until = time.perf_counter() + tr_len
        if tracing_now and now >= tr_until:
            window_ann.__exit__(None, None, None)
            tracing.stop()
            tracing_now = False
        with TraceAnnotation("bench.submit"):
            while i < len(arr) and t0 + arr[i].due_s <= now:
                a = arr[i]
                r = eng.submit(RequestSpec(prompt=a.prompt,
                                           max_new_tokens=a.max_new_tokens))
                reqs[r.rid] = r
                due[r.rid] = t0 + a.due_s
                in_window[r.rid] = a.in_window
                if a.in_window:
                    window_rids.append(r.rid)
                i += 1
        if _has_work(eng):
            pre = {r.rid: r._chunk_cursor for r in eng._prefilling.values()}
            n_log = len(eng._toklog)
            with TraceAnnotation("bench.step"):
                ts = time.perf_counter()
                eng.step()
                te = time.perf_counter()
            steps += 1
            if t0 <= ts < end:
                rec.step_ms.append((te - ts) * 1e3)
            with TraceAnnotation("bench.deliver"):
                acts = list(eng._activated)
                for r in acts:
                    watcher.q.put(("first", r._first_tok, r.rid))
                decoded = len(eng._toklog) > n_log
                if decoded:
                    nxt, rids = eng._toklog[-1]
                    watcher.q.put(("step", nxt, rids))
            if tracing_now:
                for item in _prefill_work(eng, acts, pre, chunk):
                    rec.traced_prefill.append(fam.prefill_cost(m, *item))
                if decoded:
                    ctx = [int(eng.pos[j]) for j, rid in enumerate(rids)
                           if rid >= 0]
                    rec.traced_decode.append(fam.decode_cost(m, ctx))
        else:
            nxt_due = t0 + arr[i].due_s if i < len(arr) else end
            with TraceAnnotation("bench.wait_arrival"):
                wait = min(nxt_due, end if now < end else now + 0.001) \
                    - time.perf_counter()
                if wait > 0:
                    time.sleep(min(wait, 0.05))
    if tracing_now:
        window_ann.__exit__(None, None, None)
        tracing.stop()
    jax.block_until_ready(eng.tokens)
    watcher.close()
    rec.notes.update(steps=steps, arrivals=len(window_rids),
                     arrivals_before=sum(1 for a in arr if not a.in_window),
                     replay_mismatch=watcher.mismatch,
                     evictions=rec.stats.get("evictions", 0),
                     peak_pages=rec.stats.get("peak_pages"),
                     tail_s=time.perf_counter() - end,
                     compiles_in_window=s.counter.n)
    for rid, r in reqs.items():
        rec.requests.append({
            "rid": rid, "due": due[rid] - t0, "in_window": in_window[rid],
            "prompt": list(r.prompt), "bucket": r.bucket,
            "max_new_tokens": r.max_new_tokens, "state": r.state,
            "times": [t - t0 for t in watcher.times.get(rid, [])],
            "tokens": watcher.tokens.get(rid, [])})
    rec.notes["still_decoding"] = sum(
        1 for q in rec.requests
        if q["tokens"] and len(q["tokens"]) < q["max_new_tokens"])
    if trace:
        rec.trace = tracing.load(tr_dir)
        if trace_dir is None:
            shutil.rmtree(tr_dir, ignore_errors=True)
    return rec


def _prefill_work(eng, acts, pre: Dict[int, int], chunk: int):
    """``(offset, prompt tokens)`` of every prefill forward the last step
    dispatched: one per chunk of a chunked prefill, one for a one-shot
    prefill. Tokens past the prompt (bucket padding) are not required work."""
    out = []
    still = list(eng._prefilling.values())
    prefilling = {id(r) for r in still}
    for r in acts + still:
        n = len(r.prompt)
        if chunk and r.bucket > chunk:
            c1 = r._chunk_cursor if id(r) in prefilling else r.bucket // chunk
            for k in range(pre.get(r.rid, 0), c1):
                out.append((k * chunk, max(0, min(chunk, n - k * chunk))))
        elif id(r) not in prefilling:
            out.append((0, n))
    return out


# ------------------------------------------------------------ correctness


def sample_finished(rec: Record, seed: int) -> List[dict]:
    """Requests delivered in full, drawn from the seed, with the longest of
    them first; at least ``SAMPLE_MIN`` requests and ``SAMPLE_TOKENS``
    served tokens where there are."""
    done = [q for q in rec.requests
            if len(q["tokens"]) == q["max_new_tokens"]]
    if not done:
        return []
    done.sort(key=lambda q: (-(q["bucket"] + q["max_new_tokens"]), q["rid"]))
    rng = np.random.default_rng(seed)
    rest = [done[j] for j in rng.permutation(len(done) - 1) + 1]
    out, n = [done[0]], done[0]["max_new_tokens"]
    for q in rest:
        if len(out) >= SAMPLE_MAX or (n >= SAMPLE_TOKENS
                                      and len(out) >= SAMPLE_MIN):
            break
        out.append(q)
        n += q["max_new_tokens"]
    return out


def reference_gaps(cell: Cell, wseed: int, sample: List[dict],
                   control: bool = False) -> List[np.ndarray]:
    """Per sampled request, the gap by which each served token's logit lies
    below the float32 reference's best (``control``: the gap of the token
    the float8 control puts first, at the same positions)."""
    fam, m = cell.family, cell.model
    w = weights.make_tree(fam.layout(m), wseed)
    out = []
    for q in sample:
        padded = np.zeros((q["bucket"],), np.int32)
        padded[:len(q["prompt"])] = q["prompt"]
        served = np.asarray(q["tokens"], np.int32)
        seq = np.concatenate([padded, served[:-1]])
        S = len(seq)
        pad_to = -(-S // 512) * 512            # causal: the tail pad is inert
        toks = jnp.asarray(np.pad(seq, (0, pad_to - S)))
        pos = slice(q["bucket"] - 1, q["bucket"] - 1 + len(served))
        ref = fam.reference_logits(m, w, toks)[pos]
        if control:
            low = fam.reference_logits(m, w, toks, fp8=True)[pos]
            _, pick = common.gaps(low, jnp.asarray(served))
            g, _ = common.gaps(ref, pick)
        else:
            g, _ = common.gaps(ref, jnp.asarray(served))
        out.append(np.asarray(g))
    del w
    return out


def readings(gaps: List[np.ndarray]) -> dict:
    """The numbers a configuration may compare: the widest gap over every
    compared token, and the mean gap per token. With no token compared both
    read ``NONE_COMPARED``, above every limit."""
    if not gaps:
        return {"served_token_gap": NONE_COMPARED,
                "served_token_gap_mean": NONE_COMPARED}
    allg = np.concatenate(gaps)
    return {"served_token_gap": float(allg.max()),
            "served_token_gap_mean": float(allg.mean())}


def checks(cell: Cell, rec: Record, read: dict) -> dict:
    """Every number compared, with its limit: the readings the
    configuration's ``limits`` names, requests that never got a token, and
    replayed tokens that differ from their first delivery."""
    out = {k: {"value": read[k], "limit": v} for k, v in cell.limits.items()}
    lost = sum(1 for q in rec.requests if q["in_window"] and not q["times"])
    out["lost_requests"] = {"value": lost, "limit": 0}
    out["replay_mismatch"] = {"value": rec.notes["replay_mismatch"],
                              "limit": 0}
    return out


def passed(ch: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in ch.values())


# ------------------------------------------------------------------ metrics


def read_metrics(rec: Record, names: List[str], units: Dict[str, str]):
    """Each metric's reader from ``bench/metrics/<name>.py``; a reader that
    finds nothing to read returns None."""
    out, missing = {}, []
    for name in names:
        mod = load_module(HERE / "metrics" / f"{name}.py",
                          "bench.metrics." + name.replace(".", "_"))
        v = mod.read(rec)
        if v is None:
            missing.append(name)
        else:
            out[name] = {"value": float(v), "unit": units[name]}
    return out, missing
