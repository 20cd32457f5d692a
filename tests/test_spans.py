"""The engine's host spans, its program names and the named scopes of its
decode step, as a ``jax.profiler`` trace and the compiled HLO show them; and
the scheduler's admission stamps and wait counters.

Spans are ``runtime.telemetry.span`` annotations on the profiler's clock:
they nest on the calling thread under ``engine.step``, and their names come
from ``SPAN_NAMES``. The engine's programs keep the function names that
``PROGRAM_ROLES`` (and a profile's module-to-role table) map to roles.
"""
import json
import time
from pathlib import Path

import jax
import pytest

from repro.configs import smoke_config
from repro.core.lower import PlanCache
from repro.models import api
from repro.runtime.engine import (PROGRAM_ROLES, Engine, EngineConfig,
                                  RequestSpec)
from repro.runtime.telemetry import SPAN_NAMES

CFG = smoke_config("tinyllama-1.1b")
CACHE = PlanCache()
ROLE_TABLE = Path(__file__).resolve().parents[1] / "bench" / "modules.json"
SCOPES = ("attention", "mlp", "kv_write", "sample")


@pytest.fixture(scope="module")
def params():
    return api.init_params(CFG, jax.random.key(0))


def mk_paged(params, **kw):
    """Paged engine with chunked prefill: prompts above 16 tokens take
    chunks, shorter ones one-shot prefill."""
    kw.setdefault("num_pages", 40)
    kw.setdefault("slots", 2)
    return Engine(CFG, EngineConfig(prompt_buckets=(8, 16, 32, 48),
                                    max_seq=64, kv_layout="paged",
                                    page_size=4, prefill_chunk=16, **kw),
                  params=params, plan_cache=CACHE)


def mk_dense(params):
    return Engine(CFG, EngineConfig(slots=2, prompt_buckets=(8,), max_seq=16),
                  params=params, plan_cache=CACHE)


def spec(n, new=4, start=1):
    return RequestSpec(prompt=tuple((start + j) % CFG.vocab
                                    for j in range(n)), max_new_tokens=new)


def drain(eng):
    while eng.queue or eng._prefilling or any(
            r is not None for r in eng.slots_req):
        eng.step()


def profile(tmp_path, work):
    """Run ``work`` under the profiler, host tracing only, and return the
    host events ``[(name, start_ns, end_ns, line, stats)]``."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.end_ns,
                            (plane.name, line.name),
                            {str(k): str(v) for k, v in e.stats}))
    return out


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """A paged, chunked engine's trace: a long (chunked) and a short
    (one-shot) prompt submitted and served under the profiler."""
    eng = mk_paged(params)
    served = []

    def work():
        served.append(eng.submit(spec(40)))
        served.append(eng.submit(spec(6, start=7)))
        drain(eng)

    events = profile(tmp_path_factory.mktemp("trace"), work)
    assert all(r.state == "done" for r in served)
    return events


def test_steps_nest_spans_from_the_vocabulary(traced):
    spans = [e for e in traced if e[0].startswith("engine.")]
    assert {e[0] for e in spans} <= set(SPAN_NAMES)
    steps = [e for e in spans if e[0] == "engine.step"]
    assert len(steps) >= 3
    inside = lambda e, s: (e[3] == s[3] and s[1] <= e[1]    # noqa: E731
                           and e[2] <= s[2])
    for e in spans:
        if e[0] == "engine.step":
            continue
        if e[0] == "engine.submit" or any(inside(e, s) for s in steps):
            continue
        # outside a step only submission and its key's read-back happen
        assert e[0] == "engine.readback" and any(
            inside(e, s) for s in spans if s[0] == "engine.submit"), e
    for name in ("engine.dispatch", "engine.admit", "engine.prefill",
                 "engine.pages", "engine.decode"):
        assert any(e[0] == name and inside(e, s)
                   for e in spans for s in steps), name
    # the decode program's call sits inside the decode block of a step
    decode = [e for e in spans if e[0] == "engine.decode"]
    assert any(e[0] == "engine.dispatch" and inside(e, d)
               for e in spans for d in decode)


def test_program_roles_match_the_profile_role_table():
    table = json.loads(ROLE_TABLE.read_text())
    for name, role in PROGRAM_ROLES.items():
        assert table[f"jit_{name}"] == role, name
    assert {"decode", "prefill", "cache"} == set(PROGRAM_ROLES.values())


def test_paged_programs_run_as_their_named_modules(traced):
    modules = {e[4]["hlo_module"] for e in traced if "hlo_module" in e[4]}
    for name in ("step", "chunk", "pre", "ins"):
        assert f"jit_{name}" in modules, (name, sorted(modules))


def test_dense_programs_run_as_their_named_modules(params, tmp_path):
    eng = mk_dense(params)

    def work():
        eng.submit(spec(6))
        drain(eng)

    events = profile(tmp_path, work)
    modules = {e[4]["hlo_module"] for e in events if "hlo_module" in e[4]}
    for name in ("step", "pre", "insert"):
        assert f"jit_{name}" in modules, (name, sorted(modules))


def test_every_engine_program_is_named_in_the_table(params):
    plain = mk_paged(params)
    prefix = mk_paged(params, prefix_cache=True, tiered_kv=True)
    disagg = mk_paged(params, disaggregated=True)
    dense = mk_dense(params)
    progs = [plain._decode, plain._chunk_prefill, plain._page_insert,
             plain._prefill_fn(8), prefix._page_copy, prefix._page_upload,
             prefix._build_suffix_prefill(16, 8), disagg._kv_transfer,
             dense._decode, dense._insert]
    assert {p.__name__ for p in progs} == set(PROGRAM_ROLES)


def test_decode_program_carries_the_step_scopes(params):
    eng = mk_paged(params)
    seen = []
    decode = eng._decode

    def record(*args):
        seen.append(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))
        return decode(*args)

    eng._decode = record
    eng.submit(spec(6))
    eng.step()
    hlo = decode.lower(*seen[0]).compile().as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in hlo, scope


def test_admission_stamp_and_wait_counters(params):
    # 12 free pages (page 0 is the null page): the first request's 8 prompt
    # pages plus its growth reserve leave too few for the second's 8, which
    # waits for pages while a slot is free
    eng = mk_paged(params, num_pages=13, slots=2)
    first = eng.submit(spec(32, new=8))
    eng.step()
    second = eng.submit(spec(32, new=4, start=5))
    eng.step()
    blocked = time.perf_counter()
    assert second.state == "queued"
    st = eng.stats()
    assert st.admit_wait_pages >= 1 and st.admit_wait_slots == 0
    drain(eng)
    for r in (first, second):
        assert r.state == "done"
        assert r.t_submit <= r.t_admitted <= r.t_done
    assert second.t_admitted > blocked


def test_a_full_batch_counts_slot_waits(params):
    eng = mk_paged(params, slots=1)
    reqs = [eng.submit(spec(6, start=s)) for s in (1, 2)]
    eng.step()
    assert eng.stats().admit_wait_slots == 1
    drain(eng)
    assert reqs[1].t_admitted >= reqs[0].t_admitted
    eng.reset_stats()
    assert eng.stats().admit_wait_slots == 0
    assert eng.stats().admit_wait_pages == 0
