"""Continuous-batching engine + PlanCache: fingerprint stability, hit/miss
accounting, slot recycling under mixed-length decode, and engine-vs-sequential
token equality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeCfg, smoke_config
from repro.core.lower import PlanCache
from repro.core.passes import run_pipeline
from repro.core.plans import build_program
from repro.core.printer import program_fingerprint
from repro.models import api
from repro.runtime.engine import (Engine, EngineConfig, RequestSpec,
                                  serve_sequential)

CFG = smoke_config("tinyllama-1.1b")
BUCKET = 8
TOKENS = 6
MAX_SEQ = BUCKET + TOKENS


@pytest.fixture(scope="module")
def params():
    return api.init_params(CFG, jax.random.key(0))


def decode_shape(batch=2):
    return ShapeCfg(f"engine_b{batch}", "decode", MAX_SEQ, batch)


# ------------------------------------------------------------- fingerprints


def test_fingerprint_stable_across_builds():
    a = build_program(CFG, decode_shape())
    b = build_program(CFG, decode_shape())
    assert program_fingerprint(a) == program_fingerprint(b)


def test_fingerprint_stable_across_pass_pipeline():
    a = run_pipeline(build_program(CFG, decode_shape()))
    b = run_pipeline(build_program(CFG, decode_shape()))
    assert program_fingerprint(a) == program_fingerprint(b)


def test_fingerprint_distinguishes_shapes():
    a = build_program(CFG, decode_shape(batch=2))
    b = build_program(CFG, decode_shape(batch=4))
    assert program_fingerprint(a) != program_fingerprint(b)


# --------------------------------------------------------------- plan cache


def test_plan_cache_hit_miss():
    cache = PlanCache()
    p1 = cache.lowered_plan(build_program(CFG, decode_shape()))
    assert (cache.hits, cache.misses) == (0, 1)
    p2 = cache.lowered_plan(build_program(CFG, decode_shape()))
    assert (cache.hits, cache.misses) == (1, 1)
    assert p1 is p2
    assert p1.fingerprint
    assert cache.stats()["hit_rate"] == 0.5


def test_plan_cache_miss_on_different_key():
    cache = PlanCache()
    cache.lowered_plan(build_program(CFG, decode_shape()))
    cache.lowered_plan(build_program(CFG, decode_shape()), backend="gspmd")
    cache.lowered_plan(build_program(CFG, decode_shape(batch=4)))
    assert cache.misses == 3 and cache.hits == 0


def test_plan_cache_skips_pipeline_on_hit():
    cache = PlanCache()
    trace = []
    cache.lowered_plan(build_program(CFG, decode_shape()), trace=trace)
    n_pass_entries = len(trace)
    assert n_pass_entries > 0
    cache.lowered_plan(build_program(CFG, decode_shape()), trace=trace)
    assert len(trace) == n_pass_entries  # warm hit: pipeline never ran


def test_plan_cache_lru_bound():
    cache = PlanCache(maxsize=2)
    for b in (2, 3, 4):
        cache.lowered_plan(build_program(CFG, decode_shape(batch=b)))
    assert cache.stats()["size"] == 2


# ------------------------------------------------------------------- engine


def mk_engine(params, slots=2, max_queue=64):
    return Engine(CFG, EngineConfig(slots=slots, max_queue=max_queue,
                                    prompt_buckets=(BUCKET,),
                                    max_seq=MAX_SEQ),
                  params=params, plan_cache=PlanCache())


def prompts(n, length=BUCKET, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab, size=length).tolist() for _ in range(n)]


def test_engine_matches_sequential_tokens(params):
    engine = mk_engine(params, slots=2)
    reqs = [engine.make_request(p, TOKENS) for p in prompts(4)]
    engine.run(reqs)
    seq = serve_sequential(CFG, params, reqs, max_seq=MAX_SEQ,
                           prompt_buckets=(BUCKET,))
    for r in reqs:
        assert r.state == "done"
        assert engine.finalize_request(r) == seq["tokens"][r.rid], r.rid

    st = engine.stats()
    assert st["completed"] == 4
    assert st["recycles"] >= 2          # 4 requests through 2 slots
    # first tokens come from prefill logits and are tallied separately;
    # tokens_generated counts the decode loop only
    assert st["tokens_generated"] == 4 * (TOKENS - 1)
    assert st["prefill_tokens"] == 4
    assert seq["tokens_generated"] == 4 * (TOKENS - 1)
    assert seq["prefill_tokens"] == 4


def test_engine_slot_recycling_mixed_lengths(params):
    engine = mk_engine(params, slots=2)
    lengths = [2, 5, 3, 6, 1, 4]
    reqs = [engine.make_request(p, n)
            for p, n in zip(prompts(len(lengths), seed=1), lengths)]
    engine.run(reqs)
    st = engine.stats()
    assert all(r.state == "done" for r in reqs)
    assert [len(engine.finalize_request(r)) for r in reqs] == lengths
    assert st["recycles"] >= len(lengths) - engine.ecfg.slots
    assert st["active_slots"] == 0 and st["queue_depth"] == 0
    assert 0 < st["batch_occupancy"] <= 1.0
    # decode batch never re-jits: exactly one traced decode fn in the cache
    assert st["decode_steps"] < sum(lengths)  # batching beat sequential steps


def test_engine_admission_control(params):
    engine = mk_engine(params, slots=2, max_queue=2)
    ok = [engine.submit(engine.make_request(p, 2)) for p in prompts(4)]
    assert ok == [True, True, False, False]
    assert engine.stats()["rejected"] == 2
    # horizon violation and oversized prompt are rejected up front
    too_long = engine.make_request(prompts(1)[0], TOKENS + 99)
    assert not engine.submit(too_long)
    assert "exceeds" in too_long.reason
    big = engine.make_request(list(range(BUCKET + 1)), 2)
    assert not engine.submit(big)
    assert big.state == "rejected"


def test_engine_warm_plan_cache_across_engines(params):
    cache = PlanCache()
    e1 = Engine(CFG, EngineConfig(slots=2, prompt_buckets=(BUCKET,),
                                  max_seq=MAX_SEQ),
                params=params, plan_cache=cache)
    e1.run([e1.make_request(p, 2) for p in prompts(2)])
    misses_after_first = cache.misses
    e2 = Engine(CFG, EngineConfig(slots=2, prompt_buckets=(BUCKET,),
                                  max_seq=MAX_SEQ),
                params=params, plan_cache=cache)
    e2.run([e2.make_request(p, 2) for p in prompts(2)])
    # everything the second engine needed (plan, decode, insert, prefill)
    # was a hit: no re-lowering, no re-jit
    assert cache.misses == misses_after_first
    assert cache.hits >= 4
    assert e2.stats()["plan_cache"]["hit_rate"] > 0


def test_engine_trace_has_lifecycle_events(params):
    engine = mk_engine(params, slots=1)
    reqs = [engine.make_request(p, 2) for p in prompts(2)]
    engine.run(reqs)
    events = [e.get("event") for e in engine.trace if "event" in e]
    passes = [e for e in engine.trace if "pass" in e]
    assert passes, "pass-pipeline trace entries flow through the same list"
    for ev in ("submit", "admit", "finish", "stats"):
        assert ev in events


# ---------------------------------------------------------------- paged KV

from _hyp import given, settings, st  # noqa: E402  (hypothesis or fallback)

from repro.models.layers import (NULL_PAGE, attention_decode,  # noqa: E402
                                 attention_decode_paged)
from repro.runtime.engine import PagedKVAllocator  # noqa: E402

PAGE = 4  # page size for engine tests (MAX_SEQ=16 -> 4 pages per slot)


def mk_paged(params, cfg=CFG, slots=2, num_pages=0, prefill_chunk=0,
             decode_kernel="xla"):
    return Engine(cfg, EngineConfig(slots=slots, prompt_buckets=(BUCKET,),
                                    max_seq=MAX_SEQ, kv_layout="paged",
                                    page_size=PAGE, num_pages=num_pages,
                                    prefill_chunk=prefill_chunk,
                                    decode_kernel=decode_kernel,
                                    interpret=True),
                  params=params, plan_cache=PlanCache())


def run_streams(engine, workload):
    reqs = [engine.make_request(p, n) for p, n in workload]
    engine.run(reqs)
    return [engine.finalize_request(r) for r in reqs], reqs


def mixed_workload(n=6, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, CFG.vocab, size=int(rng.integers(1, BUCKET + 1))
                          ).tolist(), int(rng.integers(1, TOKENS + 1)))
            for _ in range(n)]


@pytest.mark.parametrize("layout", ["dense", "paged_chunked"])
def test_fresh_engines_serve_identical_streams(params, layout):
    """Async dispatch stays on: the host mutates its position vector, page
    tables and policy rows in place right after dispatching a step, so every
    upload must be a snapshot. Before that, four fresh engines serving this
    workload gave four different greedy streams."""
    work = mixed_workload(5, seed=9)
    seq = serve_sequential(CFG, params, [RequestSpec(prompt=tuple(p),
                                                     max_new_tokens=n)
                                         for p, n in work],
                           max_seq=MAX_SEQ, prompt_buckets=(BUCKET,))
    want = [seq["tokens"][i + 1] for i in range(len(work))]
    # one plan cache: only the first engine compiles, so the later ones
    # dispatch back to back, which is when the host ran ahead of uploads
    cache = PlanCache()
    kw = dict(kv_layout="paged", page_size=PAGE, prefill_chunk=PAGE,
              interpret=True) if layout == "paged_chunked" else {}
    for _ in range(5):
        engine = Engine(CFG, EngineConfig(slots=2, prompt_buckets=(BUCKET,),
                                          max_seq=MAX_SEQ, **kw),
                        params=params, plan_cache=cache)
        streams, _ = run_streams(engine, work)
        assert streams == want


def test_snapshot_is_immune_to_host_mutation_under_load():
    """The engine's upload seam: on a busy CPU backend, ``jnp.asarray`` of a
    NumPy array can still be reading the host buffer after it returns, so a
    write right after the call may reach the device value. ``snapshot``
    copies first; the device value is the host state at the call."""
    from repro.runtime.engine import snapshot
    busy = jax.jit(lambda m: (m @ m @ m).sum())
    m = jnp.ones((768, 768))
    for _ in range(40):
        busy(m)
        host = np.zeros((8,), np.int32)
        dev = snapshot(host)
        host[:] = 99
        assert int(dev[0]) == 0


def test_paged_engine_matches_dense_tokens(params):
    work = mixed_workload()
    dense, dreqs = run_streams(mk_engine(params, slots=2), work)
    paged, preqs = run_streams(mk_paged(params, slots=2), work)
    assert dense == paged
    assert all(r.state == "done" for r in preqs)
    st_ = [e for e in (mk_paged(params, slots=2),)][0]  # fresh engine stats keys
    assert st_.stats()["kv_layout"] == "paged"


def test_paged_engine_mha_matches_dense(params):
    """Non-GQA (KV == H) config through both layouts."""
    import dataclasses
    cfg = dataclasses.replace(CFG, n_kv_heads=CFG.n_heads)
    p = api.init_params(cfg, jax.random.key(2))
    work = mixed_workload(4, seed=5)
    dense, _ = run_streams(Engine(cfg, EngineConfig(
        slots=2, prompt_buckets=(BUCKET,), max_seq=MAX_SEQ),
        params=p, plan_cache=PlanCache()), work)
    paged, _ = run_streams(mk_paged(p, cfg=cfg), work)
    assert dense == paged


def test_paged_engine_pallas_kernel_matches(params):
    work = mixed_workload(3, seed=7)
    dense, _ = run_streams(mk_engine(params, slots=2), work)
    paged, _ = run_streams(mk_paged(params, decode_kernel="pallas"), work)
    assert dense == paged


def test_chunked_prefill_matches_dense(params):
    work = mixed_workload(5, seed=9)
    dense, _ = run_streams(mk_engine(params, slots=2), work)
    chunked, reqs = run_streams(mk_paged(params, prefill_chunk=PAGE), work)
    assert dense == chunked
    # prompts longer than one chunk actually went through the chunked path
    engine = mk_paged(params, prefill_chunk=PAGE)
    _, reqs = run_streams(engine, [([1] * BUCKET, 3)])
    assert engine.stats()["prefill_chunks"] == BUCKET // PAGE


def test_paged_overcommit_eviction_recovers(params):
    """Pool smaller than worst-case demand: admission overcommits, decode
    growth evicts, every request still completes with the dense stream."""
    work = [(p, TOKENS) for p in prompts(6)]
    dense, _ = run_streams(mk_engine(params, slots=2), work)
    engine = mk_paged(params, slots=4, num_pages=10)
    paged, reqs = run_streams(engine, work)
    st_ = engine.stats()
    assert st_["evictions"] > 0
    assert all(r.state == "done" for r in reqs)
    assert paged == dense
    # drained engine: every page returned to the free list
    assert st_["pages_in_use"] == 0
    assert engine.allocator.available == engine.num_pages
    assert st_["peak_pages"] <= engine.num_pages


def test_paged_engine_rejects_oversized_and_unpageable(params):
    engine = mk_paged(params, slots=2, num_pages=2)
    req = engine.make_request([1] * BUCKET, TOKENS)  # needs 4 pages > 2
    assert not engine.submit(req)
    assert "pages" in req.reason
    from repro.configs import smoke_config
    with pytest.raises(NotImplementedError):
        Engine(smoke_config("xlstm-350m"),
               EngineConfig(kv_layout="paged"), plan_cache=PlanCache())


def test_paged_windowed_attention_matches_rolling():
    """Layer-level: the paged logical-order window mask reproduces the dense
    rolling-cache window attention over the same logical keys."""
    rng = np.random.default_rng(0)
    B, S, H, KV, hd, W, ps = 2, 16, 4, 2, 8, 6, 4
    pos = np.asarray([7, 15], np.int32)
    kl = rng.normal(size=(B, S, KV, hd)).astype(np.float32)
    vl = rng.normal(size=(B, S, KV, hd)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)).astype(np.float32))
    new = (jnp.asarray(rng.normal(size=(B, 1, KV, hd)).astype(np.float32)),
           jnp.asarray(rng.normal(size=(B, 1, KV, hd)).astype(np.float32)))
    # dense rolling layout: slot p % W holds logical position p
    k_roll = np.zeros((B, W, KV, hd), np.float32)
    v_roll = np.zeros((B, W, KV, hd), np.float32)
    for b in range(B):
        for p in range(max(0, pos[b] - W), pos[b]):
            k_roll[b, p % W] = kl[b, p]
            v_roll[b, p % W] = vl[b, p]
    # paged logical layout
    P = S // ps
    pool_k = np.zeros((B * P + 1, ps, KV, hd), np.float32)
    pool_v = np.zeros((B * P + 1, ps, KV, hd), np.float32)
    pt = np.zeros((B, P), np.int32)
    for b in range(B):
        for i in range(P):
            phys = 1 + b * P + i
            pt[b, i] = phys
            pool_k[phys] = kl[b, i * ps:(i + 1) * ps]
            pool_v[phys] = vl[b, i * ps:(i + 1) * ps]
    want = attention_decode(q, jnp.asarray(k_roll), jnp.asarray(v_roll),
                            jnp.asarray(pos), window=W, new_kv=new)
    got = attention_decode_paged(q, jnp.asarray(pool_k), jnp.asarray(pool_v),
                                 jnp.asarray(pt), jnp.asarray(pos),
                                 window=W, new_kv=new)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_program_fingerprint_and_plan():
    from repro.core.lower import plan_from_program
    from repro.core.printer import to_mlir
    shape = decode_shape()
    fp_dense = program_fingerprint(build_program(CFG, shape))
    prog = build_program(CFG, shape, page_geometry=(15, PAGE, 4))
    fp_paged = program_fingerprint(prog)
    fp_other = program_fingerprint(
        build_program(CFG, shape, page_geometry=(15, 2 * PAGE, 2)))
    assert len({fp_dense, fp_paged, fp_other}) == 3
    text = to_mlir(prog)
    assert "allocator(paged_kv_alloc)" in text
    assert "upir.memory_alloc" in text and "upir.memory_dealloc" in text
    assert "mm(page_size(4) num_pages(15) pages_per_slot(4))" in text
    assert "mm(page_map)" in text
    plan = plan_from_program(run_pipeline(prog))
    assert plan.page_geometry == (15, PAGE, 4)
    assert plan_from_program(run_pipeline(build_program(CFG, shape))) \
        .page_geometry is None


def test_paged_plan_cache_warm_across_engines(params):
    cache = PlanCache()
    ecfg = EngineConfig(slots=2, prompt_buckets=(BUCKET,), max_seq=MAX_SEQ,
                        kv_layout="paged", page_size=PAGE)
    e1 = Engine(CFG, ecfg, params=params, plan_cache=cache)
    e1.run([e1.make_request(p, 2) for p in prompts(2)])
    misses = cache.misses
    e2 = Engine(CFG, ecfg, params=params, plan_cache=cache)
    e2.run([e2.make_request(p, 2) for p in prompts(2)])
    assert cache.misses == misses      # warm: plan, decode, inserts, prefill
    # a dense engine on the same cache must NOT collide with the paged plans
    e3 = Engine(CFG, EngineConfig(slots=2, prompt_buckets=(BUCKET,),
                                  max_seq=MAX_SEQ),
                params=params, plan_cache=cache)
    assert cache.misses > misses


@given(st.lists(st.integers(min_value=-4, max_value=4), min_size=1,
                max_size=50))
@settings(max_examples=30, deadline=None)
def test_paged_allocator_properties(ops):
    """No page leaked, none double-allocated, double-free raises."""
    total = 12
    alloc = PagedKVAllocator(total)
    live: list = []
    for op in ops:
        if op > 0:
            got = alloc.alloc(op)
            if got is None:
                assert alloc.available < op   # all-or-nothing
            else:
                assert len(set(got)) == op
                assert all(NULL_PAGE < p <= total for p in got)
                for g in live:
                    assert not set(got) & set(g)
                live.append(got)
        elif op < 0 and live:
            grp = live.pop(-op % len(live))
            alloc.free(grp)
            with pytest.raises(ValueError):
                alloc.free(grp)
        assert alloc.available + alloc.in_use == total
        assert alloc.in_use == sum(len(g) for g in live)
    for g in live:
        alloc.free(g)
    assert alloc.available == total


# ---------------------------------------------- accounting (decode-only)


def test_sequential_rejects_over_horizon(params):
    """Over-horizon requests are marked rejected and excluded from the
    throughput denominator (previously: silent [] + counted)."""
    e = mk_engine(params, slots=1)
    good = e.make_request(prompts(1)[0], 2)
    too_long = e.make_request(prompts(1)[0], TOKENS + 99)
    huge = e.make_request(list(range(BUCKET + 5)), 2)
    out = serve_sequential(CFG, params, [good, too_long, huge],
                           max_seq=MAX_SEQ, prompt_buckets=(BUCKET,),
                           warmup=False)
    assert out["rejected"] == 2 and out["served"] == 1
    assert too_long.state == "rejected" and "exceeds" in too_long.reason
    assert huge.state == "rejected"
    assert good.rid in out["tokens"] and too_long.rid not in out["tokens"]
    assert out["tokens_generated"] == 1     # max_new=2 -> 1 decode token
    assert out["prefill_tokens"] == 1


def test_prefill_first_token_accounted_separately(params):
    """1-token requests complete at prefill: decode throughput must be 0."""
    engine = mk_engine(params, slots=2)
    reqs = [engine.make_request(p, 1) for p in prompts(3)]
    engine.run(reqs)
    st_ = engine.stats()
    assert st_["completed"] == 3
    assert st_["tokens_generated"] == 0
    assert st_["prefill_tokens"] == 3
    assert st_["decode_steps"] == 0
    assert all(len(engine.finalize_request(r)) == 1 for r in reqs)


# ----------------------------------------- ModelFamily protocol (capabilities)

from repro.models.api import CapabilityError, KernelSpec  # noqa: E402
from repro.runtime.sampling import SamplingParams  # noqa: E402


def test_family_spec_capabilities():
    assert api.family_spec(CFG).capabilities == ("pageable",)
    assert api.family_spec(smoke_config("xlstm-350m")).capabilities == \
        ("stateful_cache",)
    assert api.family_spec(smoke_config("zamba2-2.7b")).capabilities == \
        ("stateful_cache",)
    assert api.family_spec(smoke_config("whisper-large-v3")).capabilities == \
        ("needs_encoder_memory",)
    assert api.supports_paged_kv(CFG)
    assert not api.supports_paged_kv(smoke_config("whisper-large-v3"))


def test_capability_errors_are_uniform():
    wcfg = smoke_config("whisper-large-v3")
    with pytest.raises(CapabilityError, match="pageable"):
        api.paged_cache_specs(wcfg, 4, 4)
    with pytest.raises(CapabilityError, match="pageable"):
        api.decode_step_paged(wcfg, None, None, None, {})
    with pytest.raises(CapabilityError, match="needs_encoder_memory"):
        api.encode(CFG, None, {})
    with pytest.raises(CapabilityError, match="pageable"):
        api.prefill_chunk(smoke_config("xlstm-350m"), None, None, None, {}, 0)


def test_capabilities_rendered_into_program_and_plan():
    from repro.core.lower import plan_from_program
    from repro.core.printer import to_mlir
    shape = decode_shape()
    text = to_mlir(build_program(CFG, shape))
    assert "caps(pageable)" in text
    wcfg = smoke_config("whisper-large-v3")
    wtext = to_mlir(build_program(wcfg, shape))
    assert "caps(needs_encoder_memory)" in wtext
    assert "caps(encoder_memory)" in wtext        # explicit per-slot buffer
    stext = to_mlir(build_program(smoke_config("xlstm-350m"), shape))
    assert "caps(stateful_cache)" in stext
    plan = plan_from_program(run_pipeline(build_program(CFG, shape)))
    assert plan.capabilities == ("pageable",)
    wplan = plan_from_program(run_pipeline(build_program(wcfg, shape)))
    assert wplan.capabilities == ("needs_encoder_memory",)


def test_kernel_spec_validated_once_at_construction(params):
    with pytest.raises(ValueError, match="attn_impl"):
        Engine(CFG, EngineConfig(slots=2, prompt_buckets=(BUCKET,),
                                 max_seq=MAX_SEQ, decode_kernel="cuda"),
               params=params, plan_cache=PlanCache())
    with pytest.raises(ValueError, match="kv_layout"):
        Engine(CFG, EngineConfig(slots=2, prompt_buckets=(BUCKET,),
                                 max_seq=MAX_SEQ, kv_layout="block"),
               params=params, plan_cache=PlanCache())
    # the knobs live in EngineConfig now, not in the model-API signature
    import inspect
    sig = inspect.signature(api.decode_step_paged)
    assert "interpret" not in sig.parameters
    assert "attn_impl" not in sig.parameters
    assert "kernel" in sig.parameters
    with pytest.raises(ValueError):
        KernelSpec(attn_impl="nope")


# ----------------------------------------------- make_request validation


def test_make_request_rejects_degenerate_inputs(params):
    engine = mk_engine(params)
    with pytest.raises(ValueError, match="empty prompt"):
        engine.make_request([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.make_request([1, 2], 0)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.make_request([1, 2], -3)
    with pytest.raises(ValueError, match="eos_id"):
        engine.make_request([1, 2], 2, eos_id=CFG.vocab)
    with pytest.raises(ValueError, match="encoder_input"):
        engine.make_request([1, 2], 2, encoder_input=np.zeros((3, 3)))


# --------------------------------------------------- sampling + EOS decode


def sampled_workload(n=4, seed=11, sampling=None, eos_id=None):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, CFG.vocab, size=BUCKET).tolist(), TOKENS,
             sampling, eos_id) for _ in range(n)]


def run_workload(engine, work):
    reqs = [engine.make_request(p, n, sampling=s, eos_id=e)
            for p, n, s, e in work]
    engine.run(reqs)
    return [engine.finalize_request(r) for r in reqs], reqs


def test_greedy_streams_bitwise_stable_with_sampling_api(params):
    """Regression: the sampling-capable decode path must leave greedy dense
    AND paged streams bitwise-identical to the sequential reference."""
    work = mixed_workload()
    dense, dreqs = run_streams(mk_engine(params, slots=2), work)
    paged, _ = run_streams(mk_paged(params, slots=2), work)
    chunked, _ = run_streams(mk_paged(params, prefill_chunk=PAGE), work)
    seq = serve_sequential(CFG, params, dreqs, max_seq=MAX_SEQ,
                           prompt_buckets=(BUCKET,), warmup=False)
    want = [seq["tokens"][r.rid] for r in dreqs]
    assert dense == want
    assert paged == want
    assert chunked == want


def test_sampled_streams_deterministic_replay(params):
    sp = SamplingParams(temperature=1.0, top_k=8, seed=42)
    work = sampled_workload(sampling=sp)
    a, _ = run_workload(mk_engine(params, slots=2), work)
    b, _ = run_workload(mk_engine(params, slots=2), work)
    assert a == b
    # a different seed draws a different stream; greedy differs too
    other, _ = run_workload(
        mk_engine(params, slots=2),
        sampled_workload(sampling=SamplingParams(temperature=1.0, top_k=8,
                                                 seed=43)))
    greedy, _ = run_workload(mk_engine(params, slots=2), sampled_workload())
    assert a != other
    assert a != greedy


def test_sampled_matches_sequential(params):
    """Sampling is a pure function of (request key, position), shared with
    the sequential baseline — batched and one-at-a-time streams agree."""
    sp = SamplingParams(temperature=0.9, top_k=4, seed=7)
    work = sampled_workload(sampling=sp)
    streams, reqs = run_workload(mk_engine(params, slots=2), work)
    seq = serve_sequential(CFG, params, reqs, max_seq=MAX_SEQ,
                           prompt_buckets=(BUCKET,), warmup=False)
    assert streams == [seq["tokens"][r.rid] for r in reqs]


def test_top_p_sampled_matches_sequential_and_moves_streams(params):
    """Nucleus sampling rides the same key schedule: engine == sequential,
    replay is deterministic, and a tight top_p actually changes the stream
    relative to the unfiltered policy."""
    sp = SamplingParams(temperature=1.2, top_p=0.7, seed=5)
    work = sampled_workload(sampling=sp)
    streams, reqs = run_workload(mk_engine(params, slots=2), work)
    seq = serve_sequential(CFG, params, reqs, max_seq=MAX_SEQ,
                           prompt_buckets=(BUCKET,), warmup=False)
    assert streams == [seq["tokens"][r.rid] for r in reqs]
    replay, _ = run_workload(mk_engine(params, slots=2), work)
    assert streams == replay
    wide, _ = run_workload(
        mk_engine(params, slots=2),
        sampled_workload(sampling=SamplingParams(temperature=1.2, seed=5)))
    assert streams != wide
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(temperature=1.0, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(temperature=1.0, top_p=1.5)


def test_sampled_eviction_by_recompute_replays(params):
    """Paged eviction leans on the admission-time PRNG key snapshot: a
    sampled stream recomputed after eviction must reproduce exactly."""
    sp = SamplingParams(temperature=1.0, seed=7)
    work = [(p, TOKENS, sp, None) for p in prompts(6)]
    tight, treqs = run_workload(mk_paged(params, slots=4, num_pages=10), work)
    roomy, _ = run_workload(mk_paged(params, slots=4), work)
    assert tight == roomy
    assert all(r.state == "done" for r in treqs)


def test_sampled_chunked_prefill_matches_oneshot(params):
    """The chunked-prefill first token samples at the same position as the
    one-shot prefill, so streams agree chunked or not."""
    sp = SamplingParams(temperature=1.2, top_k=16, seed=3)
    work = [(p, TOKENS, sp, None) for p in prompts(4, seed=13)]
    oneshot, _ = run_workload(mk_paged(params, slots=2), work)
    chunked, _ = run_workload(mk_paged(params, prefill_chunk=PAGE), work)
    assert oneshot == chunked


def test_eos_terminates_streams(params):
    greedy, _ = run_workload(mk_engine(params, slots=2), sampled_workload())
    eos = greedy[0][1]               # a token we know the stream emits
    engine = Engine(CFG, EngineConfig(slots=2, prompt_buckets=(BUCKET,),
                                      max_seq=MAX_SEQ, eos_poll_every=1),
                    params=params, plan_cache=PlanCache())
    streams, reqs = run_workload(engine, sampled_workload(eos_id=eos))
    for g, s, r in zip(greedy, streams, reqs):
        assert r.state == "done"
        if eos in g:
            assert s == g[:g.index(eos) + 1]      # truncated at first EOS
            assert r.reason == "eos" or len(s) == len(g)
        else:
            assert s == g
    st_ = engine.stats()
    assert st_["eos_finished"] >= 1
    assert st_["tokens_generated"] < 4 * (TOKENS - 1)  # early finish saved work


def test_eos_without_poll_still_truncates(params):
    """eos_poll_every=0: the host never polls mid-run; the device-side mask
    freezes the stream and finalize truncates."""
    greedy, _ = run_workload(mk_engine(params, slots=2), sampled_workload())
    eos = greedy[0][1]
    engine = Engine(CFG, EngineConfig(slots=2, prompt_buckets=(BUCKET,),
                                      max_seq=MAX_SEQ, eos_poll_every=0),
                    params=params, plan_cache=PlanCache())
    streams, reqs = run_workload(engine, sampled_workload(eos_id=eos))
    for g, s in zip(greedy, streams):
        assert s == (g[:g.index(eos) + 1] if eos in g else g)
    assert engine.stats()["eos_finished"] == 0    # nobody polled


# -------------------------------------------------- encoder-decoder serving


WCFG = smoke_config("whisper-large-v3")
W_BUCKET, W_TOKENS, W_MAX_SEQ = 8, 5, 13


@pytest.fixture(scope="module")
def wparams():
    return api.init_params(WCFG, jax.random.key(1))


def whisper_work(n=4, seed=2):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, WCFG.vocab, size=int(rng.integers(2, W_BUCKET + 1))
                          ).tolist(),
             int(rng.integers(1, W_TOKENS + 1)),
             (rng.normal(size=(WCFG.encdec.enc_seq, WCFG.d_model))
              * 0.02).astype(np.float32))
            for _ in range(n)]


def mk_whisper(wparams, **kw):
    return Engine(WCFG, EngineConfig(slots=2, prompt_buckets=(W_BUCKET,),
                                     max_seq=W_MAX_SEQ, **kw),
                  params=wparams, plan_cache=PlanCache())


def test_encdec_serves_through_engine(wparams):
    """Whisper end-to-end through the same continuous-batching loop: per-slot
    encoder memory filled at admission, streams match the sequential path."""
    engine = mk_whisper(wparams)
    work = whisper_work()
    reqs = [engine.make_request(p, n, encoder_input=f) for p, n, f in work]
    engine.run(reqs)
    streams = [engine.finalize_request(r) for r in reqs]
    assert all(r.state == "done" for r in reqs)
    assert [len(s) for s in streams] == [n for _, n, _ in work]
    sreqs = [engine.make_request(p, n, encoder_input=f) for p, n, f in work]
    seq = serve_sequential(WCFG, wparams, sreqs, max_seq=W_MAX_SEQ,
                           prompt_buckets=(W_BUCKET,), warmup=False)
    assert streams == [seq["tokens"][r.rid] for r in sreqs]
    assert engine.stats()["capabilities"] == ["needs_encoder_memory"]
    # the per-slot encoder-memory buffer exists and was written
    assert engine.enc_memory.shape == (2, WCFG.encdec.enc_seq, WCFG.d_model)
    assert float(jnp.abs(engine.enc_memory).sum()) > 0


def test_encdec_sampled_eos_decode(wparams):
    """Acceptance: whisper serves with EOS-terminated *sampled* decode."""
    engine = mk_whisper(wparams, eos_poll_every=1)
    sp = SamplingParams(temperature=1.0, seed=5)
    work = whisper_work(3, seed=9)
    base = [engine.make_request(p, W_TOKENS, sampling=sp, encoder_input=f)
            for p, _, f in work]
    engine.run(base)
    ref = [engine.finalize_request(r) for r in base]
    eos = ref[0][0]                  # first sampled token => instant EOS hit
    e2 = mk_whisper(wparams, eos_poll_every=1)
    reqs = [e2.make_request(p, W_TOKENS, sampling=sp, eos_id=eos,
                            encoder_input=f) for p, _, f in work]
    e2.run(reqs)
    streams = [e2.finalize_request(r) for r in reqs]
    for rf, s in zip(ref, streams):
        assert s == (rf[:rf.index(eos) + 1] if eos in rf else rf)
    assert all(r.state == "done" for r in reqs)


def test_encdec_requires_encoder_input_and_rejects_paged(wparams):
    engine = mk_whisper(wparams)
    with pytest.raises(ValueError, match="needs_encoder_memory"):
        engine.make_request([1, 2], 2)
    with pytest.raises(CapabilityError, match="pageable"):
        Engine(WCFG, EngineConfig(slots=2, prompt_buckets=(W_BUCKET,),
                                  max_seq=W_MAX_SEQ, kv_layout="paged"),
               plan_cache=PlanCache())
    # non-encdec families reject stray encoder inputs
    dense = mk_engine(api.init_params(CFG, jax.random.key(0)))
    with pytest.raises(ValueError, match="encoder_input"):
        dense.make_request([1, 2], 2,
                           encoder_input=np.zeros((4, 4), np.float32))


# ------------------------------------------------- stats field semantics


def test_stats_rejected_vs_evicted_vs_finished(params):
    """The three terminal accountings never bleed into each other."""
    engine = mk_paged(params, slots=4, num_pages=10)
    ok = [engine.make_request(p, TOKENS) for p in prompts(6)]
    bad = engine.make_request(list(range(BUCKET + 1)), 2)   # over bucket
    assert not engine.submit(bad)
    engine.run(ok)
    st_ = engine.stats()
    assert st_["submitted"] == 7                  # 6 served + 1 rejected
    assert st_["rejected"] == 1
    assert st_["completed"] == 6
    assert st_["evictions"] > 0
    # eviction requeues internally: it must not inflate submitted/completed
    assert st_["completed"] + st_["rejected"] == st_["submitted"]
    assert st_["eos_finished"] == 0
    assert bad.state == "rejected" and all(r.state == "done" for r in ok)


def test_stats_tokens_per_s_counts_decode_only(params):
    engine = mk_engine(params, slots=2)
    reqs = [engine.make_request(p, n)
            for p, n in zip(prompts(3), (1, 4, 6))]
    engine.run(reqs)
    st_ = engine.stats()
    # one prefill token per request; decode tokens exclude them
    assert st_["prefill_tokens"] == 3
    assert st_["tokens_generated"] == (1 - 1) + (4 - 1) + (6 - 1)
    assert st_["elapsed_s"] > 0
    assert st_["tokens_per_s"] == pytest.approx(
        st_["tokens_generated"] / st_["elapsed_s"])


def test_reset_stats_zeroes_counters_keeps_artifacts(params):
    engine = mk_engine(params, slots=2)
    engine.run([engine.make_request(p, 3) for p in prompts(2)])
    assert engine.stats()["completed"] == 2
    misses = engine.plan_cache.misses
    engine.reset_stats()
    st_ = engine.stats()
    for k in ("decode_steps", "prefills", "recycles", "submitted",
              "completed", "rejected", "eos_finished",
              "tokens_generated", "prefill_tokens", "peak_concurrent"):
        assert st_[k] == 0, k
    assert st_["elapsed_s"] == 0.0 and st_["tokens_per_s"] == 0.0
    # compiled artifacts survive: a rerun costs no new plan-cache misses
    engine.run([engine.make_request(p, 3) for p in prompts(2)])
    assert engine.plan_cache.misses == misses
    assert engine.stats()["completed"] == 2
