"""Live-block paged decode: the block list, its attention against the
whole-row gather at every width of the ladder, the engine's streams against
the dense engine's, and the fill counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.lower import PlanCache
from repro.models import api
from repro.models.layers import (NULL_PAGE, attention_decode_blocks,
                                 attention_decode_live,
                                 attention_decode_paged, block_pages,
                                 decode_block_ladder, live_block_list,
                                 row_blocks)
from repro.runtime.engine import Engine, EngineConfig, RequestSpec

# ------------------------------------------------------------- layer level

PS, P, C = 4, 12, 2          # 12 pages of 4 tokens a row, blocks of 2 pages
BT = C * PS                  # 8 tokens a block
NB = P // C                  # 6 blocks a row


def paged_batch(rng, positions, H, KV, hd, dtype):
    """A pool holding ``len(positions)`` rows in shuffled physical pages;
    a row whose position is None is inactive (a null table row)."""
    B = len(positions)
    phys = rng.permutation(np.arange(1, B * P + 1))
    table = phys.reshape(B, P).astype(np.int32)
    pos = np.zeros(B, np.int32)
    for b, p in enumerate(positions):
        if p is None:
            table[b] = NULL_PAGE
            pos[b] = 3            # stale: a null row holds no blocks anyway
        else:
            pos[b] = p
    shape = (B * P + 1, PS, KV, hd)
    k = jnp.asarray(rng.normal(size=shape), dtype)
    v = jnp.asarray(rng.normal(size=shape), dtype)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), dtype)
    new = (jnp.asarray(rng.normal(size=(B, 1, KV, hd)), dtype),
           jnp.asarray(rng.normal(size=(B, 1, KV, hd)), dtype))
    return q, k, v, table, pos, new


LAYER_CASES = {
    # name: (H, KV, window, row positions, extra padding entries)
    "gqa": (8, 2, 0, [5, 20, 33, 9], 0),
    "mha": (4, 4, 0, [5, 20, 33, 9], 0),
    "window": (8, 2, 6, [5, 20, 33, 9], 0),
    "inactive_rows": (8, 2, 0, [None, 17, None, 30], 0),
    "page_boundary": (8, 2, 0, [4, 12, 28, 44], 0),
    "block_boundary": (8, 2, 0, [8, 16, 40, 1], 0),
    "long_among_short": (8, 2, 0, [3, 47, 2, 6, 1, 5], 0),
    "padded": (8, 2, 0, [3, 47, None, 6], 7),
    "padded_window": (8, 2, 10, [12, 47, 2, 6], 3),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_live_blocks_match_whole_row(case, dtype):
    H, KV, window, positions, pad = LAYER_CASES[case]
    rng = np.random.default_rng(sorted(LAYER_CASES).index(case))
    q, k, v, table, pos, new = paged_batch(rng, positions, H, KV, 16,
                                           jnp.dtype(dtype))
    live = table[:, 0] != NULL_PAGE
    need = int(np.where(live, row_blocks(pos, BT, NB), 0).sum())
    blocks = live_block_list(jnp.asarray(table), jnp.asarray(pos),
                             n_blocks=need + pad, block_pages=C, page_size=PS)
    assert int((np.asarray(blocks.row) < len(positions)).sum()) == need
    assert int(blocks.count) == need
    got = attention_decode_blocks(q, k, v, blocks, jnp.asarray(pos), new,
                                  window=window)
    want = attention_decode_paged(q, k, v, jnp.asarray(table),
                                  jnp.asarray(pos), window=window, new_kv=new)
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    # a null row's output is never read; the whole-row form reads the null
    # page there, the block form only the new token
    assert np.isfinite(got).all()
    tol = 1e-5 if dtype == "float32" else 8e-3
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)


@pytest.mark.parametrize("positions", [
    [None, None, None, None],        # no live row: the smallest width
    [3, None, 9, 2],                 # one block a row fits the smallest
    [47, 12, None, 20],              # between two widths
    [47, 47, 47, 47],                # every block of every row: the top
], ids=["empty", "smallest", "middle", "top"])
def test_ladder_switch_matches_whole_row(positions):
    rng = np.random.default_rng(len(positions) + sum(p or 0 for p in positions))
    q, k, v, table, pos, new = paged_batch(rng, positions, 8, 2, 16,
                                           jnp.float32)
    widths = decode_block_ladder(len(positions), NB)
    assert len(widths) > 2
    blocks = live_block_list(jnp.asarray(table), jnp.asarray(pos),
                             n_blocks=widths[-1], block_pages=C, page_size=PS)
    got = jax.jit(attention_decode_live, static_argnums=6)(
        q, k, v, blocks, jnp.asarray(pos), new, widths)
    want = attention_decode_paged(q, k, v, jnp.asarray(table),
                                  jnp.asarray(pos), new_kv=new)
    live = table[:, 0] != NULL_PAGE
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)


def test_live_block_list_layout():
    table = np.zeros((3, P), np.int32)
    table[0] = np.arange(100, 100 + P)
    table[2] = np.arange(200, 200 + P)
    pos = np.array([17, 40, 7], np.int32)       # row 1 is inactive
    bl = live_block_list(jnp.asarray(table), jnp.asarray(pos), n_blocks=6,
                         block_pages=C, page_size=PS)
    # row 0: positions <= 17 lie in blocks 0..2; row 2: block 0; padding
    assert np.asarray(bl.row).tolist() == [0, 0, 0, 2, 3, 3]
    assert np.asarray(bl.block).tolist() == [0, 1, 2, 0, 0, 0]
    assert np.asarray(bl.pages).tolist() == [
        [100, 101], [102, 103], [104, 105], [200, 201],
        [NULL_PAGE, NULL_PAGE], [NULL_PAGE, NULL_PAGE]]
    assert int(bl.count) == 4
    head = bl.head(4)
    assert np.asarray(head.row).tolist() == [0, 0, 0, 2]
    assert head.pages.shape == (4, C)


def test_block_geometry():
    assert block_pages(16, 272) == 8            # 128 tokens of 16
    assert block_pages(4, 4) == 4               # never past a row
    assert block_pages(256, 10) == 1            # at least one page
    assert row_blocks(np.array([0, 127, 128, 4351]), 128, 34).tolist() == \
        [1, 1, 2, 34]
    # granite-3-2b in the benchmark: 32 slots of 34 blocks
    ladder = decode_block_ladder(32, 34)
    assert len(ladder) <= 8 and ladder[0] == 32 and ladder[-1] == 32 * 34
    assert list(ladder) == sorted(set(ladder))
    assert decode_block_ladder(4, 1) == (4,)    # one block a row: one width


# ------------------------------------------------------------ engine level

CFG = smoke_config("tinyllama-1.1b")
PAGE = 4
MAX_SEQ = 256                 # 64 pages of 4: two 128-token blocks a row
BUCKETS = (16, 128)


@pytest.fixture(scope="module")
def params():
    return api.init_params(CFG, jax.random.key(0))


def ladder_engine(params, slots=2, **kw):
    return Engine(CFG, EngineConfig(slots=slots, prompt_buckets=BUCKETS,
                                    max_seq=MAX_SEQ, kv_layout="paged",
                                    page_size=PAGE, **kw),
                  params=params, plan_cache=PlanCache())


def mixed_requests(seed=5):
    """Short requests around two that cross a block boundary together."""
    rng = np.random.default_rng(seed)
    out = [RequestSpec(prompt=tuple(rng.integers(0, CFG.vocab, n).tolist()),
                       max_new_tokens=k) for n, k in ((120, 24), (125, 36))]
    for n in (5, 11, 16, 3, 9):
        out.append(RequestSpec(
            prompt=tuple(rng.integers(0, CFG.vocab, n).tolist()),
            max_new_tokens=int(rng.integers(4, 12))))
    return out


def drive(engine, specs):
    reqs = [engine.submit(s) for s in specs]
    while engine.queue or any(r is not None for r in engine.slots_req) \
            or engine._prefilling:
        engine.step()
    return reqs


def serve(engine, specs):
    return [engine.finalize_request(r) for r in drive(engine, specs)]


def test_ladder_engine_matches_dense_and_lowers_nothing(params):
    from jax import monitoring
    paged = ladder_engine(params)
    assert len(paged.decode_widths) >= 2
    # warm up as the benchmark does, at the smallest width only: one
    # request per bucket, one decode step of a one-block row; the one decode
    # program holds every width
    serve(paged, [RequestSpec(prompt=(1,) * BUCKETS[0], max_new_tokens=2),
                  RequestSpec(prompt=(1,) * BUCKETS[1], max_new_tokens=1)])
    assert paged.stats().decode_dispatched_blocks == paged.decode_widths[0]
    lowered = []

    def on(event, duration, **_):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            lowered.append(event)

    monitoring.register_event_duration_secs_listener(on)
    widths = []
    count = paged._live_blocks

    def record(active):
        out = count(active)
        widths.append(out[1])
        return out

    paged._live_blocks = record
    try:
        reqs = drive(paged, mixed_requests())
    finally:
        monitoring.unregister_event_duration_listener(on)
    assert lowered == []
    streams = [paged.finalize_request(r) for r in reqs]
    assert set(widths) == set(paged.decode_widths)
    dense = Engine(CFG, EngineConfig(slots=2, prompt_buckets=BUCKETS,
                                     max_seq=MAX_SEQ),
                   params=params, plan_cache=PlanCache())
    assert streams == serve(dense, mixed_requests())


def test_block_fill_counters(params):
    engine = ladder_engine(params)
    assert engine.decode_widths[0] == 2          # one block per slot
    # a full first block: every decode step holds 2 live blocks of 2
    long = RequestSpec(prompt=tuple(range(128)), max_new_tokens=4)
    serve(engine, [long])
    st = engine.stats()
    assert st.decode_steps == 3
    assert st.decode_live_blocks == st.decode_dispatched_blocks == 6
    assert st.decode_block_fill == 1.0
    # one short row: 1 live block in the 2-block width, per step
    engine.reset_stats()
    serve(engine, [RequestSpec(prompt=(7,) * 10, max_new_tokens=5)])
    st = engine.stats()
    assert (st.decode_steps, st.decode_live_blocks,
            st.decode_dispatched_blocks) == (4, 4, 8)
    assert st.decode_block_fill == 0.5


def test_block_fill_counters_absent_without_live_blocks(params):
    dense = Engine(CFG, EngineConfig(slots=2, prompt_buckets=BUCKETS,
                                     max_seq=MAX_SEQ),
                   params=params, plan_cache=PlanCache())
    assert dense.decode_widths == ()
    assert "decode_block_fill" not in dense.stats()
    pallas = ladder_engine(params, decode_kernel="pallas", interpret=True)
    assert pallas.decode_widths == ()
    assert pallas.stats().decode_dispatched_blocks is None
