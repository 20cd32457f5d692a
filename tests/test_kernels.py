"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or fixed-seed fallback

from repro.kernels import ops, ref

KEY = jax.random.key(0)


def rnd(shape, dtype=jnp.float32, scale=1.0, seed=0):
    return (jax.random.normal(jax.random.key(seed), shape) * scale).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-1, atol=2e-1)}


@pytest.mark.parametrize("n,block", [(512, 128), (4096, 1024), (2048, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_axpy(n, block, dtype):
    x, y = rnd((n,), dtype, seed=1), rnd((n,), dtype, seed=2)
    out = ops.axpy(2.5, x, y, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.axpy(jnp.asarray(2.5, dtype), x, y),
                                          np.float32), **TOL[dtype])


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (256, 256, 256, 128, 128, 128),
    (512, 384, 256, 128, 128, 128),
    (128, 512, 128, 128, 128, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul(m, k, n, bm, bn, bk, dtype):
    a, b = rnd((m, k), dtype, 0.3, 3), rnd((k, n), dtype, 0.3, 4)
    out = ops.matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.matmul(a, b), np.float32),
                               **TOL[dtype])


@pytest.mark.parametrize("m,k", [(512, 512), (1024, 256)])
def test_matvec(m, k):
    a, x = rnd((m, k), seed=5), rnd((k,), seed=6)
    out = ops.matvec(a, x, bm=256, bk=256, interpret=True)
    np.testing.assert_allclose(out, ref.matvec(a, x), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("m,n,bm,bn", [(256, 256, 128, 128), (128, 384, 64, 128)])
def test_stencil(m, n, bm, bn):
    u = rnd((m, n), seed=7)
    np.testing.assert_allclose(ops.stencil2d(u, bm=bm, bn=bn, interpret=True),
                               ref.stencil2d(u), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,H,hd,bq,bk", [
    (2, 256, 4, 64, 64, 64),
    (1, 512, 2, 32, 128, 256),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, S, H, hd, bq, bk, causal):
    q = rnd((B, S, H, hd), scale=0.3, seed=8)
    k = rnd((B, S, H, hd), scale=0.3, seed=9)
    v = rnd((B, S, H, hd), scale=0.3, seed=10)
    out = ops.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk, interpret=True)
    np.testing.assert_allclose(out, ref.flash_attention(q, k, v, causal=causal),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_matches_model_chunked():
    from repro.models.layers import attention_chunked
    q = rnd((2, 256, 4, 32), scale=0.3, seed=11)
    k = rnd((2, 256, 4, 32), scale=0.3, seed=12)
    v = rnd((2, 256, 4, 32), scale=0.3, seed=13)
    a = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    b = attention_chunked(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 256, 4, 16, 8, 64),
    (1, 128, 2, 32, 16, 32),
])
def test_ssm_scan(B, S, H, P, N, chunk):
    x = rnd((B, S, H, P), scale=0.5, seed=14)
    dt = jax.nn.softplus(rnd((B, S, H), seed=15))
    A = -jnp.exp(jax.random.uniform(jax.random.key(16), (H,), maxval=1.0))
    Bm = rnd((B, S, N), scale=0.5, seed=17)
    Cm = rnd((B, S, N), scale=0.5, seed=18)
    y = ops.ssm_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    y_ref, _ = ref.ssm_chunk_scan(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, y_ref, rtol=2e-3, atol=2e-3)


# ------------------------------------------------ hypothesis shape sweeps


@given(st.integers(1, 16), st.integers(1, 8))
@settings(max_examples=15, deadline=None)
def test_axpy_any_blockcount(nblocks, scale):
    n = 128 * nblocks
    x, y = rnd((n,), seed=20), rnd((n,), seed=21)
    out = ops.axpy(float(scale), x, y, block=128, interpret=True)
    np.testing.assert_allclose(out, ref.axpy(float(scale), x, y),
                               rtol=2e-5, atol=2e-5)


@given(st.sampled_from([128, 256, 384]), st.sampled_from([128, 256]),
       st.sampled_from([128, 256]))
@settings(max_examples=10, deadline=None)
def test_matmul_shape_sweep(m, k, n):
    a, b = rnd((m, k), scale=0.3, seed=22), rnd((k, n), scale=0.3, seed=23)
    out = ops.matmul(a, b, bm=128, bn=128, bk=128, interpret=True)
    np.testing.assert_allclose(out, ref.matmul(a, b), rtol=2e-4, atol=2e-4)


# ------------------------------------------------ paged-attention decode


def _paged_pool(B, S, KV, hd, page_size, seed=30):
    """Random pool + a shuffled page table covering S logical positions."""
    P = S // page_size
    NP = B * P + 1                       # + reserved null page 0
    k = rnd((NP, page_size, KV, hd), seed=seed)
    v = rnd((NP, page_size, KV, hd), seed=seed + 1)
    perm = np.random.default_rng(seed).permutation(np.arange(1, NP))
    pt = jnp.asarray(perm[:B * P].reshape(B, P).astype(np.int32))
    return k, v, pt


@pytest.mark.parametrize("KV,window", [(4, 0), (2, 0), (2, 10)])
def test_paged_attention_kernel_vs_xla(KV, window):
    from repro.kernels.paged_attention import paged_attention_decode
    from repro.models.layers import attention_decode_paged

    B, S, H, hd, ps = 3, 32, 4, 16, 8
    q = rnd((B, 1, H, hd), seed=40)
    k_pages, v_pages, pt = _paged_pool(B, S, KV, hd, ps, seed=41)
    pos = jnp.asarray([0, 13, 31], jnp.int32)
    new_kv = (rnd((B, 1, KV, hd), seed=42), rnd((B, 1, KV, hd), seed=43))
    for nkv in (None, new_kv):
        want = attention_decode_paged(q, k_pages, v_pages, pt, pos,
                                      window=window, new_kv=nkv)
        got = paged_attention_decode(q, k_pages, v_pages, pt, pos,
                                     window=window, new_kv=nkv,
                                     interpret=True)
        assert not bool(jnp.isnan(got).any())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
