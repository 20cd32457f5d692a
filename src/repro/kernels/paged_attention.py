"""Paged-attention decode Pallas kernel — block-gather through a page table.

One token per sequence attends to a KV cache stored as fixed-size physical
pages: the page table (a scalar-prefetch operand, resident before the kernel
body runs) drives the ``BlockSpec`` index maps, so each grid step DMAs exactly
one physical page of K and V — the kernel never materializes the gathered
logical view that the XLA path (``models.layers.attention_decode_paged``)
builds. Online softmax over pages with running (m, l, acc) in VMEM scratch,
same recurrence as ``flash_attention.py``.

Grid: (batch, kv_heads, logical_pages); pages are the innermost (sequential)
dim so the q tile and accumulators stay VMEM-resident while pages stream.
GQA is native: the q block is the [G, hd] group of one KV head, so K/V pages
are loaded once per KV head, not per q head.

The kernel computes attention over *cached* tokens only (positions < limit);
the deferred-insert merge of the current token's K/V (see
``attention_decode``'s ``new_kv`` contract) happens outside in
``paged_attention_decode`` from the kernel's (out, m, l) partials.

Validated in interpret mode against the XLA paged path and the dense cache —
tests assert identical greedy token streams through the serving engine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _paged_decode_kernel(pt_ref, limit_ref, q_ref, k_ref, v_ref,
                         o_ref, m_out_ref, l_out_ref,
                         m_ref, l_ref, acc_ref, *,
                         scale, page_size, kv_heads, group, pages, window):
    b = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        # NEG (not -inf) so an all-masked table leaves exact zeros, no NaNs
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                       # [H, hd]
    k = k_ref[0]                                       # [PS*KV, hd]
    v = v_ref[0]
    # one page holds every KV head, rows interleaved (token-major): row r is
    # token r // KV of head r % KV. All heads score against the whole page in
    # one MXU pass and the cross-head entries are masked out.
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    shape = (kv_heads * group, page_size * kv_heads)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    limit = limit_ref[b]
    kpos = ip * page_size + col // kv_heads
    valid = (col % kv_heads == row // group) & (kpos < limit)
    if window:
        valid &= kpos >= limit - window
    s = jnp.where(valid, s, NEG)
    m_prev = m_ref[...]                                # [H, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # explicit re-mask: when every entry so far is masked, m_new == NEG and
    # exp(s - m_new) would be 1 for masked entries
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ip == pages - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]


def paged_attention_partial(q, k_pages, v_pages, page_table, limit, *,
                            window: int = 0, interpret: bool = False):
    """Cache-only paged attention with softmax partials.

    q: [B,1,H,hd]; k_pages/v_pages: [NP,PS,KV,hd]; page_table: [B,P] int32;
    limit: [B] — positions ``< limit`` (and ``>= limit - window`` when
    windowed) are attended. Returns (out [B,KV,G,hd] normalized, m [B,KV,G],
    l [B,KV,G]) so callers can merge more keys online.

    Each grid step DMAs one whole physical page, all KV heads at once, viewed
    as ``[PS*KV, hd]``: every block's trailing dims are the array's own, which
    the TPU's (8, 128) tiling accepts at any head count and head width.
    """
    B, _, H, hd = q.shape
    NP, PS, KV, _ = k_pages.shape
    P = page_table.shape[1]
    G = H // KV
    scale = 1.0 / np.sqrt(hd)
    qf = q.reshape(B, H, hd)
    kf = k_pages.reshape(NP, PS * KV, hd)
    vf = v_pages.reshape(NP, PS * KV, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, i, pt, lim: (b, 0, 0)),
            pl.BlockSpec((1, PS * KV, hd),
                         lambda b, i, pt, lim: (pt[b, i], 0, 0)),
            pl.BlockSpec((1, PS * KV, hd),
                         lambda b, i, pt, lim: (pt[b, i], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, H, hd), lambda b, i, pt, lim: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, i, pt, lim: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, i, pt, lim: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    out, m, l = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, page_size=PS,
                          kv_heads=KV, group=G, pages=P, window=window),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        interpret=interpret,
    )(page_table, limit, qf, kf, vf)
    return (out.reshape(B, KV, G, hd), m.reshape(B, KV, G),
            l.reshape(B, KV, G))


def paged_attention_decode(q, k_pages, v_pages, page_table, pos, *,
                           window: int = 0, new_kv=None,
                           interpret: bool = False):
    """Drop-in kernel counterpart of ``attention_decode_paged``.

    Same signature/semantics: ``new_kv=(k_new, v_new)`` runs deferred-insert
    (cache positions ``< pos``; the new token's K/V merged online outside the
    kernel); without it, positions ``<= pos`` must already be in the pool.
    Returns [B,1,H,hd].
    """
    B, _, H, hd = q.shape
    KV = k_pages.shape[2]
    G = H // KV
    limit = (pos if new_kv is not None else pos + 1).astype(jnp.int32)
    o_c, m_c, l_c = paged_attention_partial(
        q, k_pages, v_pages, page_table, limit, window=window,
        interpret=interpret)
    if new_kv is None:
        return o_c.reshape(B, 1, H, hd)
    k_new, v_new = new_kv
    qg = q.reshape(B, KV, G, hd)
    scale = 1.0 / np.sqrt(hd)
    s_new = jnp.einsum("bkgh,bkh->bkg", qg.astype(jnp.float32),
                       k_new[:, 0].astype(jnp.float32)) * scale
    m_tot = jnp.maximum(m_c, s_new)
    alpha = jnp.exp(m_c - m_tot)                       # [B,KV,G]
    p_n = jnp.exp(s_new - m_tot)
    acc = o_c.astype(jnp.float32) * (l_c * alpha)[..., None] \
        + p_n[..., None] * v_new[:, 0, :, None, :].astype(jnp.float32)
    out = acc / (l_c * alpha + p_n)[..., None]
    return out.astype(q.dtype).reshape(B, 1, H, hd)
