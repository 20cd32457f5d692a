"""Shared layer primitives: norms, RoPE, attention paths, MLP variants.

Everything is a pure function over explicit param pytrees. Matmuls run in the
config's compute dtype (bf16 on TPU); normalization statistics and softmax run in
f32. The chunked attention path is the XLA realization of online-softmax (flash)
attention — ``kernels/flash_attention.py`` is the Pallas version of the same
algorithm for real TPUs; both are validated against ``kernels/ref.py``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------- embedding


def embed_lookup(embed, tokens, dtype):
    """Token embedding lookup.

    In distributed traces the lookup is a one-hot matmul (MaxText's iota-embed):
    XLA's SPMD partitioner handles a dot over the model-sharded vocab dim
    cleanly (partial products + psum), whereas a gather from a sharded table
    triggers involuntary full rematerialization (observed on the 16x16 mesh).
    """
    from ..core.act_sharding import distributed
    if distributed():
        onehot = jax.nn.one_hot(tokens, embed.shape[0], dtype=dtype)
        return jnp.einsum("...v,vd->...d", onehot, embed.astype(dtype))
    return embed.astype(dtype)[tokens]


# ------------------------------------------------------------------------- norms


def rmsnorm(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def layernorm(x, w, b=None, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)
    return y + b.astype(x.dtype) if b is not None else y


def norm(x, w, kind: str = "rmsnorm"):
    return rmsnorm(x, w) if kind == "rmsnorm" else layernorm(x, w)


# -------------------------------------------------------------------------- rope


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))                 # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]                        # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- MLP


def mlp_apply(p, x, act: str, glu: bool, dtype):
    x = x.astype(dtype)
    h = jnp.einsum("...d,df->...f", x, p["w1"].astype(dtype))
    h = _act(h, act)
    if glu:
        h = h * jnp.einsum("...d,df->...f", x, p["w3"].astype(dtype))
    return jnp.einsum("...f,fd->...d", h, p["w2"].astype(dtype))


def _act(h, act: str):
    if act == "silu":
        return jax.nn.silu(h)
    if act == "gelu":
        return jax.nn.gelu(h)
    if act == "relu2":
        r = jax.nn.relu(h)
        return r * r
    raise ValueError(act)


# --------------------------------------------------------------------- attention


def gqa_expand(k, H: int):
    """[B,S,KV,hd] -> [B,S,KV,G,hd] view helper factor G = H // KV."""
    B, S, KV, hd = k.shape
    return k, H // KV


def gqa_expand_kv(k, H: int):
    """Expand GQA K/V [B,S,KV,hd] -> [B,S,H,hd] by repeating each group.

    On a TP mesh the q-head count divides the model axis where KV often does
    not (kv=4/8 vs 16 shards); expanding keys/values lets scores shard on the
    head dim instead of replicating attention across the model axis.
    """
    KV = k.shape[2]
    if KV == H:
        return k
    return jnp.repeat(k, H // KV, axis=2)


def attention_full(q, k, v, *, causal: bool, q_offset=0, window: int = 0,
                   bias=None):
    """Plain-softmax attention. q: [B,Sq,H,hd], k/v: [B,Sk,KV,hd]."""
    B, Sq, H, hd = q.shape
    k = gqa_expand_kv(k, H)
    v = gqa_expand_kv(v, H)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    if bias is not None:
        scores = scores + bias
    if causal or window:
        qpos = q_offset + jnp.arange(Sq)[:, None]
        kpos = jnp.arange(k.shape[1])[None, :]
        mask = jnp.ones((Sq, k.shape[1]), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, v)
    return out.reshape(B, Sq, H, hd)


def attention_chunked(q, k, v, *, causal: bool = True, q_chunk: int = 1024,
                      kv_chunk: int = 1024, window: int = 0):
    """Online-softmax attention, double-chunked (XLA flash). Memory O(chunk^2)."""
    B, Sq, H, hd = q.shape
    k = gqa_expand_kv(k, H)
    v = gqa_expand_kv(v, H)
    Sk = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    scale = 1.0 / np.sqrt(hd)

    qs = q.reshape(B, nq, q_chunk, H, hd)
    ks = k.reshape(B, nk, kv_chunk, H, hd)
    vs = v.reshape(B, nk, kv_chunk, H, hd)

    def q_step(_, qi):
        q_i, iq = qi                                   # [B,qc,H,hd]

        def kv_step(carry, kj):
            m, l, acc = carry
            k_j, v_j, jk = kj
            s = jnp.einsum("bqhd,bshd->bhqs", q_i, k_j).astype(jnp.float32)
            s = s * scale
            if causal or window:
                qpos = iq * q_chunk + jnp.arange(q_chunk)[:, None]
                kpos = jk * kv_chunk + jnp.arange(kv_chunk)[None, :]
                ok = jnp.ones_like(kpos <= qpos)
                if causal:
                    ok &= kpos <= qpos
                if window:
                    ok &= kpos > qpos - window
                s = jnp.where(ok[None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqs,bshd->bhqd", p.astype(q.dtype), v_j).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (ks.swapaxes(0, 1), vs.swapaxes(0, 1), jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)               # [B,H,qc,hd]

    _, outs = jax.lax.scan(q_step, None,
                           (qs.swapaxes(0, 1), jnp.arange(nq)))
    # outs: [nq, B, H, qc, hd] -> [B, Sq, H, hd]
    outs = outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, hd)
    return outs


def attention_decode(q, k_cache, v_cache, pos, *, window: int = 0,
                     new_kv=None):
    """One-token attention against a (possibly rolling) KV cache.

    q: [B,1,H,hd]; k_cache/v_cache: [B,S,KV,hd]; pos: [B] absolute position of
    the *new* token. Entries past ``pos`` are masked. With ``window``, cache
    slots hold the last ``window`` positions (rolling), mask handles validity.

    ``new_kv=(k_new, v_new)`` ([B,1,KV,hd] each) runs in *deferred-insert*
    mode: the cache is read-only (positions < pos) and the new token's K/V is
    merged into the softmax on the fly — the caller scatters it into the cache
    once, outside the layer loop (in-loop insert forces XLA to copy the whole
    stacked cache every iteration: §Perf D2).
    """
    S = k_cache.shape[1]
    slot = jnp.arange(S)[None, :]                      # [1,S]
    limit = pos if new_kv is not None else pos + 1
    if window:
        valid = slot < jnp.minimum(limit, window)[:, None]
    else:
        valid = slot < limit[:, None]
    return _attend_cached(q, k_cache, v_cache, valid, new_kv)


def _attend_cached(q, k_cache, v_cache, valid, new_kv):
    """Shared decode-attention core: softmax over cache entries where ``valid``
    ([B,S] bool), optionally merging a deferred new-token K/V online. The dense
    rolling path and the paged path both route here so their arithmetic is
    op-for-op identical (token-stream equality between layouts)."""
    B, S, KV, hd = k_cache.shape
    H = q.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    if new_kv is not None:
        k_new, v_new = new_kv
        s_new = jnp.einsum("bkgh,bskh->bkgs", qg,
                           k_new).astype(jnp.float32)[..., 0] / np.sqrt(hd)
        # online-softmax merge (concatenating scores across the seq-SHARDED
        # dim forces an SPMD gather — measured 2.5x collective blowup): all
        # reductions over S stay local-per-shard + tiny cross-shard reduces
        m = jnp.maximum(scores.max(axis=-1), s_new)          # [B,KV,G]
        p = jnp.exp(scores - m[..., None])
        l_c = p.sum(axis=-1)
        o_c = jnp.einsum("bkgs,bskh->bkgh", p.astype(q.dtype), v_cache)
        p_n = jnp.exp(s_new - m)                             # [B,KV,G]
        o = (o_c.astype(jnp.float32)
             + p_n[..., None] * v_new[:, 0, :, None, :].astype(jnp.float32))
        out = (o / (l_c + p_n)[..., None]).astype(q.dtype)
        return out.reshape(B, 1, H, hd)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v_cache)
    return out.reshape(B, 1, H, hd)


def cache_insert(cache, new, pos, *, window: int = 0):
    """Insert [B,1,KV,hd] into [B,S,KV,hd] at per-example position (rolling if
    windowed).

    Two lowerings (§Perf D1-D3):
      * single-device: vmapped dynamic_update_slice — in-place scatter;
      * distributed: one-hot masked select — a dynamic-index scatter into the
        seq-SHARDED cache dim forces the SPMD partitioner to gather the shard
        boundary (measured 2.5x collective blowup on llama3 decode), while the
        mask form is embarrassingly local. Call it ONCE per step (outside the
        layer scan) — in-loop it rewrites the whole cache per layer (D2).
    """
    from ..core.act_sharding import distributed
    idx = pos % window if window else pos
    if distributed():
        S = cache.shape[1]
        onehot = (jnp.arange(S)[None, :] == idx[:, None])     # [B,S] bool
        return jnp.where(onehot[..., None, None], new, cache)

    def one(c, n, i):
        return jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=0)

    return jax.vmap(one)(cache, new, idx)


# ----------------------------------------------------------------- paged KV

# Physical page 0 is reserved as the *null page*: page-table entries of
# inactive/unmapped slots point at it, so stray scatters land somewhere
# harmless and stray gathers read data that the position mask discards.
NULL_PAGE = 0


def gather_kv_pages(pool, page_table):
    """Gather a logical-order KV view through the page table.

    pool: [NP, PS, KV, hd] physical pages; page_table: [B, P] int32 mapping
    logical page i of sequence b to a physical page. Returns
    [B, P*PS, KV, hd] where row j holds the K/V of logical position j
    (garbage past the sequence length — callers mask by position).
    """
    g = pool[page_table]                               # [B, P, PS, KV, hd]
    B, P, PS, KV, hd = g.shape
    return g.reshape(B, P * PS, KV, hd)


def attention_decode_paged(q, k_pages, v_pages, page_table, pos, *,
                           window: int = 0, new_kv=None):
    """One-token attention against a paged KV pool, over whole rows.

    q: [B,1,H,hd]; k_pages/v_pages: [NP,PS,KV,hd]; page_table: [B,P] int32;
    pos: [B]. Same contract as ``attention_decode`` (including deferred-insert
    ``new_kv``) but the cache is gathered through the page table, and the
    layout is logical-order (non-rolling), so a ``window`` masks positions
    ``[limit - window, limit)`` instead of rolling slots.

    It gathers all ``P`` pages of every row, whatever the row's position.
    The paged decode step reads only the live blocks instead
    (``attention_decode_live``); this whole-row form is its reference.
    """
    k_c = gather_kv_pages(k_pages, page_table)
    v_c = gather_kv_pages(v_pages, page_table)
    S = k_c.shape[1]
    slot = jnp.arange(S)[None, :]
    limit = pos if new_kv is not None else pos + 1
    valid = slot < limit[:, None]
    if window:
        valid &= slot >= (limit - window)[:, None]
    return _attend_cached(q, k_c, v_c, valid, new_kv)


# A live block is ``block_pages`` consecutive logical pages of one row, about
# this many tokens. Paged decode gathers the blocks that hold a row's cached
# positions, not the row's whole horizon.
LIVE_BLOCK_TOKENS = 128
# Most widths of the paged decode step's live-block ladder.
DECODE_WIDTHS = 8


def block_pages(page_size: int, pages_per_row: int) -> int:
    """Pages per live block: ``LIVE_BLOCK_TOKENS`` worth, at least one page
    and at most a whole row."""
    return max(1, min(LIVE_BLOCK_TOKENS // page_size, pages_per_row))


def row_blocks(pos, block_tokens: int, blocks_per_row: int):
    """Live blocks of a decoding row at position ``pos`` (numpy or jax
    array): every block holding a position ``<= pos``. The engine counts
    the step's blocks with the same function the step's block list uses."""
    return (pos // block_tokens + 1).clip(max=blocks_per_row)


def decode_block_ladder(slots: int, blocks_per_row: int) -> Tuple[int, ...]:
    """The widths, in live blocks, of the paged decode attention: geometric
    from one block per slot up to every block of every slot (so any state
    fits), at most ``DECODE_WIDTHS`` of them. A horizon of one block per row
    gives one width."""
    top = slots * blocks_per_row
    ratio = blocks_per_row ** (1 / (DECODE_WIDTHS - 1))
    return tuple(sorted({min(top, math.ceil(slots * ratio ** k))
                         for k in range(DECODE_WIDTHS)}))


class LiveBlocks(NamedTuple):
    """The live KV blocks of a decode batch, ordered by row, then block.

    row [N] int32: owning row, ``B`` on padding entries (past the live
    count), which the per-row merge drops; block [N] int32: the block's
    index within its row; pages [N, c] int32: its physical pages
    (``NULL_PAGE`` on padding); count [] int32: the live entries."""

    row: jax.Array
    block: jax.Array
    pages: jax.Array
    count: jax.Array

    def head(self, n: int) -> "LiveBlocks":
        """The first ``n`` entries: the list at width ``n`` (which holds
        every live entry when ``n >= count``)."""
        return LiveBlocks(self.row[:n], self.block[:n], self.pages[:n],
                          self.count)


def live_block_list(page_table, pos, *, n_blocks: int, block_pages: int,
                    page_size: int) -> LiveBlocks:
    """Build the ``n_blocks``-entry live-block list of a decode batch.

    page_table: [B,P] int32 (null rows for inactive slots); pos: [B]. A row
    with a mapped first page holds ``row_blocks(pos)`` blocks; a null row
    holds none. Entries past ``n_blocks`` are dropped, so callers pick
    ``n_blocks`` at least the batch's total.
    """
    B, P = page_table.shape
    c = block_pages
    nb = -(-P // c)
    table = jnp.pad(page_table, ((0, 0), (0, nb * c - P)),
                    constant_values=NULL_PAGE).reshape(B, nb, c)
    per_row = jnp.where(page_table[:, 0] != NULL_PAGE,
                        row_blocks(pos, c * page_size, nb), 0)
    ends = jnp.cumsum(per_row)                               # [B]
    j = jnp.arange(n_blocks, dtype=ends.dtype)
    row = (ends[None, :] <= j[:, None]).sum(axis=1, dtype=jnp.int32)
    r = jnp.minimum(row, B - 1)
    live = row < B
    block = jnp.where(live, j - (ends[r] - per_row[r]), 0).astype(jnp.int32)
    pages = jnp.where(live[:, None], table[r, block], NULL_PAGE)
    return LiveBlocks(row, block, pages, ends[-1].astype(jnp.int32))


def attention_decode_live(q, k_pages, v_pages, blocks: LiveBlocks, pos,
                          new_kv, widths: Tuple[int, ...], *, window: int = 0):
    """``attention_decode_blocks`` at the smallest of ``widths`` that holds
    ``blocks.count``, chosen on the device: each width is one branch of a
    ``lax.switch`` over the first ``width`` entries of ``blocks`` (built at
    ``widths[-1]``), so one compiled program serves every width."""
    def at(width):
        return lambda q, k_p, v_p, bl, pos, new_kv: attention_decode_blocks(
            q, k_p, v_p, bl.head(width), pos, new_kv, window=window)

    branch = jnp.searchsorted(jnp.asarray(widths, jnp.int32), blocks.count)
    return jax.lax.switch(branch, [at(w) for w in widths],
                          q, k_pages, v_pages, blocks, pos, new_kv)


def attention_decode_blocks(q, k_pages, v_pages, blocks: LiveBlocks, pos,
                            new_kv, *, window: int = 0):
    """One-token attention against a paged KV pool, over live blocks only.

    The contract of ``attention_decode_paged`` with the new token's K/V
    deferred (``new_kv``, as the decode step always passes it), but K/V is
    gathered per live block ([N, c*PS, KV, hd]) instead of per whole row.
    Each block is scored against its row's query (GQA-native) and masked by
    position as the whole-row form masks; the row's softmax maximum is the
    segment maximum over its blocks and the new token, so every block's
    probabilities, sums and value products are those of the whole-row form,
    summed per row in f32.
    """
    B, _, H, hd = q.shape
    _, PS, KV, _ = k_pages.shape
    G = H // KV
    N, c = blocks.pages.shape
    T = c * PS
    k_b = k_pages[blocks.pages].reshape(N, T, KV, hd)
    v_b = v_pages[blocks.pages].reshape(N, T, KV, hd)
    r = jnp.minimum(blocks.row, B - 1)
    lim = jnp.where(blocks.row < B, pos[r], 0)                # [N]
    tpos = blocks.block[:, None] * T + jnp.arange(T)[None, :]  # [N,T]
    valid = tpos < lim[:, None]
    if window:
        valid &= tpos >= (lim - window)[:, None]
    qg = q.reshape(B, KV, G, hd)
    scores = jnp.einsum("nkgh,ntkh->nkgt", qg[r],
                        k_b).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    segment = dict(segment_ids=blocks.row, num_segments=B,
                   indices_are_sorted=True)
    k_new, v_new = new_kv
    s_new = jnp.einsum("bkgh,bskh->bkgs", qg,
                       k_new).astype(jnp.float32)[..., 0] / np.sqrt(hd)
    m = jnp.maximum(jax.ops.segment_max(scores.max(axis=-1), **segment),
                    s_new)                                    # [B,KV,G]
    p = jnp.exp(scores - m[r][..., None])                     # [N,KV,G,T]
    l_c = jax.ops.segment_sum(p.sum(axis=-1), **segment)      # [B,KV,G]
    o_b = jnp.einsum("nkgt,ntkh->nkgh", p.astype(q.dtype), v_b)
    o_c = jax.ops.segment_sum(o_b.astype(jnp.float32), **segment)
    p_n = jnp.exp(s_new - m)                                  # [B,KV,G]
    o = o_c + p_n[..., None] * v_new[:, 0, :, None, :].astype(jnp.float32)
    out = (o / (l_c + p_n)[..., None]).astype(q.dtype)
    return out.reshape(B, 1, H, hd)


def cache_insert_chunk(cache, new, pos):
    """Insert a chunk [B,C,KV,hd] into [B,S,KV,hd] at per-row start positions
    (non-rolling logical layout) — the dense-cache write of the speculative
    verify step. Callers guarantee ``pos + C <= S`` (speculative engines size
    their caches with ``lookahead_k`` slack rows so the update never clamps);
    entries past the accepted prefix are masked by position until the next
    chunk overwrites them, so rejected drafts need no dense rollback.
    """
    def one(c, n, i):
        return jax.lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), i,
                                                   axis=0)

    return jax.vmap(one)(cache, new, pos)


def cache_insert_paged_chunk(pool, new, page_table, pos):
    """Scatter a chunk of C new tokens' K/V into the paged pool, all layers
    at once — the paged-cache write of the speculative verify step.

    pool: [L,NP,PS,KV,hd]; new: [L,B,C,KV,hd]; page_table: [B,P]; pos: [B].
    Token j of row b lands in page ``page_table[b, (pos+j) // PS]`` at offset
    ``(pos+j) % PS``. Callers guarantee the covering pages are mapped (the
    engine allocates ``lookahead_k`` ahead and rolls the tail back on
    rejection); null-row slots scatter into the reserved null page.
    """
    ps = pool.shape[2]
    C = new.shape[2]
    positions = pos[:, None] + jnp.arange(C)[None, :]           # [B,C]
    phys = jnp.take_along_axis(page_table, positions // ps, axis=1)
    off = positions % ps
    return pool.at[:, phys, off].set(new.astype(pool.dtype))


def cache_insert_paged(pool, new, page_table, pos):
    """Scatter one new token's K/V into the paged pool, all layers at once.

    pool: [L,NP,PS,KV,hd]; new: [L,B,1,KV,hd]; page_table: [B,P]; pos: [B].
    The target page is ``page_table[b, pos // PS]`` at offset ``pos % PS``.
    Slots whose page-table row is null (all ``NULL_PAGE``) scatter into the
    reserved null page — harmless by construction.
    """
    ps = pool.shape[2]
    B = pos.shape[0]
    phys = jnp.take_along_axis(page_table, (pos // ps)[:, None], axis=1)[:, 0]
    off = pos % ps
    return pool.at[:, phys, off].set(new[:, :, 0].astype(pool.dtype))


def cache_write_pages(pool, kv, page_ids):
    """Write whole pages of prefilled K/V into the pool.

    pool: [L,NP,PS,KV,hd]; kv: [L,1,n*PS,KV,hd] (page-aligned chunk of one
    sequence); page_ids: [n] int32 physical destinations, one per page.
    """
    L, NP, PS, KV, hd = pool.shape
    kvr = kv.reshape(L, -1, PS, KV, hd)
    return pool.at[:, page_ids].set(kvr.astype(pool.dtype))


def cache_copy_pages(pool, src_ids, dst_ids):
    """Duplicate physical pages inside the pool — the copy-on-write op.

    pool: [L,NP,PS,KV,hd]; src_ids/dst_ids: [n] int32. Every row of page
    ``src_ids[j]`` (all layers) is copied into page ``dst_ids[j]``. The
    engine calls this when a slot must write into a prefix-shared page
    (refcount > 1): the shared original stays byte-identical for its other
    readers, and the writer proceeds into its private copy.
    """
    return pool.at[:, dst_ids].set(pool[:, src_ids])


def attention_prefill_chunk(q, k_ctx, v_ctx, k_new, v_new, offset, *,
                            window: int = 0):
    """Chunked-prefill attention: a chunk of queries at absolute positions
    ``offset + [0, C)`` attends to already-cached context (positions
    ``< offset``, gathered in logical order) plus itself causally.

    q: [B,C,H,hd]; k_ctx/v_ctx: [B,Sc,KV,hd]; k_new/v_new: [B,C,KV,hd];
    offset: [B] int32. Plain softmax, mirroring ``attention_full`` so chunked
    prefill reproduces the one-shot prefill numerics.
    """
    B, C, H, hd = q.shape
    Sc = k_ctx.shape[1]
    k = gqa_expand_kv(jnp.concatenate([k_ctx, k_new], axis=1), H)
    v = gqa_expand_kv(jnp.concatenate([v_ctx, v_new], axis=1), H)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    qpos = offset[:, None] + jnp.arange(C)[None, :]            # [B,C]
    kpos = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(Sc)[None, :], (B, Sc)), qpos],
        axis=1)                                                # [B,Sc+C]
    is_ctx = (jnp.arange(Sc + C) < Sc)[None, None, :]          # [1,1,Sc+C]
    ctx_ok = (kpos < offset[:, None])[:, None, :]              # [B,1,Sc+C]
    causal_ok = kpos[:, None, :] <= qpos[:, :, None]           # [B,C,Sc+C]
    ok = jnp.where(is_ctx, ctx_ok, causal_ok)
    if window:
        ok &= kpos[:, None, :] > qpos[:, :, None] - window
    scores = jnp.where(ok[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, v)
    return out.reshape(B, C, H, hd)
