"""Flash attention (prefill) Pallas kernel with causal + sliding-window masks.

Grid: (B*Hkv*G, Tq/bq, Tk/bk) — the KV axis is innermost so the online-
softmax state (m, l, acc) for one query tile lives in VMEM scratch across KV
steps.  Causal upper-triangle KV tiles are skipped with pl.when (zero MXU
work), which is the triangular schedule the pure-JAX blockwise version can't
express (EXPERIMENTS.md §Perf).

VMEM working set per step (bq=bk=512, D=128, bf16):
q 512x128x2 + k/v 2x512x128x2 + acc 512x128x4 + p 512x512x4 ~ 1.7 MB.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, nk: int, bq: int, bk: int, scale: float,
                  causal: bool, window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # does this KV tile intersect the (causal, windowed) mask at all?
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window > 0:
        run = jnp.logical_and(run, (q_start - (k_start + bk - 1)) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                         # [bq, D]
        k = k_ref[0]                                         # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [bq, bk]
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= (rows - cols) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p.astype(v_ref.dtype), v_ref[0],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 512, bk: int = 512, interpret: bool = False):
    """q: [B,H,T,D]; k,v: [B,Hkv,T,D] (GQA).  Returns [B,H,T,D]."""
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    bq, bk = min(bq, T), min(bk, T)
    assert T % bq == 0 and T % bk == 0
    nq, nk = T // bq, T // bk
    scale = 1.0 / math.sqrt(D)

    # flatten (B, Hkv, G) into one grid axis; the kv index map drops G
    qf = q.reshape(B * Hkv * G, T, D)
    kf = k.reshape(B * Hkv, T, D)
    vf = v.reshape(B * Hkv, T, D)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, nk=nk, bq=bq, bk=bk, scale=scale,
                          causal=causal, window=window),
        grid=(B * Hkv * G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j, G=G: (h // G, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j, G=G: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv * G, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, T, D)


# ---------------------------------------------------------------------------
# packed multi-request prefill (flat stream + per-segment arena history)
# ---------------------------------------------------------------------------


def _packed_prefill_kernel(sot_ref, st_ref, off_ref, len_ref, bt_ref,
                           q_ref, kn_ref, vn_ref, kp_ref, vp_ref, o_ref,
                           m_ref, l_ref, acc_ref, *,
                           nw: int, nq: int, bq: int, P: int, n_pages: int,
                           ring: int, scale: float, window: int, G: int):
    i = pl.program_id(1)                   # query tile (one segment each)
    j = pl.program_id(2)                   # KV step: [0,nw) history pages,
    #                                        [nw,nw+nq) stream tiles

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seg = sot_ref[i]                       # segment owning this query tile
    start = st_ref[seg]
    off = off_ref[seg]
    ln = len_ref[seg]
    t0 = i * bq

    def accum(s, v):
        m_prev = m_ref[...]                                  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j < nw)
    def _history():
        # one logical page of THIS segment's arena history; sentinel pages
        # (unallocated / pad segment), pages beyond the ring span, and
        # zero-history segments are skipped whole
        jh = jnp.minimum(j, nw - 1)
        page = bt_ref[seg, jh]
        s0 = jh * P

        @pl.when((page < n_pages) & (s0 < ring) & (off > 0))
        def _run():
            q = q_ref[0]                                     # [bq, D]
            k = kp_ref[0]                                    # [P, D]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bq, P]
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, P), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, P), 1)
            jj_q = t0 + rows - start       # query index within segment
            q_pos = off + jj_q
            s_idx = s0 + cols              # logical ring slot
            # ring slot s holds the largest position p < off, p % ring == s
            prev_pos = off - 1 - jnp.remainder(off - 1 - s_idx, ring)
            mask = ((jj_q < ln) & (s_idx < ring)
                    & (prev_pos >= 0) & (prev_pos <= q_pos))
            if window > 0:
                mask &= (q_pos - prev_pos) < window
            accum(jnp.where(mask, s, NEG_INF), vp_ref[0])

    @pl.when(j >= nw)
    def _stream():
        # one stream tile: only causally-visible tiles of the SAME segment
        jj = jnp.maximum(j - nw, 0)

        @pl.when((sot_ref[jj] == seg) & (jj <= i))
        def _run():
            q = q_ref[0]                                     # [bq, D]
            k = kn_ref[0]                                    # [bq, D]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bq, bq]
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
            jj_q = t0 + rows - start
            jj_k = jj * bq + cols - start
            mask = (jj_q < ln) & (jj_k < ln) & (jj_k <= jj_q)
            if window > 0:
                mask &= (jj_q - jj_k) < window
            accum(jnp.where(mask, s, NEG_INF), vn_ref[0])

    @pl.when(j == nw + nq - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("ring", "window", "bq", "interpret"))
def packed_prefill_attention(q, k_new, v_new, k_pages, v_pages, block_tables,
                             seg_starts, seg_offsets, seg_lengths, *,
                             ring: int, window: int = 0, bq: int = 128,
                             interpret: bool = False):
    """Segment-masked online-softmax attention over a PACKED prefill stream.

    One tick's prefill chunks, laid out back to back in a flat stream of T
    tokens (rope already applied):

      q:            [T, H, D]    packed queries
      k_new/v_new:  [T, Hkv, D]  the stream's own keys/values
      k_pages/v_pages: [n_pages, P, Hkv, D] arena history pool
      block_tables: [N, W] int32 per-SEGMENT page rows (>= n_pages =
                    unallocated sentinel; pad segments are all-sentinel)
      seg_starts/seg_offsets/seg_lengths: [N] segment start in the stream
                    (multiple of ``bq`` — a tile never straddles two
                    segments), arena history length, and token count

    Grid: (Hkv*G, T/bq, W + T/bq) — for each query tile the KV axis first
    walks the owning segment's history pages (scalar-prefetched block-table
    rows gather physical pages in the BlockSpec index_map, exactly like the
    paged decode kernel) and then the stream tiles, skipping other
    segments' tiles and causal-future tiles whole via pl.when.  ``ring`` is
    the arena's logical ring span R (positions live at ``pos % R``); the
    dense [B, R, ...] arena is served by the same kernel as a 1-page-per-
    segment pool view (P = R, block table = the segment's slot).

    Returns ctx [T, H, D] (pre-``wo``); rows of pad tokens are garbage the
    caller discards, exactly like padded rows in the pure-JAX path.
    """
    T, H, D = q.shape
    Hkv = k_new.shape[1]
    G = H // Hkv
    n_pages, P = k_pages.shape[0], k_pages.shape[1]
    W = block_tables.shape[1]
    assert T % bq == 0, (T, bq)
    nq = T // bq
    scale = 1.0 / math.sqrt(D)
    starts = jnp.asarray(seg_starts, jnp.int32)
    bt = jnp.asarray(block_tables, jnp.int32)
    # owning segment of each query tile (pad segments carry start == T, so
    # tail tiles resolve to the last real segment and mask out row-wise)
    tile0 = jnp.arange(nq, dtype=jnp.int32) * bq
    seg_of_tile = jnp.maximum(
        jnp.sum(tile0[:, None] >= starts[None, :], axis=1) - 1,
        0).astype(jnp.int32)
    qh = q.swapaxes(0, 1)                  # [H, T, D]
    kh = k_new.swapaxes(0, 1)              # [Hkv, T, D]
    vh = v_new.swapaxes(0, 1)

    # the pool as [n_pages, P, Hkv*D]: a (1, P, D) block at column block
    # h // G is one kv head's page, and its last two dims tile as Mosaic
    # requires ((P, D) = (16, 128) at the paged widths) where a
    # (1, P, 1, D) block of the 4-D pool does not
    kp = k_pages.reshape(n_pages, P, Hkv * D)
    vp = v_pages.reshape(n_pages, P, Hkv * D)

    def page_map(h, i, j, sot, st, off, ln, btr):
        # clamp the sentinel: the fetched page is ignored (pl.when masks
        # the whole step) but the DMA address must stay in bounds
        pg = btr[sot[i], jnp.minimum(j, W - 1)]
        return (jnp.minimum(pg, n_pages - 1), 0, h // G)

    def stream_map(h, i, j, sot, st, off, ln, btr):
        return (h // G, jnp.maximum(j - W, 0), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(Hkv * G, nq, W + nq),
        in_specs=[
            pl.BlockSpec((1, bq, D),
                         lambda h, i, j, sot, st, off, ln, btr: (h, i, 0)),
            pl.BlockSpec((1, bq, D), stream_map),
            pl.BlockSpec((1, bq, D), stream_map),
            pl.BlockSpec((1, P, D), page_map),
            pl.BlockSpec((1, P, D), page_map),
        ],
        out_specs=pl.BlockSpec(
            (1, bq, D), lambda h, i, j, sot, st, off, ln, btr: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_packed_prefill_kernel, nw=W, nq=nq, bq=bq, P=P,
                          n_pages=n_pages, ring=ring, scale=scale,
                          window=window, G=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv * G, T, D), q.dtype),
        interpret=interpret,
    )(seg_of_tile, starts, jnp.asarray(seg_offsets, jnp.int32),
      jnp.asarray(seg_lengths, jnp.int32), bt, qh, kh, vh, kp, vp)
    return out.swapaxes(0, 1)              # [T, H, D]
