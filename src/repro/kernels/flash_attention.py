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


# keys per history block: a segment's live history pages are fetched this
# many keys at a time (32 pages of 16 at the paged widths), double-buffered.
# On a v5e at the served widths a call took 2-23% less time than at 256
# and 6-50% less than at 128.
HIST_BLOCK = 512


def _packed_prefill_kernel(sot_ref, st_ref, off_ref, len_ref, bt_ref,
                           q_ref, kn_ref, vn_ref, kp_hbm, vp_hbm, o_ref,
                           kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *,
                           bq: int, P: int, PB: int, W: int, n_pages: int,
                           ring: int, scale: float, window: int, G: int,
                           D: int):
    h = pl.program_id(0)                   # kv head; its G query heads ride
    i = pl.program_id(1)                   # query tile (one segment each)
    seg = sot_ref[i]                       # segment owning this query tile
    start = st_ref[seg]
    off = off_ref[seg]
    ln = len_ref[seg]
    t0 = i * bq
    bk = PB * P                            # keys per history block

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def q_rows():                                            # [G*bq, D]
        return q_ref[0].reshape(G * bq, D)

    def accum(s, v):
        m_prev = m_ref[...]                                  # [G*bq, 1]
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

    def scores(k):                                           # [G*bq, n]
        return jax.lax.dot_general(
            q_rows(), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    def keep_rows(s, keep):
        # keep: [bq, n], one row per query of the tile, the same for each
        # of the G heads stacked in s
        n = s.shape[1]
        s = jnp.where(keep[None], s.reshape(G, bq, n), NEG_INF)
        return s.reshape(G * bq, n)

    # query index within the segment, per row of the tile
    jj_q = t0 - start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    # -- history: only the live pages, min(off, ring) ring slots ----------
    hist = jnp.minimum(off, ring)
    n_live = (hist + P - 1) // P
    n_blk = (n_live + PB - 1) // PB

    def table(b, p):                       # table entry of page p of block b
        return bt_ref[seg, jnp.minimum(b * PB + p, W - 1)]

    # the page loops are fori_loops, not unrolled: the kernel lowers in a
    # fraction of the time, which every compiled stream length pays
    def start_block(b, slot):
        # one DMA per page and K/V; the sentinel (and a block's tail past
        # the table) clamps to a real page whose keys the mask drops
        def body(p, carry):
            page = jnp.minimum(table(b, p), n_pages - 1)
            for x, (src, buf) in enumerate(((kp_hbm, kbuf), (vp_hbm, vbuf))):
                pltpu.make_async_copy(
                    src.at[page, :, pl.ds(h * D, D)], buf.at[slot, p],
                    sem.at[x, slot]).start()
            return carry
        jax.lax.fori_loop(0, PB, body, 0)

    def wait_block(slot):
        def body(p, carry):
            for x, buf in enumerate((kbuf, vbuf)):
                pltpu.make_async_copy(
                    kp_hbm.at[0, :, pl.ds(0, D)], buf.at[slot, p],
                    sem.at[x, slot]).wait()
            return carry
        jax.lax.fori_loop(0, PB, body, 0)

    def history_block(b, carry):
        slot = b % 2

        @pl.when(b + 1 < n_blk)
        def _prefetch():
            start_block(b + 1, 1 - slot)

        # unallocated pages among the block's live ones (scalar; the
        # per-column page mask is built only where there is one)
        n_dead = jax.lax.fori_loop(
            0, PB, lambda p, n: n + ((b * PB + p < n_live)
                                     & (table(b, p) >= n_pages)
                                     ).astype(jnp.int32), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s_idx = b * bk + cols              # logical ring slot
        ok = (s_idx < hist).astype(jnp.int32)

        def page_mask():
            def body(p, live):
                dead = (cols >= p * P) & (cols < (p + 1) * P) & (
                    table(b, p) >= n_pages)
                return jnp.where(dead, 0, live)
            return jax.lax.fori_loop(0, PB, body, ok)

        ok = jax.lax.cond(n_dead == 0, lambda: ok, page_mask) > 0

        wait_block(slot)
        k = kbuf[slot].reshape(bk, D)
        v = vbuf[slot].reshape(bk, D)
        s = scores(k)
        if window > 0:
            # ring slot s holds the largest position p < off, p % ring == s
            prev_pos = off - 1 - jnp.remainder(off - 1 - s_idx, ring)
            s = keep_rows(s, ok & ((off + jj_q - prev_pos) < window))
        else:
            s = jnp.where(ok, s, NEG_INF)
        accum(s, v)
        return carry

    # -- stream: the segment's own tiles up to the causal diagonal --------
    def stream_tile(jj, diagonal: bool):
        kk = pl.multiple_of(jj * bq, bq)
        s = scores(kn_ref[0, pl.ds(kk, bq), :])              # [G*bq, bq]
        jj_k = kk - start + jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
        keep = None
        if diagonal:
            keep = jj_k <= jj_q
        if window > 0:
            near = (jj_q - jj_k) < window
            keep = near if keep is None else keep & near
        if keep is not None:
            s = keep_rows(s, keep)
        accum(s, vn_ref[0, pl.ds(kk, bq), :])

    def stream_block(jj, carry):
        stream_tile(jj, diagonal=False)
        return carry

    @pl.when(t0 < start + ln)              # tail tiles past every segment
    def _attend():
        @pl.when(n_blk > 0)
        def _first():
            start_block(0, 0)

        jax.lax.fori_loop(0, n_blk, history_block, 0)
        jax.lax.fori_loop(start // bq, i, stream_block, 0)
        stream_tile(i, diagonal=True)

    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).reshape(G, bq, D).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("ring", "window", "bq", "bk",
                                    "interpret"))
def packed_prefill_attention(q, k_new, v_new, k_pages, v_pages, block_tables,
                             seg_starts, seg_offsets, seg_lengths, *,
                             ring: int, window: int = 0, bq: int = 128,
                             bk: int = HIST_BLOCK, interpret: bool = False):
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

    Grid: (Hkv, T/bq), one step per (KV head, query tile), named
    ``packed_prefill_attention`` in the trace.  The G query heads that
    share the KV head ride the step as one [G*bq, D] block, so each page
    is read once per query tile, not G times.  A step walks its segment's
    LIVE history only, ``ceil(min(offset, ring) / P)`` pages: a loop whose
    trip count comes from the scalar-prefetched offsets fetches
    ``bk // P`` pages at a time from the pool left in HBM, one DMA per
    page through the block-table row, double-buffered.  Then it walks the
    segment's own stream tiles up to the causal diagonal (the KV head's
    whole stream K/V is one [T, D] VMEM block each, which bounds T).  So
    the work and the DMAs scale with the live pages, not with the table
    width W, and a wider table (more sentinel columns) changes nothing.
    ``ring`` is the arena's logical ring span R (positions at ``pos % R``).

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
    PB = max(1, bk // P)                   # pages per history block
    scale = 1.0 / math.sqrt(D)
    starts = jnp.asarray(seg_starts, jnp.int32)
    bt = jnp.asarray(block_tables, jnp.int32)
    # owning segment of each query tile (pad segments carry start == T, so
    # tail tiles resolve to the last real segment and are skipped)
    tile0 = jnp.arange(nq, dtype=jnp.int32) * bq
    seg_of_tile = jnp.maximum(
        jnp.sum(tile0[:, None] >= starts[None, :], axis=1) - 1,
        0).astype(jnp.int32)
    qh = q.swapaxes(0, 1).reshape(Hkv, G, T, D)
    kh = k_new.swapaxes(0, 1)              # [Hkv, T, D]
    vh = v_new.swapaxes(0, 1)

    # the pool as [n_pages, P, Hkv*D]: one kv head's page is then the
    # (P, D) tile at lane block h, a contiguous DMA
    kp = k_pages.reshape(n_pages, P, Hkv * D)
    vp = v_pages.reshape(n_pages, P, Hkv * D)

    def tile_map(h, i, *_):
        return (h, 0, i, 0)

    def head_map(h, i, *_):
        return (h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(Hkv, nq),
        in_specs=[
            pl.BlockSpec((1, G, bq, D), tile_map),
            pl.BlockSpec((1, T, D), head_map),
            pl.BlockSpec((1, T, D), head_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, G, bq, D), tile_map),
        scratch_shapes=[
            pltpu.VMEM((2, PB, P, D), k_pages.dtype),
            pltpu.VMEM((2, PB, P, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((G * bq, 1), jnp.float32),
            pltpu.VMEM((G * bq, 1), jnp.float32),
            pltpu.VMEM((G * bq, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_packed_prefill_kernel, bq=bq, P=P, PB=PB, W=W,
                          n_pages=n_pages, ring=ring, scale=scale,
                          window=window, G=G, D=D),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, G, T, D), q.dtype),
        name="packed_prefill_attention",
        interpret=interpret,
    )(seg_of_tile, starts, jnp.asarray(seg_offsets, jnp.int32),
      jnp.asarray(seg_lengths, jnp.int32), bt, qh, kh, vh, kp, vp)
    return out.reshape(H, T, D).swapaxes(0, 1)   # [T, H, D]
