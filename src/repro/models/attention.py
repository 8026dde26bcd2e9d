"""Attention: GQA with qk-norm / sliding-window / local-global variants.

Two execution paths, mirroring HALO's phase split:

* ``attn_prefill`` — compute-bound GEMM path.  For long sequences it uses a
  blockwise (flash-style, online-softmax) pure-JAX implementation so the
  lowered HLO has O(T * block) live memory instead of O(T^2).  On TPU the
  Pallas flash kernel (kernels/flash_attention.py) implements the same
  algorithm with explicit VMEM tiling.

* ``attn_decode`` — memory-bound GEMV path.  One new token attends to a KV
  cache of S entries.  The cache is laid out [B, S, Hkv, Dh] so that S can be
  sequence-sharded across the ``model`` mesh axis (the TPU analogue of HALO's
  bank-level CiD GEMV: every shard scans its local slice of the cache and the
  softmax is reconstructed with tiny cross-shard reductions).

Sliding-window layers use a ring-buffer cache of length min(W, S): keys are
stored with RoPE already applied at their absolute position, so the ring
order does not matter; validity masking only needs the current position.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (
    apply_rope,
    dense_init,
    head_rmsnorm,
    matmul,
)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def attn_init(key, d_model: int, n_heads: int, n_kv_heads: int, d_head: int,
              dtype, qk_norm: bool = False, d_model_out: Optional[int] = None):
    d_out = d_model_out or d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * d_head, dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * d_head, dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * d_head, dtype),
        "wo": dense_init(ks[3], n_heads * d_head, d_out, dtype),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((d_head,), dtype)
        p["k_norm"] = jnp.ones((d_head,), dtype)
    return p


# ---------------------------------------------------------------------------
# qkv projection (shared between phases)
# ---------------------------------------------------------------------------

def _project_qkv(params, x, n_heads, n_kv_heads, d_head, positions, theta,
                 qk_norm: bool):
    from repro.distributed.policy import constrain
    B = x.shape[0]
    T = x.shape[1]
    q = matmul(x, params["wq"]).reshape(B, T, n_heads, d_head)
    k = matmul(x, params["wk"]).reshape(B, T, n_kv_heads, d_head)
    v = matmul(x, params["wv"]).reshape(B, T, n_kv_heads, d_head)
    from repro.distributed.policy import get_policy
    pol = get_policy()
    q = constrain(q, "act_bthd")
    if pol is not None and pol.sp_enabled and T > 1:
        # sequence-parallel prefill: gather K/V across the token shards
        # (0.27 GB/layer vs the 4.3 GB/layer of f32 activation all-reduces
        # that Megatron-style TP costs at 32k context — §Perf iteration 2)
        k = constrain(k, "kv_full")
        v = constrain(v, "kv_full")
    elif n_kv_heads > 1:      # kv=1 (gemma3) cannot shard the head axis
        k = constrain(k, "act_bthd")
        v = constrain(v, "act_bthd")
    if qk_norm:
        q = head_rmsnorm(params["q_norm"], q)
        k = head_rmsnorm(params["k_norm"], k)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    return q, k, v


def _maybe_softcap(scores, softcap: float):
    if softcap and softcap > 0.0:
        return softcap * jnp.tanh(scores / softcap)
    return scores


# ---------------------------------------------------------------------------
# prefill / train
# ---------------------------------------------------------------------------

def _dense_attention(q, k, v, positions, kv_positions, window, softcap,
                     pad_mask=None):
    """Reference masked attention.  q:[B,Tq,H,D] k,v:[B,Tk,Hkv,D]."""
    B, Tq, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Tq, Hkv, G, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(D)
    scores = _maybe_softcap(scores, softcap)
    # causal + window mask.  window is traced (per-layer); 0 means "full".
    pq = positions[:, :, None]                       # [B,Tq,1]
    pk = kv_positions[:, None, :]                    # [B,1,Tk]
    causal = pk <= pq
    w = jnp.where(window > 0, window, jnp.iinfo(jnp.int32).max)
    in_window = (pq - pk) < w
    valid = causal & in_window
    if pad_mask is not None:
        valid = valid & pad_mask[:, None, :]
    mask = valid[:, None, None]                      # [B,1,1,Tq,Tk]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Tq, H, D).astype(q.dtype)


def _blockwise_attention(q, k, v, positions, window, softcap,
                         block_q: int = 512, block_kv: int = 1024,
                         pad_mask=None):
    """Flash-style online-softmax attention; O(T*block) live memory.

    Causal masking is applied at block granularity through the score mask;
    the FLOP count still includes upper-triangle blocks (see EXPERIMENTS.md
    §Perf for the triangular-schedule optimization that removes them).
    """
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    nq = T // block_q
    nk = T // block_kv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, nq, block_q, Hkv, G, D)
    kb = k.reshape(B, nk, block_kv, Hkv, D)
    vb = v.reshape(B, nk, block_kv, Hkv, D)
    pos_q = positions.reshape(B, nq, block_q)
    pos_k = positions.reshape(B, nk, block_kv)
    if pad_mask is None:
        pad_mask = jnp.ones((B, T), jnp.bool_)
    pm_k = pad_mask.reshape(B, nk, block_kv)
    w = jnp.where(window > 0, window, jnp.iinfo(jnp.int32).max)

    @partial(jax.checkpoint,
             policy=jax.checkpoint_policies.nothing_saveable)
    def q_block_inner(qblk, pq):
        """One query block vs all kv blocks.  Rematerialized in backward so
        the per-(q,kv)-block probabilities are never stacked across blocks
        (flash-attention memory discipline, pure-JAX edition)."""
        m0 = jnp.full((B, Hkv, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, block_q, D), jnp.float32)

        def kv_block(acc, ki):
            m, l, a = acc
            kblk, vblk, pk, pmk = ki
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = _maybe_softcap(s, softcap)
            causal = pk[:, None, None, None, :] <= pq[:, None, None, :, None]
            in_w = (pq[:, None, None, :, None] - pk[:, None, None, None, :]) < w
            ok = causal & in_w & pmk[:, None, None, None, :]
            s = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk,
                            preferred_element_type=jnp.float32)
            a_new = a * corr[..., None] + pv
            return (m_new, l_new, a_new), None

        (m, l, a), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), pos_k.swapaxes(0, 1),
             pm_k.swapaxes(0, 1)))
        out = a / jnp.maximum(l[..., None], 1e-30)           # [B,Hkv,G,bq,D]
        out = out.transpose(0, 3, 1, 2, 4)                   # [B,bq,Hkv,G,D]
        return out.astype(qblk.dtype)

    from repro.distributed.policy import get_policy
    pol = get_policy()
    if pol is not None and pol.sp_enabled:
        # SEQUENCE-PARALLEL: q blocks are sharded over 'model'; a scan would
        # serialize them globally (GSPMD slices the scan axis), so the block
        # loop becomes a vmap — batched ops with a sharded leading dim stay
        # shard-local.  K/V were all-gathered by the caller.
        outs = jax.vmap(q_block_inner)(
            qg.swapaxes(0, 1), pos_q.swapaxes(0, 1))         # [nq,B,bq,...]
    else:
        def q_block(carry, qi):
            qblk, pq = qi                                    # [B,bq,Hkv,G,D]
            return carry, q_block_inner(qblk, pq)

        _, outs = jax.lax.scan(
            q_block, None,
            (qg.swapaxes(0, 1), pos_q.swapaxes(0, 1)))       # [nq,B,bq,...]
    out = outs.swapaxes(0, 1).reshape(B, T, H, D)
    return out


def _can_use_pallas_flash(q, softcap, pad_mask, positions) -> bool:
    """The Pallas kernel path: TPU backend, no softcap/padding, contiguous
    positions (the kernel masks by absolute block indices)."""
    import jax as _jax
    if _jax.default_backend() != "tpu":
        return False
    if softcap or pad_mask is not None:
        return False
    T = q.shape[1]
    return T % 512 == 0


def attn_prefill(params, x, positions, *, n_heads, n_kv_heads, d_head,
                 theta, window, softcap=0.0, qk_norm=False,
                 dense_threshold: int = 2048, pad_mask=None):
    """Full-sequence attention.  Returns [B, T, d_model_out] and (k, v) for
    cache initialization.  ``pad_mask`` [B,T] marks valid (non-pad) keys.

    Dispatch: small T -> dense reference; long T on TPU -> the Pallas flash
    kernel (kernels/flash_attention.py: triangular tile schedule, VMEM-
    resident probs); otherwise the pure-JAX blockwise path (same online-
    softmax algorithm — the CPU/dry-run stand-in the §Roofline kernel-region
    discount maps back onto the kernel).
    """
    B, T, _ = x.shape
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head,
                           positions, theta, qk_norm)
    if T <= dense_threshold:
        out = _dense_attention(q, k, v, positions, positions, window, softcap,
                               pad_mask=pad_mask)
    else:
        use_kernel = _can_use_pallas_flash(q, softcap, pad_mask, positions)
        w = None
        if use_kernel:
            try:
                w = int(window)        # concrete at trace time (per-run)
            except Exception:
                use_kernel = False
        if use_kernel:
            from repro.kernels import ops as _kops
            out = _kops.flash_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True,
                window=w).transpose(0, 2, 1, 3)
        else:
            out = _blockwise_attention(q, k, v, positions, window, softcap,
                                       pad_mask=pad_mask)
    out = matmul(out.reshape(B, T, n_heads * d_head), params["wo"])
    return out, (k, v)


# ---------------------------------------------------------------------------
# chunked prefill (arena-direct)
# ---------------------------------------------------------------------------

def attn_chunk(params, x, offsets, lengths, slots, cache_k, cache_v, *,
               n_heads, n_kv_heads, d_head, theta, window, softcap=0.0,
               qk_norm=False):
    """Chunked prefill against the decode arena (HALO's CiM -> CiD handoff).

    x: [N, C, d] — N packed chunk rows of up to C tokens; row ``n`` carries
    tokens ``[offsets[n], offsets[n]+lengths[n])`` of the request living in
    arena slot ``slots[n]``.  cache_k/v: [B, R, Hkv, Dh] decode arena
    (R = min(window, S) ring for sliding-window runs, R = S otherwise).

    Entries at positions < offsets[n] were written by earlier chunks of the
    same request; the chunk attends over that history + itself (causal,
    windowed) and then writes its own K/V into the arena.  History is
    gathered BEFORE the write: a ring entry the chunk is about to overwrite
    is still needed by the chunk's early queries.  Padded rows
    (slots[n] >= B) and padded positions (j >= lengths[n]) scatter out of
    bounds and are dropped.

    Returns (out [N, C, d_model], new_cache_k, new_cache_v).
    """
    N, C, _ = x.shape
    B, R = cache_k.shape[0], cache_k.shape[1]
    offs = jnp.asarray(offsets, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    slot = jnp.asarray(slots, jnp.int32)
    j = jnp.arange(C, dtype=jnp.int32)
    positions = offs[:, None] + j[None, :]                       # [N, C]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head,
                           positions, theta, qk_norm)

    row = jnp.clip(slot, 0, B - 1)
    prev_k = cache_k[row]                                        # [N, R, ...]
    prev_v = cache_v[row]
    s_idx = jnp.arange(R, dtype=jnp.int32)
    # ring slot s holds the largest position p < off with p % R == s
    # (for full-attention runs R == S, so this reduces to p == s when
    # s < off and "not yet written" otherwise — one formula for both)
    prev_pos = offs[:, None] - 1 - ((offs[:, None] - 1 - s_idx[None, :]) % R)
    chunk_pos = jnp.where(j[None, :] < lens[:, None], positions, -1)
    kv_k = jnp.concatenate([prev_k, k], axis=1)                  # [N, R+C, ...]
    kv_v = jnp.concatenate([prev_v, v], axis=1)
    kv_pos = jnp.concatenate([prev_pos, chunk_pos], axis=1)      # [N, R+C]

    Hkv = n_kv_heads
    G = n_heads // Hkv
    qg = q.reshape(N, C, Hkv, G, d_head)
    scores = jnp.einsum("nqhgd,nkhd->nhgqk", qg, kv_k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d_head)
    scores = _maybe_softcap(scores, softcap)
    pq = positions[:, :, None]                                   # [N, C, 1]
    pk = kv_pos[:, None, :]                                      # [N, 1, R+C]
    w = jnp.where(window > 0, window, jnp.iinfo(jnp.int32).max)
    valid = (pk >= 0) & (pk <= pq) & ((pq - pk) < w)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("nhgqk,nkhd->nqhgd", probs.astype(kv_v.dtype), kv_v,
                     preferred_element_type=jnp.float32)
    ctx = ctx.reshape(N, C, n_heads * d_head).astype(x.dtype)
    out = matmul(ctx, params["wo"])

    # arena write: ring discipline keeps only the row's last R positions
    # (earlier chunk positions a later same-chunk token wraps onto must
    # not be scattered — duplicate scatter indices are order-undefined)
    keep = (j[None, :] < lens[:, None]) & (j[None, :] >= lens[:, None] - R)
    w_slot = jnp.where(keep, jnp.broadcast_to(slot[:, None], (N, C)), B)
    w_idx = jnp.where(keep, positions % R, R)
    new_k = cache_k.at[w_slot, w_idx].set(k, mode="drop")
    new_v = cache_v.at[w_slot, w_idx].set(v, mode="drop")
    return out, new_k, new_v


# ---------------------------------------------------------------------------
# chunked prefill (paged pool)
# ---------------------------------------------------------------------------

def attn_chunk_paged(params, x, offsets, lengths, slots, cache, block_table,
                     *, n_heads, n_kv_heads, d_head, theta, window,
                     softcap=0.0, qk_norm=False):
    """Chunked prefill writing K/V into the paged block pool.

    Same packing contract as ``attn_chunk`` (x: [N, C, d], row ``n`` holds
    tokens [offsets[n], offsets[n]+lengths[n]) of the request in slot
    ``slots[n]``) but the arena is a pool ``cache = {"k","v"[, scales]}``
    of shape [n_pages, P, Hkv, Dh] addressed via ``block_table`` [B, W]:
    position ``pos`` of a slot lives at page ``bt[slot, (pos % R) // P]``
    offset ``(pos % R) % P`` where R is the run's logical ring span
    (min(window, W*P-ish) — derived from the pool the same way the engine's
    KVPool derives it).  History is gathered through the block table BEFORE
    the chunk's own K/V are scattered (ring overwrite discipline), and
    quantized pools ("k_scale" present; int8 pages, or packed-int4 uint8
    pages at half the head width) dequantize history / quantize writes —
    the attention math itself stays full precision (CiM prefill).

    Returns (out [N, C, d_model], new_cache dict).
    """
    from repro.serving.quantized_cache import (
        dequantize, pack_int4, quantize_token, quantize_token_int4,
        unpack_int4)

    n_rows, C, _ = x.shape
    n_pages, P = cache["k"].shape[0], cache["k"].shape[1]
    B, W = block_table.shape[0], block_table.shape[1]
    capacity = n_pages * P
    try:
        w_static = int(window)
    except Exception as e:          # pragma: no cover - window is per-run static
        raise ValueError("paged attention needs a trace-time window") from e
    R = min(w_static, capacity) if w_static > 0 else capacity
    S = W * P                                     # gathered logical span
    quant = "k_scale" in cache
    q4 = quant and cache["k"].dtype == jnp.uint8  # packed nibble pages

    offs = jnp.asarray(offsets, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    slot = jnp.asarray(slots, jnp.int32)
    j = jnp.arange(C, dtype=jnp.int32)
    positions = offs[:, None] + j[None, :]                       # [N, C]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head,
                           positions, theta, qk_norm)

    # gather the rows' history pages BEFORE writing (a ring entry the chunk
    # overwrites is still needed by the chunk's early queries)
    row = jnp.clip(slot, 0, B - 1)
    bt_rows = jnp.asarray(block_table, jnp.int32)[row]           # [N, W]
    pages = jnp.clip(bt_rows, 0, n_pages - 1)
    if quant:
        raw_k, raw_v = cache["k"][pages], cache["v"][pages]
        if q4:
            raw_k, raw_v = unpack_int4(raw_k), unpack_int4(raw_v)
        prev_k = dequantize(raw_k, cache["k_scale"][pages])
        prev_v = dequantize(raw_v, cache["v_scale"][pages])
        prev_k = prev_k.astype(x.dtype)
        prev_v = prev_v.astype(x.dtype)
    else:
        prev_k = cache["k"][pages]                # [N, W, P, Hkv, Dh]
        prev_v = cache["v"][pages]
    prev_k = prev_k.reshape(n_rows, S, n_kv_heads, d_head)
    prev_v = prev_v.reshape(n_rows, S, n_kv_heads, d_head)
    s_idx = jnp.arange(S, dtype=jnp.int32)
    # ring slot s holds the largest position p < off with p % R == s
    prev_pos = offs[:, None] - 1 - ((offs[:, None] - 1 - s_idx[None, :]) % R)
    prev_pos = jnp.where(s_idx[None, :] < R, prev_pos, -1)       # page tail pad
    unalloc = jnp.repeat(bt_rows >= n_pages, P, axis=1)          # [N, S]
    prev_pos = jnp.where(unalloc, -1, prev_pos)
    chunk_pos = jnp.where(j[None, :] < lens[:, None], positions, -1)
    kv_k = jnp.concatenate([prev_k, k], axis=1)                  # [N, S+C, ...]
    kv_v = jnp.concatenate([prev_v, v], axis=1)
    kv_pos = jnp.concatenate([prev_pos, chunk_pos], axis=1)      # [N, S+C]

    Hkv = n_kv_heads
    G = n_heads // Hkv
    qg = q.reshape(n_rows, C, Hkv, G, d_head)
    scores = jnp.einsum("nqhgd,nkhd->nhgqk", qg, kv_k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d_head)
    scores = _maybe_softcap(scores, softcap)
    pq = positions[:, :, None]                                   # [N, C, 1]
    pk = kv_pos[:, None, :]                                      # [N, 1, S+C]
    wmask = jnp.where(window > 0, window, jnp.iinfo(jnp.int32).max)
    valid = (pk >= 0) & (pk <= pq) & ((pq - pk) < wmask)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("nhgqk,nkhd->nqhgd", probs.astype(kv_v.dtype), kv_v,
                     preferred_element_type=jnp.float32)
    ctx = ctx.reshape(n_rows, C, n_heads * d_head).astype(x.dtype)
    out = matmul(ctx, params["wo"])

    # pool write: only the row's last R positions (ring discipline — see
    # attn_chunk), through the block table, with padded rows / positions /
    # unallocated pages all dropping out of bounds
    keep = (j[None, :] < lens[:, None]) & (j[None, :] >= lens[:, None] - R)
    valid_row = (slot >= 0) & (slot < B)
    ridx = positions % R
    w_page = jnp.take_along_axis(bt_rows, ridx // P, axis=1)     # [N, C]
    w_page = jnp.where(keep & valid_row[:, None], w_page, n_pages)
    w_off = jnp.where(keep, ridx % P, P)
    new_cache = dict(cache)
    if quant:
        if q4:
            k_q, k_s = quantize_token_int4(k)       # [N,C,Hkv,Dh],[N,C,Hkv]
            v_q, v_s = quantize_token_int4(v)
            k_q, v_q = pack_int4(k_q), pack_int4(v_q)
        else:
            k_q, k_s = quantize_token(k)
            v_q, v_s = quantize_token(v)
        new_cache["k"] = cache["k"].at[w_page, w_off].set(k_q, mode="drop")
        new_cache["k_scale"] = cache["k_scale"].at[w_page, w_off].set(
            k_s, mode="drop")
        new_cache["v"] = cache["v"].at[w_page, w_off].set(v_q, mode="drop")
        new_cache["v_scale"] = cache["v_scale"].at[w_page, w_off].set(
            v_s, mode="drop")
    else:
        new_cache["k"] = cache["k"].at[w_page, w_off].set(k, mode="drop")
        new_cache["v"] = cache["v"].at[w_page, w_off].set(v, mode="drop")
    return out, new_cache


# ---------------------------------------------------------------------------
# packed chunked prefill (flat token stream, per-token segment metadata)
# ---------------------------------------------------------------------------

class PackedSegs(NamedTuple):
    """Per-token segment metadata for a packed prefill stream of T tokens
    holding N segments (one per request chunk; pad segments carry
    start == T so no token maps onto them).

    Per-token ([T]): seg_id, positions (absolute), valid (non-pad),
    jj (index within segment), lens_tok (segment length broadcast),
    tok_slot (arena slot broadcast).  Per-segment ([N]): starts, offsets,
    lengths, slots.
    """
    seg_id: Any
    positions: Any
    valid: Any
    jj: Any
    lens_tok: Any
    tok_slot: Any
    starts: Any
    offsets: Any
    lengths: Any
    slots: Any


def make_packed_segs(starts, offsets, lengths, slots, T: int) -> PackedSegs:
    """Expand per-segment (starts/offsets/lengths/slots, all [N]) into the
    per-token view over a T-token stream.  ``starts`` must be non-decreasing
    with starts[0] == 0; pad segments use start == T (stream length) so the
    running count assigns tail tokens to the last real segment."""
    starts = jnp.asarray(starts, jnp.int32)
    offsets = jnp.asarray(offsets, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    t = jnp.arange(T, dtype=jnp.int32)
    seg_id = jnp.maximum(
        jnp.sum(t[:, None] >= starts[None, :], axis=1) - 1, 0
    ).astype(jnp.int32)
    jj = t - starts[seg_id]
    lens_tok = lengths[seg_id]
    valid = jj < lens_tok
    positions = offsets[seg_id] + jj
    tok_slot = slots[seg_id]
    return PackedSegs(seg_id, positions, valid, jj, lens_tok, tok_slot,
                      starts, offsets, lengths, slots)


# The packed-prefill kernel's query tile: segment starts must be aligned
# to it so a tile never straddles two segments.
PACKED_KERNEL_TILE = 128

# Trace-time route counters, in the pattern of layers.gemv_route_count:
# each traced packed-prefill / paged-decode attention call adds one to the
# route it took ("kernel" = the Pallas kernel, "jnp" = the gathered
# jax.numpy path), so a program that gave way to jnp on a TPU shows it.
_routes: Dict[str, int] = {}


def _note_route(name: str) -> None:
    _routes[name] = _routes.get(name, 0) + 1


def attention_route_counts() -> Dict[str, int]:
    """{"packed_prefill.kernel"|"packed_prefill.jnp"|"paged_decode.kernel"
    |"paged_decode.jnp": traced calls} since the last reset."""
    return dict(_routes)


def reset_attention_route_counts() -> None:
    _routes.clear()


def packed_kernel_align(softcap, quantized: bool) -> int:
    """Segment alignment the packed-prefill Pallas kernel needs where it
    runs (TPU backend, no softcap, float KV), else 0.  The serving engine
    aligns its packed streams to this, so on a TPU the default paged path
    reaches the kernel whatever ``PhaseAwareConfig.pack_align`` says."""
    if jax.default_backend() != "tpu" or (softcap and softcap > 0.0):
        return 0
    return 0 if quantized else PACKED_KERNEL_TILE


def _use_packed_kernel(pack_align: int, T: int, softcap, window,
                       quantized: bool = False) -> bool:
    """Pallas packed-prefill kernel dispatch: ``packed_kernel_align``
    holds, the stream's segments are tile-aligned (a bq-tile never
    straddles two segments), and the window is a trace-time constant.
    Counts the route taken."""
    tile = packed_kernel_align(softcap, quantized)
    use = bool(tile) and pack_align % tile == 0 and T % pack_align == 0
    if use:
        try:
            int(window)
        except Exception:
            use = False
    _note_route("packed_prefill.kernel" if use else "packed_prefill.jnp")
    return use


def _packed_attention_jax(q, k, v, prev_k, prev_v, prev_pos, seg, *,
                          n_heads, n_kv_heads, d_head, window, softcap):
    """Pure-JAX segment-masked attention over a packed stream.

    q: [T, H, D]; k/v: [T, Hkv, D] (the stream's own projected keys/values);
    prev_k/prev_v: [N, S, Hkv, D] per-SEGMENT arena history with logical
    positions prev_pos [N, S] (-1 = invalid).  Token t attends over its
    segment's history plus the causally-visible same-segment stream tokens.
    Returns ctx [T, H*D] float-accumulated then cast to q.dtype.

    The history and self halves run as separate einsums (summing the two
    softmax partials) so the [T, T] self block never broadcasts to
    [T, S+T, ...] — same online-softmax algebra as a concat, and the
    masked entries contribute exact zeros either way.
    """
    T = q.shape[0]
    S = prev_k.shape[1]
    Hkv = n_kv_heads
    G = n_heads // Hkv
    qg = q.reshape(T, Hkv, G, d_head)
    # per-token history view (gather by segment id)
    ph_k = prev_k[seg.seg_id]                                    # [T, S, ...]
    ph_v = prev_v[seg.seg_id]
    pk_h = prev_pos[seg.seg_id]                                  # [T, S]
    s_hist = jnp.einsum("thgd,tshd->thgs", qg, ph_k,
                        preferred_element_type=jnp.float32)
    s_self = jnp.einsum("thgd,uhd->thgu", qg, k,
                        preferred_element_type=jnp.float32)
    scores = jnp.concatenate([s_hist, s_self], axis=-1) / math.sqrt(d_head)
    scores = _maybe_softcap(scores, softcap)
    pq = seg.positions[:, None]                                  # [T, 1]
    kv_pos_self = jnp.where(seg.valid, seg.positions, -1)        # [T]
    pk = jnp.concatenate(
        [pk_h, jnp.broadcast_to(kv_pos_self[None, :], (T, T))], axis=1)
    w = jnp.where(window > 0, window, jnp.iinfo(jnp.int32).max)
    ok = (pk >= 0) & (pk <= pq) & ((pq - pk) < w)
    same = jnp.concatenate(
        [jnp.ones((T, S), jnp.bool_),
         seg.seg_id[None, :] == seg.seg_id[:, None]], axis=1)
    scores = jnp.where((ok & same)[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("thgs,tshd->thgd", probs[..., :S].astype(ph_v.dtype),
                     ph_v, preferred_element_type=jnp.float32)
    ctx = ctx + jnp.einsum("thgu,uhd->thgd", probs[..., S:].astype(v.dtype),
                           v, preferred_element_type=jnp.float32)
    return ctx.reshape(T, n_heads * d_head)


def attn_chunk_packed(params, x, seg: PackedSegs, cache_k, cache_v, *,
                      n_heads, n_kv_heads, d_head, theta, window,
                      softcap=0.0, qk_norm=False, pack_align: int = 0):
    """Packed-stream chunked prefill against the dense decode arena.

    x: [1, T, d] — one flat stream of N segments described by ``seg``
    (see ``PackedSegs``); cache_k/v: [B, R, Hkv, Dh].  Same arena-direct
    contract as ``attn_chunk`` but without padded rows: each token attends
    over its OWN segment's ring history plus the causally-visible tokens of
    the same segment inside the stream.  On TPU with a tile-aligned stream
    the attention sweep runs in the Pallas ``packed_prefill_attention``
    kernel (the dense arena is presented as a pool of ring pages).

    Returns (out [1, T, d_model], new_cache_k, new_cache_v).
    """
    _, T, _ = x.shape
    B, R = cache_k.shape[0], cache_k.shape[1]
    positions = seg.positions[None]                              # [1, T]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head,
                           positions, theta, qk_norm)
    q, k, v = q[0], k[0], v[0]                                   # [T, ...]

    if _use_packed_kernel(pack_align, T, softcap, window):
        from repro.kernels import ops as _kops
        # the dense arena as a page pool: each slot's ring cut into pages
        # of Pd tokens (the largest power of two <= 128 dividing R), a
        # segment's block-table row its slot's pages in ring order (pad
        # segments' slot B maps past the pool: the sentinel)
        Pd = math.gcd(R, 128)
        nr = R // Pd
        rows = (seg.slots[:, None] * nr
                + jnp.arange(nr, dtype=jnp.int32)[None, :])
        ctx = _kops.packed_prefill_attention(
            q, k, v, cache_k.reshape(B * nr, Pd, *cache_k.shape[2:]),
            cache_v.reshape(B * nr, Pd, *cache_v.shape[2:]), rows,
            seg.starts, seg.offsets, seg.lengths,
            ring=R, window=int(window), bq=pack_align)
        ctx = ctx.reshape(T, n_heads * d_head)
    else:
        row = jnp.clip(seg.slots, 0, B - 1)
        prev_k = cache_k[row]                                    # [N, R, ...]
        prev_v = cache_v[row]
        s_idx = jnp.arange(R, dtype=jnp.int32)
        offs = seg.offsets
        prev_pos = (offs[:, None] - 1
                    - ((offs[:, None] - 1 - s_idx[None, :]) % R))
        ctx = _packed_attention_jax(
            q, k, v, prev_k, prev_v, prev_pos, seg,
            n_heads=n_heads, n_kv_heads=n_kv_heads, d_head=d_head,
            window=window, softcap=softcap)
    out = matmul(ctx[None].astype(x.dtype), params["wo"])

    # arena write: identical ring discipline to attn_chunk, per token
    keep = seg.valid & (seg.jj >= seg.lens_tok - R)
    w_slot = jnp.where(keep, seg.tok_slot, B)
    w_idx = jnp.where(keep, seg.positions % R, R)
    new_k = cache_k.at[w_slot, w_idx].set(k, mode="drop")
    new_v = cache_v.at[w_slot, w_idx].set(v, mode="drop")
    return out, new_k, new_v


def attn_chunk_packed_paged(params, x, seg: PackedSegs, cache, block_table,
                            *, n_heads, n_kv_heads, d_head, theta, window,
                            softcap=0.0, qk_norm=False, pack_align: int = 0):
    """Packed-stream chunked prefill writing K/V into the paged block pool.

    Same stream contract as ``attn_chunk_packed``; the arena is the pool
    ``cache`` ([n_pages, P, ...]) addressed via ``block_table`` [B, W]
    exactly as in ``attn_chunk_paged`` (ring span R, sentinel pages drop,
    quantized — int8 or packed-int4 — pools dequantize history / quantize
    writes).  On TPU the float pool path runs the Pallas kernel with the
    segments' block-table rows scalar-prefetched.

    Returns (out [1, T, d_model], new_cache dict).
    """
    from repro.serving.quantized_cache import (
        dequantize, pack_int4, quantize_token, quantize_token_int4,
        unpack_int4)

    _, T, _ = x.shape
    n_pages, P = cache["k"].shape[0], cache["k"].shape[1]
    B, W = block_table.shape[0], block_table.shape[1]
    capacity = n_pages * P
    try:
        w_static = int(window)
    except Exception as e:          # pragma: no cover - window is per-run static
        raise ValueError("paged attention needs a trace-time window") from e
    R = min(w_static, capacity) if w_static > 0 else capacity
    S = W * P
    quant = "k_scale" in cache
    q4 = quant and cache["k"].dtype == jnp.uint8  # packed nibble pages

    positions = seg.positions[None]                              # [1, T]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head,
                           positions, theta, qk_norm)
    q, k, v = q[0], k[0], v[0]                                   # [T, ...]
    bt_rows = jnp.asarray(block_table, jnp.int32)[
        jnp.clip(seg.slots, 0, B - 1)]                           # [N, W]

    if _use_packed_kernel(pack_align, T, softcap, window, quantized=quant):
        from repro.kernels import ops as _kops
        ctx = _kops.packed_prefill_attention(
            q, k, v, cache["k"], cache["v"], bt_rows,
            seg.starts, seg.offsets, seg.lengths,
            ring=R, window=w_static, bq=pack_align)
        ctx = ctx.reshape(T, n_heads * d_head)
    else:
        pages = jnp.clip(bt_rows, 0, n_pages - 1)
        if quant:
            raw_k, raw_v = cache["k"][pages], cache["v"][pages]
            if q4:
                raw_k, raw_v = unpack_int4(raw_k), unpack_int4(raw_v)
            prev_k = dequantize(raw_k, cache["k_scale"][pages])
            prev_v = dequantize(raw_v, cache["v_scale"][pages])
            prev_k = prev_k.astype(x.dtype)
            prev_v = prev_v.astype(x.dtype)
        else:
            prev_k = cache["k"][pages]            # [N, W, P, Hkv, Dh]
            prev_v = cache["v"][pages]
        N = bt_rows.shape[0]
        prev_k = prev_k.reshape(N, S, n_kv_heads, d_head)
        prev_v = prev_v.reshape(N, S, n_kv_heads, d_head)
        s_idx = jnp.arange(S, dtype=jnp.int32)
        offs = seg.offsets
        prev_pos = (offs[:, None] - 1
                    - ((offs[:, None] - 1 - s_idx[None, :]) % R))
        prev_pos = jnp.where(s_idx[None, :] < R, prev_pos, -1)
        unalloc = jnp.repeat(bt_rows >= n_pages, P, axis=1)      # [N, S]
        prev_pos = jnp.where(unalloc, -1, prev_pos)
        ctx = _packed_attention_jax(
            q, k, v, prev_k, prev_v, prev_pos, seg,
            n_heads=n_heads, n_kv_heads=n_kv_heads, d_head=d_head,
            window=window, softcap=softcap)
    out = matmul(ctx[None].astype(x.dtype), params["wo"])

    # pool write: ring discipline through the block table, per token
    keep = seg.valid & (seg.jj >= seg.lens_tok - R)
    valid_row = (seg.tok_slot >= 0) & (seg.tok_slot < B)
    ridx = seg.positions % R
    bt_tok = bt_rows[seg.seg_id]                                 # [T, W]
    w_page = jnp.take_along_axis(bt_tok, (ridx // P)[:, None], axis=1)[:, 0]
    w_page = jnp.where(keep & valid_row, w_page, n_pages)
    w_off = jnp.where(keep, ridx % P, P)
    new_cache = dict(cache)
    if quant:
        if q4:
            k_q, k_s = quantize_token_int4(k)     # [T,Hkv,Dh],[T,Hkv]
            v_q, v_s = quantize_token_int4(v)
            k_q, v_q = pack_int4(k_q), pack_int4(v_q)
        else:
            k_q, k_s = quantize_token(k)
            v_q, v_s = quantize_token(v)
        new_cache["k"] = cache["k"].at[w_page, w_off].set(k_q, mode="drop")
        new_cache["k_scale"] = cache["k_scale"].at[w_page, w_off].set(
            k_s, mode="drop")
        new_cache["v"] = cache["v"].at[w_page, w_off].set(v_q, mode="drop")
        new_cache["v_scale"] = cache["v_scale"].at[w_page, w_off].set(
            v_s, mode="drop")
    else:
        new_cache["k"] = cache["k"].at[w_page, w_off].set(k, mode="drop")
        new_cache["v"] = cache["v"].at[w_page, w_off].set(v, mode="drop")
    return out, new_cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _q8_sweep(q, ck, cks, cv, cvs, valid, *, n_heads, n_kv_heads, d_head,
              softcap):
    """The s8 x s8 decode attention sweep shared by the dense and paged
    int8 paths (see ``attn_decode_q8`` for the math / HALO reading).

    q: [B, 1, H, Dh] float; ck/cv: int8 [B, S, Hkv, Dh] (dense arena or a
    block-table gather of the page pool); cks/cvs: f32 [B, S, Hkv] scales;
    valid: [B, S] entry mask.  Returns ctx f32 [B, Hkv, G, Dh].
    """
    from repro.serving.quantized_cache import quantize_token

    B = q.shape[0]
    Hkv = n_kv_heads
    G = n_heads // Hkv
    # quantize q per head; s8 x s8 scores [B,Hkv,G,Dh].[B,S,Hkv,Dh]
    q_q, q_s = quantize_token(q.reshape(B, Hkv, G, d_head))
    s_i32 = jax.lax.dot_general(
        q_q, ck, (((3,), (3,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.int32)                      # [B,Hkv,G,S]
    scores = (s_i32.astype(jnp.float32)
              * q_s[..., None]
              * cks.transpose(0, 2, 1)[:, :, None, :])
    scores = scores / math.sqrt(d_head)
    scores = _maybe_softcap(scores, softcap)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)                    # [B,Hkv,G,S]
    # fold v_scale into p, re-quantize, s8 x s8 attn_v
    p_scaled = probs * cvs.transpose(0, 2, 1)[:, :, None, :]
    p_q, p_s = quantize_token(p_scaled)                        # scale [B,Hkv,G]
    ctx_i32 = jax.lax.dot_general(
        p_q, cv, (((3,), (1,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.int32)                      # [B,Hkv,G,Dh]
    return ctx_i32.astype(jnp.float32) * p_s[..., None]


def attn_decode_q8(params, x, cache, pos, *, n_heads, n_kv_heads,
                   d_head, theta, window, softcap=0.0, qk_norm=False,
                   slot=None, extra_mask=None):
    """int8-KV decode — the HALO-faithful memory format (CiD computes int8
    end to end; Section IV-A).  The cache stores int8 values with one f32
    scale per (token, kv-head); BOTH attention contractions run as s8 x s8
    ``dot_general`` (the MXU's native int8 path, = CiD's 8-bit bank MACs):

      scores[s] = (q_q . k_q[s]) * q_scale * k_scale[s]
      out       = (p'_q . v_q)   * p'_scale          with p' = p * v_scale[s]

    folding the per-token v_scale into p BEFORE quantizing keeps the second
    contraction exact up to int8 rounding.  HBM traffic per token: S*(Hkv*Dh
    + 4) bytes per cache side — 2x less than bf16, 4x less than f32.

    cache: {"k": int8 [B,S,Hkv,Dh], "k_scale": f32 [B,S,Hkv], "v", "v_scale"}
    """
    from repro.distributed.policy import constrain
    from repro.serving.quantized_cache import quantize_token

    B = x.shape[0]
    S = cache["k"].shape[1]
    pos_in = jnp.asarray(pos, jnp.int32)
    pos = jnp.broadcast_to(pos_in, (B,))
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head,
                           pos[:, None], theta, qk_norm)
    # quantize the new K/V entry (per kv-head) and splice into the arena
    k_q, k_s = quantize_token(k)                       # [B,1,Hkv,Dh],[B,1,Hkv]
    v_q, v_s = quantize_token(v)
    if slot is None:
        slot = pos_in % S if pos_in.ndim == 0 else pos % S
    slot = jnp.asarray(slot, jnp.int32)
    if slot.ndim == 0:
        ck = jax.lax.dynamic_update_slice(cache["k"], k_q, (0, slot, 0, 0))
        cks = jax.lax.dynamic_update_slice(cache["k_scale"], k_s, (0, slot, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v_q, (0, slot, 0, 0))
        cvs = jax.lax.dynamic_update_slice(cache["v_scale"], v_s, (0, slot, 0))
    else:
        bidx = jnp.arange(B)
        ck = cache["k"].at[bidx, slot].set(k_q[:, 0])
        cks = cache["k_scale"].at[bidx, slot].set(k_s[:, 0])
        cv = cache["v"].at[bidx, slot].set(v_q[:, 0])
        cvs = cache["v_scale"].at[bidx, slot].set(v_s[:, 0])

    slots = jnp.arange(S, dtype=jnp.int32)
    written = slots[None, :] <= pos[:, None]
    wrapped = pos[:, None] >= S
    valid = written | wrapped
    if extra_mask is not None:
        valid = valid & extra_mask
    ctx = _q8_sweep(q, ck, cks, cv, cvs, valid, n_heads=n_heads,
                    n_kv_heads=n_kv_heads, d_head=d_head, softcap=softcap)
    ctx = ctx.reshape(B, 1, n_heads * d_head).astype(x.dtype)
    out = matmul(ctx, params["wo"])
    new_cache = {"k": ck, "k_scale": cks, "v": cv, "v_scale": cvs}
    return out, new_cache


def attn_decode(params, x, cache_k, cache_v, pos, *, n_heads, n_kv_heads,
                d_head, theta, window, softcap=0.0, qk_norm=False,
                slot=None, extra_mask=None):
    """One-token decode against a (possibly ring-buffer) KV cache.

    x: [B, 1, d_model]; cache_k/v: [B, S, Hkv, Dh]; pos: scalar or [B] int32
    (absolute position of the NEW token).  Returns (out [B,1,d], new_k, new_v).

    ``slot`` optionally overrides the physical write index (serving engine
    with right-padded prompts); ``extra_mask`` [B, S] marks additionally
    invalid cache entries (e.g. prompt padding).

    The cache sequence axis S may be sharded across the 'model' mesh axis;
    the softmax over S then lowers to local GEMVs + tiny all-reduces
    (flash-decode semantics via GSPMD).
    """
    B = x.shape[0]
    S = cache_k.shape[1]
    pos_in = jnp.asarray(pos, jnp.int32)
    pos = jnp.broadcast_to(pos_in, (B,))
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head,
                           pos[:, None], theta, qk_norm)
    # ring-buffer slot for the new entry: scalar -> dynamic_update_slice
    # (dry-run / aligned batch), per-batch vector -> scatter (serving engine)
    if slot is None:
        slot = pos_in % S if pos_in.ndim == 0 else pos % S
    slot = jnp.asarray(slot, jnp.int32)
    if slot.ndim == 0:
        ck = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))
    else:
        bidx = jnp.arange(B)
        ck = cache_k.at[bidx, slot].set(k[:, 0])
        cv = cache_v.at[bidx, slot].set(v[:, 0])
    from repro.distributed.policy import constrain
    ck = constrain(ck, "kv_bshd")
    cv = constrain(cv, "kv_bshd")

    Hkv = n_kv_heads
    G = n_heads // Hkv
    qg = q.reshape(B, Hkv, G, d_head)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, ck,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d_head)
    scores = _maybe_softcap(scores, softcap)
    # validity: slot s was written iff s <= pos (before wrap) else always.
    slots = jnp.arange(S, dtype=jnp.int32)
    written = slots[None, :] <= pos[:, None]
    wrapped = pos[:, None] >= S
    valid = written | wrapped
    if extra_mask is not None:
        valid = valid & extra_mask
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhgs,bshd->bhgd", probs.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    ctx = ctx.reshape(B, 1, n_heads * d_head).astype(x.dtype)
    out = matmul(ctx, params["wo"])
    return out, ck, cv


# ---------------------------------------------------------------------------
# decode (paged pool)
# ---------------------------------------------------------------------------

def _paged_ring(window, n_pages: int, page_size: int) -> int:
    """Logical ring span of a paged run: min(window, pool capacity)."""
    capacity = n_pages * page_size
    w = int(window)                 # per-run static (trace-time constant)
    return min(w, capacity) if w > 0 else capacity


def attn_decode_paged(params, x, cache, block_table, pos, *, n_heads,
                      n_kv_heads, d_head, theta, window, softcap=0.0,
                      qk_norm=False):
    """One-token decode against the paged block pool, routed through the
    Pallas paged flash-decode kernel (kernels/decode_attention.py).

    x: [B, 1, d_model]; cache: {"k","v"} of [n_pages, P, Hkv, Dh];
    block_table: [B, W] int32 (sentinel >= n_pages: unallocated — the
    engine hands inactive slots all-sentinel rows, so their writes drop);
    pos: [B] absolute position of the NEW token.  Returns (out, new_cache).
    """
    from repro.kernels import ops as _kops

    B = x.shape[0]
    k_pages, v_pages = cache["k"], cache["v"]
    n_pages, P = k_pages.shape[0], k_pages.shape[1]
    R = _paged_ring(window, n_pages, P)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head,
                           pos[:, None], theta, qk_norm)
    bt = jnp.asarray(block_table, jnp.int32)
    # write the new entry through the block table (ring index within R)
    bidx = jnp.arange(B)
    ridx = pos % R
    w_page = bt[bidx, ridx // P]                 # sentinel rows drop
    ck = k_pages.at[w_page, ridx % P].set(k[:, 0], mode="drop")
    cv = v_pages.at[w_page, ridx % P].set(v[:, 0], mode="drop")
    # ring validity: slot s written iff s <= pos (before wrap) else always
    # -> exactly min(pos + 1, R) valid leading logical entries
    lengths = jnp.minimum(pos + 1, R)
    if softcap and softcap > 0.0:
        # the kernel has no softcap path; gather a dense view and reuse the
        # reference math (softcapped GQA decode is not on the paper's path)
        _note_route("paged_decode.jnp")
        gk = ck[jnp.clip(bt, 0, n_pages - 1)].reshape(
            B, -1, n_kv_heads, d_head)
        gv = cv[jnp.clip(bt, 0, n_pages - 1)].reshape(
            B, -1, n_kv_heads, d_head)
        Hkv, G = n_kv_heads, n_heads // n_kv_heads
        qg = q.reshape(B, Hkv, G, d_head)
        s = jnp.einsum("bhgd,bshd->bhgs", qg, gk,
                       preferred_element_type=jnp.float32) / math.sqrt(d_head)
        s = _maybe_softcap(s, softcap)
        S = gk.shape[1]
        ok = (jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]) \
            & ~jnp.repeat(bt >= n_pages, P, axis=1)
        s = jnp.where(ok[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhgs,bshd->bhgd", p.astype(gv.dtype), gv,
                         preferred_element_type=jnp.float32)
    else:
        _note_route("paged_decode.kernel")
        ctx = _kops.paged_decode_attention(
            q.reshape(B, n_heads, d_head), ck, cv, bt, lengths)
    ctx = ctx.reshape(B, 1, n_heads * d_head).astype(x.dtype)
    out = matmul(ctx, params["wo"])
    return out, {"k": ck, "v": cv}


def attn_decode_q8_paged(params, x, cache, block_table, pos, *, n_heads,
                         n_kv_heads, d_head, theta, window, softcap=0.0,
                         qk_norm=False):
    """int8 paged decode: the HALO-faithful memory format on the block pool.

    cache: {"k": int8 [n_pages,P,Hkv,Dh], "k_scale": f32 [n_pages,P,Hkv],
    "v", "v_scale"} — scales ride in a parallel page array under the SAME
    block table.  Both contractions run s8 x s8 exactly like the dense
    ``attn_decode_q8``; the pool is gathered into a per-sequence view
    first (the CiD analogue: the bank reads whole rows, the row decoder is
    the block table).
    """
    from repro.serving.quantized_cache import quantize_token

    B = x.shape[0]
    n_pages, P = cache["k"].shape[0], cache["k"].shape[1]
    Hkv = n_kv_heads
    R = _paged_ring(window, n_pages, P)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head,
                           pos[:, None], theta, qk_norm)
    k_q, k_s = quantize_token(k)                   # [B,1,Hkv,Dh],[B,1,Hkv]
    v_q, v_s = quantize_token(v)
    bt = jnp.asarray(block_table, jnp.int32)
    bidx = jnp.arange(B)
    ridx = pos % R
    w_page = bt[bidx, ridx // P]
    off = ridx % P
    ck = cache["k"].at[w_page, off].set(k_q[:, 0], mode="drop")
    cks = cache["k_scale"].at[w_page, off].set(k_s[:, 0], mode="drop")
    cv = cache["v"].at[w_page, off].set(v_q[:, 0], mode="drop")
    cvs = cache["v_scale"].at[w_page, off].set(v_s[:, 0], mode="drop")

    # gather the sequence's pages (int8 + scales) through the block table
    _note_route("paged_decode.jnp")
    rows = jnp.clip(bt, 0, n_pages - 1)
    S = bt.shape[1] * P
    gk = ck[rows].reshape(B, S, Hkv, d_head)       # int8
    gks = cks[rows].reshape(B, S, Hkv)
    gv = cv[rows].reshape(B, S, Hkv, d_head)
    gvs = cvs[rows].reshape(B, S, Hkv)
    lengths = jnp.minimum(pos + 1, R)
    valid = (jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]) \
        & ~jnp.repeat(bt >= n_pages, P, axis=1)
    ctx = _q8_sweep(q, gk, gks, gv, gvs, valid, n_heads=n_heads,
                    n_kv_heads=n_kv_heads, d_head=d_head, softcap=softcap)
    ctx = ctx.reshape(B, 1, n_heads * d_head).astype(x.dtype)
    out = matmul(ctx, params["wo"])
    return out, {"k": ck, "k_scale": cks, "v": cv, "v_scale": cvs}


def attn_decode_q4_paged(params, x, cache, block_table, pos, *, n_heads,
                         n_kv_heads, d_head, theta, window, softcap=0.0,
                         qk_norm=False):
    """Packed-int4 paged decode: quarter-width KV bytes on the block pool.

    cache: {"k": uint8 [n_pages,P,Hkv,Dh//2] (nibble pairs), "k_scale": f32
    [n_pages,P,Hkv], "v", "v_scale"}.  The new token is quantized to int4
    per kv-head, packed, and scattered through the block table exactly like
    the q8 path; the sweep runs in the Pallas ``paged_decode_attention_q4``
    kernel, which unpacks and dequantizes in-register so the HBM bytes per
    step stay at the packed width (softcap falls back to a gathered dense
    reference view, mirroring ``attn_decode_paged``)."""
    from repro.kernels import ops as _kops
    from repro.serving.quantized_cache import (
        dequantize, pack_int4, quantize_token_int4, unpack_int4)

    B = x.shape[0]
    n_pages, P = cache["k"].shape[0], cache["k"].shape[1]
    Hkv = n_kv_heads
    R = _paged_ring(window, n_pages, P)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, d_head,
                           pos[:, None], theta, qk_norm)
    k_q, k_s = quantize_token_int4(k)              # [B,1,Hkv,Dh],[B,1,Hkv]
    v_q, v_s = quantize_token_int4(v)
    k_q, v_q = pack_int4(k_q), pack_int4(v_q)      # [B,1,Hkv,Dh//2] uint8
    bt = jnp.asarray(block_table, jnp.int32)
    bidx = jnp.arange(B)
    ridx = pos % R
    w_page = bt[bidx, ridx // P]
    off = ridx % P
    ck = cache["k"].at[w_page, off].set(k_q[:, 0], mode="drop")
    cks = cache["k_scale"].at[w_page, off].set(k_s[:, 0], mode="drop")
    cv = cache["v"].at[w_page, off].set(v_q[:, 0], mode="drop")
    cvs = cache["v_scale"].at[w_page, off].set(v_s[:, 0], mode="drop")

    lengths = jnp.minimum(pos + 1, R)
    if softcap and softcap > 0.0:
        _note_route("paged_decode.jnp")
        rows = jnp.clip(bt, 0, n_pages - 1)
        S = bt.shape[1] * P
        gk = dequantize(unpack_int4(ck[rows]), cks[rows]).reshape(
            B, S, Hkv, d_head)
        gv = dequantize(unpack_int4(cv[rows]), cvs[rows]).reshape(
            B, S, Hkv, d_head)
        G = n_heads // Hkv
        qg = q.reshape(B, Hkv, G, d_head)
        s = jnp.einsum("bhgd,bshd->bhgs", qg, gk.astype(q.dtype),
                       preferred_element_type=jnp.float32) / math.sqrt(d_head)
        s = _maybe_softcap(s, softcap)
        ok = (jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]) \
            & ~jnp.repeat(bt >= n_pages, P, axis=1)
        s = jnp.where(ok[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhgs,bshd->bhgd", p, gv,
                         preferred_element_type=jnp.float32)
    else:
        _note_route("paged_decode.kernel")
        ctx = _kops.paged_decode_attention_q4(
            q.reshape(B, n_heads, d_head), ck, cks, cv, cvs, bt, lengths)
    ctx = ctx.reshape(B, 1, n_heads * d_head).astype(x.dtype)
    out = matmul(ctx, params["wo"])
    return out, {"k": ck, "k_scale": cks, "v": cv, "v_scale": cvs}
