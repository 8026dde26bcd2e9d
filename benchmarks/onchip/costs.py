"""Operations and bytes the algorithm needs, from shapes and live lengths.

Every count here is of the work a correct implementation has to do, never
of the grid a kernel happens to walk: attention runs over the live context
only (causal within a prefill chunk), logits are one row per decode token
and one per prefill segment end, and each weight and each live KV entry is
read once per step.  A kernel that stops walking the whole page pool then
shows as a higher share of its roofline, not as a changed count.

``m`` is a model configuration dict with the Hugging Face key names
(``hidden_size``, ``num_attention_heads``, ...); ``itemsize`` is that of
the served dtype (2 for bf16)."""

from __future__ import annotations

from typing import Iterable, Sequence, Tuple

Segment = Tuple[int, int]          # (history tokens already cached, new tokens)


def _dims(m):
    return (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
            m["num_hidden_layers"], m["vocab_size"])


def layer_matmul_params(m) -> int:
    """Weights one decoder layer multiplies by: q, k, v, o and the gated
    feed-forward (norm scales are elementwise and not counted)."""
    d, H, Hkv, D, ff, _, _ = _dims(m)
    return d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * ff


def head_params(m) -> int:
    d, *_, V = _dims(m)
    return d * V


def kv_bytes_per_token(m, itemsize: int = 2) -> int:
    d, H, Hkv, D, ff, L, V = _dims(m)
    return 2 * L * Hkv * D * itemsize


def causal_pairs(seg: Segment) -> int:
    """(query, key) pairs of one prefill segment: query j (1-based) of a
    segment with ``o`` cached tokens sees ``o + j`` keys."""
    o, t = seg
    return t * o + t * (t + 1) // 2


# -- kernels (one call = one layer) --------------------------------------------

def paged_decode_kernel(m, ctx_lens: Sequence[int], itemsize: int = 2):
    """FLOPs and bytes of one layer's paged decode attention: each row's
    query against its ``ctx`` live keys; K and V of the live context read
    once, the query and output written once."""
    d, H, Hkv, D, *_ = _dims(m)
    n = sum(ctx_lens)
    flops = 4 * H * D * n
    nbytes = 2 * Hkv * D * itemsize * n + 2 * len(ctx_lens) * H * D * itemsize
    return flops, nbytes


def packed_prefill_kernel(m, segments: Iterable[Segment], itemsize: int = 2):
    """FLOPs and bytes of one layer's packed prefill attention over the
    tick's segments: causal pairs only; history and new K/V read once,
    queries read and outputs written once."""
    d, H, Hkv, D, *_ = _dims(m)
    segments = list(segments)
    pairs = sum(causal_pairs(s) for s in segments)
    new = sum(t for _, t in segments)
    ctx = sum(o + t for o, t in segments)
    flops = 4 * H * D * pairs
    nbytes = 2 * Hkv * D * itemsize * ctx + 2 * new * H * D * itemsize
    return flops, nbytes


# -- whole model steps ---------------------------------------------------------

def decode_step(m, ctx_lens: Sequence[int], itemsize: int = 2):
    """One decode step over a batch with live contexts ``ctx_lens`` (each
    counting the token being decoded): matmul FLOPs per token, one logits
    row per token, attention over the live context; bytes are every
    matmul weight and the head once, the live K/V once, and the new K/V
    written."""
    d, H, Hkv, D, ff, L, V = _dims(m)
    B = len(ctx_lens)
    att_f, att_b = paged_decode_kernel(m, ctx_lens, itemsize)
    flops = B * (2 * L * layer_matmul_params(m) + 2 * head_params(m)) \
        + L * att_f
    nbytes = (L * layer_matmul_params(m) + head_params(m)) * itemsize \
        + L * 2 * Hkv * D * itemsize * sum(ctx_lens) \
        + B * kv_bytes_per_token(m, itemsize)
    return flops, nbytes


def prefill_step(m, segments: Iterable[Segment], itemsize: int = 2):
    """One packed prefill step: matmul FLOPs for every new token, one
    logits row per segment, causal attention per layer; bytes are the
    weights and head once, history and new K/V read once, new K/V
    written."""
    d, H, Hkv, D, ff, L, V = _dims(m)
    segments = list(segments)
    new = sum(t for _, t in segments)
    att_f, att_b = packed_prefill_kernel(m, segments, itemsize)
    flops = 2 * new * L * layer_matmul_params(m) \
        + 2 * len(segments) * head_params(m) + L * att_f
    nbytes = (L * layer_matmul_params(m) + head_params(m)) * itemsize \
        + L * att_b + new * kv_bytes_per_token(m, itemsize)
    return flops, nbytes


def ideal_seconds(flops: float, nbytes: float, peaks) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_s)
