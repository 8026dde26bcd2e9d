"""Plain float32 reference of a Qwen3 dense decoder, as the published
``Qwen3ForCausalLM`` computes it.

It imports nothing of the program under test. Weights come in the
published layout (the ``hidden_size x out`` orientation of the
program's matmuls, rotary pairs ``(j, j + head_dim / 2)``):

    {"embed": [Vp, d], "head": [d, Vp] or None (tied), "final_norm": [d],
     "layers": {"ln1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "ln2",
                "w_gate", "w_up", "w_down"}}     # each stacked [L, ...]

(``Vp`` may exceed ``vocab_size``; rows past it are never read.)

Every layer: RMSNorm (eps ``rms_norm_eps``), q/k/v projections, per-head
RMSNorm of q and k (eps ``rms_norm_eps`` as in Qwen3, or ``qk_norm_eps``
where the configuration gives it), rotary embedding with ``rope_theta`` by
``rotate_half``, causal grouped-query attention, output projection,
residual; RMSNorm, SwiGLU feed-forward, residual. Then the final norm and
the head. Every matmul runs in float32 at ``Precision.HIGHEST`` (a TPU
otherwise runs float32 matmuls in bf16 passes). Attention is computed in
blocks of query rows, so a 9k-token sequence fits one chip.

``weight_bits=8`` rounds every matmul weight to int8 per output channel
(symmetric, scale max|w| / 127) before use: the control, the model
computed one precision step below the bf16 it is served in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _int8(w):
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-30) / 127
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _rope(x, pos, theta):
    """x [T, h, D], pos [T]: rotate pairs (j, j + D/2) by pos * theta^(-2j/D)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2
                          / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(m, weight_bits, x, lw, pos):
    T = x.shape[0]
    H, Hkv, D = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    G, eps, qk_eps = H // Hkv, m["rms_norm_eps"], m["qk_norm_eps"]
    w = {k: (_int8(v) if weight_bits == 8 and k in MATMULS
             else v.astype(jnp.float32)) for k, v in lw.items()}

    def mm(a, b):
        return jnp.matmul(a, b, precision=_HI)

    h = _rms(x, w["ln1"], eps)
    q = _rms(mm(h, w["wq"]).reshape(T, H, D), w["q_norm"], qk_eps)
    k = _rms(mm(h, w["wk"]).reshape(T, Hkv, D), w["k_norm"], qk_eps)
    v = mm(h, w["wv"]).reshape(T, Hkv, D)
    q = _rope(q, pos, m["rope_theta"]).reshape(T, Hkv, G, D)
    k = _rope(k, pos, m["rope_theta"])
    nb = T // Q_BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        pb = jax.lax.dynamic_slice_in_dim(pos, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=_HI) / np.sqrt(D)
        s = jnp.where(pos[None, None, None, :] <= pb[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=_HI)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(T, H * D)
    x = x + mm(o, w["wo"])
    h = _rms(x, w["ln2"], eps)
    return x + mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
                  w["w_down"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _hidden(m_items, weight_bits, weights, tokens):
    m = dict(m_items)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, lw):
        return _layer(m, weight_bits, x, lw, pos), None
    x, _ = jax.lax.scan(body, x, weights["layers"])
    return x


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _logits(m_items, weight_bits, weights, h, block):
    """Logits of the vocabulary rows ``[block, block + VOCAB_BLOCK)``: the
    head is upcast one block at a time, so float32 never holds all of it.
    Rows past the model's vocabulary (the program's padding) are never
    read."""
    m = dict(m_items)
    h = _rms(h, weights["final_norm"], m["rms_norm_eps"])
    end = min(block + VOCAB_BLOCK, m["vocab_size"])
    head = weights["embed"][block:end].T if weights["head"] is None \
        else weights["head"][:, block:end]
    head = _int8(head) if weight_bits == 8 else head.astype(jnp.float32)
    return jnp.matmul(h, head, precision=_HI)


MODEL_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
              "rms_norm_eps", "rope_theta", "vocab_size")
VOCAB_BLOCK = 32768


def padded_len(n: int) -> int:
    """A sequence length the reference compiles for: a multiple of the
    query block, in steps of 2,048 past 2,048."""
    step = Q_BLOCK if n <= 2048 else 2048
    return -(-n // step) * step


def logits_at(weights, m, tokens, out_positions, weight_bits: int = 16,
              length: int = 0, rows: int = 0):
    """float32 logits [len(out_positions), V] of one causal pass over
    ``tokens``. The sequence is padded to ``padded_len`` of ``length`` (or
    of its own length, if longer; causal attention keeps the padding out
    of every real position) and the positions to ``rows``, so that one
    compiled program serves every request of a cell."""
    tokens = np.asarray(tokens, np.int32)
    out = np.asarray(out_positions, np.int32)
    padded = np.zeros(padded_len(max(length, len(tokens))), np.int32)
    padded[:len(tokens)] = tokens
    pos = np.zeros(max(rows, len(out)), np.int32)
    pos[:len(out)] = out
    items = tuple((k, m[k]) for k in MODEL_KEYS) + (
        ("qk_norm_eps", m.get("qk_norm_eps", m["rms_norm_eps"])),)
    h = _hidden(items, weight_bits, weights, jnp.asarray(padded))
    h = h[jnp.asarray(pos)]
    return np.concatenate([
        np.asarray(_logits(items, weight_bits, weights, h, b))
        for b in range(0, m["vocab_size"], VOCAB_BLOCK)], axis=-1)[:len(out)]
