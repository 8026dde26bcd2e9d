"""Plain float32 ``jax.numpy`` forward of a dense GQA decoder — the oracle
served logits are compared with.

No kernels, no KV cache, no chunking, no packing: one causal pass over a
whole sequence, every weight upcast to float32 as it is used and every
matmul at full float32 precision (``Precision.HIGHEST``; a TPU otherwise
runs float32 matmuls in bf16 passes).  It covers the plans the paged
serving path runs for GQA models — dense FFN, qk-norm, sliding windows,
logit softcap, tied or untied heads — and refuses the rest (MLA, MoE,
SSM, multi-codebook) rather than approximate them.

Served logits are compared with it by TEACHER FORCING: feed prompt +
generated tokens, and read the logits at the positions that predicted
each generated token (``reference_logits(..., out_positions)``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.transformer import build_plan

_HI = jax.lax.Precision.HIGHEST


def _f32(a):
    return a.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=_HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(scale)


def _rope(x, pos, theta):
    """Rotate interleaved pairs (x[2i], x[2i+1]) by pos * theta^(-2i/d).
    x: [T, H, D]; pos: [T]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None, None] * inv          # [T, 1, d/2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _layer(cfg: ModelConfig, window: int, theta: float, lp, x, pos):
    T = x.shape[0]
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["scale"], cfg.norm_eps)
    q = _mm(h, a["wq"]).reshape(T, H, D)
    k = _mm(h, a["wk"]).reshape(T, Hkv, D)
    v = _mm(h, a["wv"]).reshape(T, Hkv, D)
    if cfg.attn.qk_norm:
        q = _rms(q, a["q_norm"], 1e-5)
        k = _rms(k, a["k_norm"], 1e-5)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / math.sqrt(D)
    if cfg.attn.logit_softcap:
        s = cfg.attn.logit_softcap * jnp.tanh(s / cfg.attn.logit_softcap)
    ok = pos[None, :] <= pos[:, None]
    if window > 0:
        ok &= (pos[:, None] - pos[None, :]) < window
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=_HI).reshape(T, H * D)
    x = x + _mm(o, a["wo"])
    h = _rms(x, lp["ln2"]["scale"], cfg.norm_eps)
    f = lp["ffn"]
    g = _mm(h, f["wi_gate"])
    g = jax.nn.gelu(g, approximate=True) if cfg.act == "gelu" \
        else jax.nn.silu(g)
    return x + _mm(g * _mm(h, f["wi_up"]), f["wo"])


def reference_logits(params, cfg: ModelConfig, tokens, out_positions):
    """float32 logits [len(out_positions), V] of one causal pass over
    ``tokens`` [T] (``V`` = the padded vocabulary the served head scores).
    Positions past the last real token may be padding: causal attention
    keeps them out of every earlier position."""
    plan = build_plan(cfg)
    if (cfg.mla.enabled or cfg.n_codebooks > 1
            or any(r.kind != "attn" or r.ffn_kind != "dense" for r in plan)):
        raise NotImplementedError(
            f"{cfg.name}: the float32 reference covers dense GQA plans only")
    tokens = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = _f32(jnp.take(params["embed"], tokens, axis=0))
    for run, rp in zip(plan, params["runs"]):
        def body(xx, lp, run=run):
            return _layer(cfg, run.window, run.theta, lp, xx, pos), None
        x, _ = jax.lax.scan(body, x, rp)
    h = _rms(x[jnp.asarray(out_positions, jnp.int32)],
             params["final_norm"]["scale"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return _mm(h, head)
