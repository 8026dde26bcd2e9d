"""Model assembly: config -> (init, train-forward, prefill, decode_step).

The layer stack is compiled as a sequence of RUNS: maximal contiguous groups
of layers with identical block structure (kind, ffn kind, window, rope theta).
Each run is executed with a single ``jax.lax.scan`` over its stacked
parameters, which keeps compile time O(#distinct-run-shapes) instead of
O(n_layers) — essential for dry-running 60-80 layer configs on 512 host
devices.  Examples:

  qwen3-1.7b      -> 1 run  (28 x attn+dense)
  gemma3-1b       -> 9 runs (5 local | 1 global | ... | 2 local)
  deepseek-v2     -> 2 runs (1 x mla+dense | 59 x mla+moe)
  mamba2-2.7b     -> 1 run  (64 x ssd)
  zamba2-2.7b     -> 18 runs (9 x [6 ssd | shared-attn]); the shared attention
                     block's weights are stored ONCE and reused per invocation.

Caches are a list aligned with the runs; windowed-attention runs allocate a
ring buffer of length min(window, seq), SSD runs a constant-size recurrent
state, MLA runs a compressed-latent cache.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.policy import constrain
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    embed_init,
    ffn,
    ffn_init,
    matmul,
    rmsnorm,
    rmsnorm_init,
)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# run plan
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RunSpec:
    kind: str                 # "attn" | "ssm" | "shared_attn"
    n_layers: int             # 0 for shared_attn
    ffn_kind: str = "dense"   # "dense" | "moe" | "none"
    window: int = 0           # 0 = full attention
    theta: float = 10000.0
    layer_start: int = 0      # first absolute layer index of this run


def build_plan(cfg: ModelConfig) -> List[RunSpec]:
    runs: List[RunSpec] = []
    if cfg.family == "hybrid":
        h = cfg.hybrid
        n_groups = cfg.n_layers // h.shared_attn_every
        for g in range(n_groups):
            runs.append(RunSpec("ssm", h.shared_attn_every, "none",
                                layer_start=g * h.shared_attn_every))
            runs.append(RunSpec("shared_attn", 0, "none"))
        rem = cfg.n_layers - n_groups * h.shared_attn_every
        if rem:
            runs.append(RunSpec("ssm", rem, "none",
                                layer_start=n_groups * h.shared_attn_every))
        return runs

    kinds = cfg.layer_kinds()

    def sig(i: int) -> Tuple[str, str]:
        k = kinds[i]
        f = "none" if (k == "ssm" and cfg.d_ff == 0) else cfg.ffn_kind(i)
        return (k, f)

    i = 0
    while i < cfg.n_layers:
        kind, ffn_kind = sig(i)
        j = i
        while j < cfg.n_layers and sig(j) == (kind, ffn_kind):
            j += 1
        window = 0
        theta = cfg.attn.rope_theta
        if kind == "attn_local":
            window = cfg.attn.sliding_window
            if cfg.attn.rope_local_theta:
                theta = cfg.attn.rope_local_theta
        runs.append(RunSpec("attn" if kind.startswith("attn") else "ssm",
                            j - i, ffn_kind, window, theta, layer_start=i))
        i = j
    return runs


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _stack(key, n: int, init_fn):
    """Stack n independently-initialized param trees along axis 0.  One
    vmapped init instead of n stacked ones: the same values, and a traced
    init that does not grow with depth (a jitted full-width init otherwise
    spends minutes compiling)."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def padded_vocab(cfg: ModelConfig) -> int:
    return -(-cfg.vocab_size // 256) * 256


def _layer_init(key, cfg: ModelConfig, run: RunSpec):
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": rmsnorm_init(d, dtype)}
    if run.kind == "attn":
        if cfg.mla.enabled:
            p["attn"] = mla_mod.mla_init(ks[0], d, cfg.n_heads, cfg.mla, dtype)
        else:
            p["attn"] = attn_mod.attn_init(
                ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, dtype,
                qk_norm=cfg.attn.qk_norm)
        p["ln2"] = rmsnorm_init(d, dtype)
        if run.ffn_kind == "moe":
            p["moe"] = moe_mod.moe_init(ks[1], d, cfg.moe, dtype)
        elif run.ffn_kind == "dense":
            p["ffn"] = ffn_init(ks[1], d, cfg.d_ff, dtype)
    elif run.kind == "ssm":
        p["ssm"] = ssm_mod.ssm_init(ks[0], d, cfg.ssm, dtype)
        if run.ffn_kind == "dense":
            p["ln2"] = rmsnorm_init(d, dtype)
            p["ffn"] = ffn_init(ks[1], d, cfg.d_ff, dtype)
    return p


def _shared_attn_init(key, cfg: ModelConfig):
    """Zamba2 shared transformer block operating on concat([h, embed])."""
    dtype = jnp.dtype(cfg.dtype)
    h = cfg.hybrid
    d_in = cfg.d_model * (2 if h.concat_embedding else 1)
    nh = h.shared_attn_n_heads
    ks = jax.random.split(key, 4)
    return {
        "ln1": rmsnorm_init(d_in, dtype),
        "attn": attn_mod.attn_init(ks[0], d_in, nh, nh, d_in // nh, dtype,
                                   d_model_out=d_in),
        "ln2": rmsnorm_init(d_in, dtype),
        "ffn": ffn_init(ks[1], d_in, cfg.d_ff, dtype),
        "down": jax.random.normal(ks[2], (d_in, cfg.d_model), jnp.float32)
                .astype(dtype) / (d_in ** 0.5),
    }


def init_params(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    V = padded_vocab(cfg)
    plan = build_plan(cfg)
    ks = jax.random.split(key, len(plan) + 4)
    params: Params = {}
    if cfg.n_codebooks > 1:
        params["embed"] = _stack(
            ks[-1], cfg.n_codebooks,
            lambda k: embed_init(k, V, cfg.d_model, dtype))      # [K,V,d]
    else:
        params["embed"] = embed_init(ks[-1], V, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks > 1:
            params["lm_head"] = _stack(
                ks[-2], cfg.n_codebooks,
                lambda k: embed_init(k, V, cfg.d_model, dtype).T)
        else:
            params["lm_head"] = embed_init(ks[-2], V, cfg.d_model, dtype).T
    params["final_norm"] = rmsnorm_init(cfg.d_model, dtype)
    runs_params: List[Params] = []
    shared_done = False
    for r, run in enumerate(plan):
        if run.kind == "shared_attn":
            if not shared_done:
                params["shared_attn"] = _shared_attn_init(ks[r], cfg)
                shared_done = True
            runs_params.append({})                              # weights shared
        else:
            runs_params.append(
                _stack(ks[r], run.n_layers,
                       lambda k, run=run: _layer_init(k, cfg, run)))
    params["runs"] = runs_params
    return params


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def cache_len(run: RunSpec, seq_len: int) -> int:
    if run.window > 0:
        return min(run.window, seq_len)
    return seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> List[Any]:
    """Allocate the decode cache for every run (zeros)."""
    dtype = jnp.dtype(cfg.dtype)
    caches: List[Any] = []
    for run in build_plan(cfg):
        if run.kind == "attn":
            S = cache_len(run, seq_len)
            if cfg.mla.enabled:
                w = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
                caches.append({"latent": jnp.zeros(
                    (run.n_layers, batch, S, w), dtype)})
            else:
                shape = (run.n_layers, batch, S, cfg.n_kv_heads, cfg.d_head)
                caches.append({"k": jnp.zeros(shape, dtype),
                               "v": jnp.zeros(shape, dtype)})
        elif run.kind == "ssm":
            s = cfg.ssm
            di = s.d_inner(cfg.d_model)
            nh = s.n_heads(cfg.d_model)
            conv_dim = di + 2 * s.n_groups * s.d_state
            caches.append({
                "conv": jnp.zeros((run.n_layers, batch, s.d_conv - 1, conv_dim), dtype),
                "state": jnp.zeros((run.n_layers, batch, nh, s.head_dim, s.d_state),
                                   jnp.float32),
            })
        else:  # shared_attn
            h = cfg.hybrid
            d_in = cfg.d_model * (2 if h.concat_embedding else 1)
            dh = d_in // h.shared_attn_n_heads
            shape = (batch, seq_len, h.shared_attn_n_heads, dh)
            caches.append({"k": jnp.zeros(shape, dtype),
                           "v": jnp.zeros(shape, dtype)})
    return caches


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> List[Any]:
    """ShapeDtypeStruct tree mirroring init_cache (dry-run, no allocation)."""
    return jax.eval_shape(lambda: init_cache(cfg, batch, seq_len))


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens):
    """tokens: [B,T] or [B,K,T] (musicgen).  Returns [B,T,d]."""
    if cfg.n_codebooks > 1:
        # sum of per-codebook embeddings (gather per codebook)
        out = 0.0
        for k in range(cfg.n_codebooks):
            out = out + jnp.take(params["embed"][k], tokens[:, k], axis=0)
        return out.astype(jnp.dtype(cfg.dtype))
    return jnp.take(params["embed"], tokens, axis=0)


def lm_logits(params, cfg: ModelConfig, h):
    """h: [B,T,d] -> [B,T,V] (or [B,T,K,V])."""
    if cfg.tie_embeddings:
        table = params["embed"]
        if cfg.n_codebooks > 1:
            return jnp.einsum("btd,kvd->btkv", h, table,
                              preferred_element_type=jnp.float32)
        return jnp.einsum("btd,vd->btv", h, table,
                          preferred_element_type=jnp.float32)
    head = params["lm_head"]
    if cfg.n_codebooks > 1:
        return jnp.einsum("btd,kdv->btkv", h, head,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("btd,dv->btv", h, head,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# run bodies
# ---------------------------------------------------------------------------

def _attn_layer_prefill(cfg, run, lp, x, positions, want_cache: bool):
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    if cfg.mla.enabled:
        a, cache = mla_mod.mla_prefill(lp["attn"], h, positions,
                                       n_heads=cfg.n_heads, m=cfg.mla)
        kv = (cache,)
    else:
        a, (k, v) = attn_mod.attn_prefill(
            lp["attn"], h, positions,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
            theta=run.theta, window=jnp.int32(run.window),
            softcap=cfg.attn.logit_softcap, qk_norm=cfg.attn.qk_norm)
        kv = (k, v)
    x = x + a
    aux = jnp.float32(0.0)
    if run.ffn_kind == "moe":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        f, aux = moe_mod.moe_apply(lp["moe"], h, cfg.moe, cfg.act)
        x = x + f
    elif run.ffn_kind == "dense":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + ffn(lp["ffn"], h, cfg.act)
    if not want_cache:
        kv = None
    return x, kv, aux


def _attn_layer_decode(cfg, run, lp, x, cache, pos):
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    if cfg.mla.enabled:
        a, latent = mla_mod.mla_decode(lp["attn"], h, cache["latent"], pos,
                                       n_heads=cfg.n_heads, m=cfg.mla)
        new_cache = {"latent": latent}
    elif "k_scale" in cache:
        # int8 KV arena (HALO-faithful decode format, serving engine opt-in)
        a, new_cache = attn_mod.attn_decode_q8(
            lp["attn"], h, cache, pos,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
            theta=run.theta, window=jnp.int32(run.window),
            softcap=cfg.attn.logit_softcap, qk_norm=cfg.attn.qk_norm)
        x = x + a
        if run.ffn_kind == "moe":
            h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
            f, _ = moe_mod.moe_apply(lp["moe"], h, cfg.moe, cfg.act)
            x = x + f
        elif run.ffn_kind == "dense":
            h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
            x = x + ffn(lp["ffn"], h, cfg.act)
        return x, new_cache
    else:
        a, ck, cv = attn_mod.attn_decode(
            lp["attn"], h, cache["k"], cache["v"], pos,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
            theta=run.theta, window=jnp.int32(run.window),
            softcap=cfg.attn.logit_softcap, qk_norm=cfg.attn.qk_norm)
        new_cache = {"k": ck, "v": cv}
    x = x + a
    if run.ffn_kind == "moe":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        f, _ = moe_mod.moe_apply(lp["moe"], h, cfg.moe, cfg.act)
        x = x + f
    elif run.ffn_kind == "dense":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + ffn(lp["ffn"], h, cfg.act)
    return x, new_cache


def _attn_layer_decode_paged(cfg, run, lp, x, cache, bt, pos):
    """One attention layer of a paged one-token decode step.

    ``cache`` is the run's page pool slice ([n_pages, P, ...] leaves) and
    ``bt`` the [B, W] block table; per-slot validity is encoded in the
    table (inactive slots carry all-sentinel rows), so no merge-with-mask
    pass is needed — dropped scatters ARE the mask.
    """
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    if cfg.mla.enabled:
        if "latent_scale" in cache:
            a, latent, lscale = mla_mod.mla_decode_paged(
                lp["attn"], h, cache["latent"], bt, pos,
                n_heads=cfg.n_heads, m=cfg.mla,
                scales=cache["latent_scale"])
            new_cache = {"latent": latent, "latent_scale": lscale}
        else:
            a, latent = mla_mod.mla_decode_paged(
                lp["attn"], h, cache["latent"], bt, pos,
                n_heads=cfg.n_heads, m=cfg.mla)
            new_cache = {"latent": latent}
    elif "k_scale" in cache:
        # quantized pools: int8 pages, or packed-int4 (uint8 nibble pairs)
        q_decode = (attn_mod.attn_decode_q4_paged
                    if cache["k"].dtype == jnp.uint8
                    else attn_mod.attn_decode_q8_paged)
        a, new_cache = q_decode(
            lp["attn"], h, cache, bt, pos,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
            theta=run.theta, window=run.window,
            softcap=cfg.attn.logit_softcap, qk_norm=cfg.attn.qk_norm)
    else:
        a, new_cache = attn_mod.attn_decode_paged(
            lp["attn"], h, cache, bt, pos,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
            theta=run.theta, window=run.window,
            softcap=cfg.attn.logit_softcap, qk_norm=cfg.attn.qk_norm)
    x = x + a
    if run.ffn_kind == "moe":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        f, _ = moe_mod.moe_apply(lp["moe"], h, cfg.moe, cfg.act)
        x = x + f
    elif run.ffn_kind == "dense":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + ffn(lp["ffn"], h, cfg.act)
    return x, new_cache


def _attn_layer_chunk(cfg, run, lp, x, offsets, lengths, slots, cache):
    """One attention layer of a packed prefill chunk (arena-direct write)."""
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    if cfg.mla.enabled:
        a, latent = mla_mod.mla_chunk(lp["attn"], h, offsets, lengths, slots,
                                      cache["latent"],
                                      n_heads=cfg.n_heads, m=cfg.mla)
        new_cache = {"latent": latent}
    else:
        a, ck, cv = attn_mod.attn_chunk(
            lp["attn"], h, offsets, lengths, slots, cache["k"], cache["v"],
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
            theta=run.theta, window=jnp.int32(run.window),
            softcap=cfg.attn.logit_softcap, qk_norm=cfg.attn.qk_norm)
        new_cache = {"k": ck, "v": cv}
    x = x + a
    if run.ffn_kind == "moe":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        f, _ = moe_mod.moe_apply(lp["moe"], h, cfg.moe, cfg.act)
        x = x + f
    elif run.ffn_kind == "dense":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + ffn(lp["ffn"], h, cfg.act)
    return x, new_cache


def _attn_layer_chunk_paged(cfg, run, lp, x, offsets, lengths, slots, cache,
                            bt):
    """One attention layer of a packed prefill chunk against the page pool."""
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    if cfg.mla.enabled:
        if "latent_scale" in cache:
            a, latent, lscale = mla_mod.mla_chunk_paged(
                lp["attn"], h, offsets, lengths, slots, cache["latent"], bt,
                n_heads=cfg.n_heads, m=cfg.mla,
                scales=cache["latent_scale"])
            new_cache = {"latent": latent, "latent_scale": lscale}
        else:
            a, latent = mla_mod.mla_chunk_paged(
                lp["attn"], h, offsets, lengths, slots, cache["latent"], bt,
                n_heads=cfg.n_heads, m=cfg.mla)
            new_cache = {"latent": latent}
    else:
        a, new_cache = attn_mod.attn_chunk_paged(
            lp["attn"], h, offsets, lengths, slots, cache, bt,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
            theta=run.theta, window=run.window,
            softcap=cfg.attn.logit_softcap, qk_norm=cfg.attn.qk_norm)
    x = x + a
    if run.ffn_kind == "moe":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        f, _ = moe_mod.moe_apply(lp["moe"], h, cfg.moe, cfg.act)
        x = x + f
    elif run.ffn_kind == "dense":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + ffn(lp["ffn"], h, cfg.act)
    return x, new_cache


def _attn_layer_chunk_packed(cfg, run, lp, x, seg, cache, pack_align):
    """One attention layer of a PACKED prefill stream (arena-direct)."""
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    if cfg.mla.enabled:
        a, latent = mla_mod.mla_chunk_packed(lp["attn"], h, seg,
                                             cache["latent"],
                                             n_heads=cfg.n_heads, m=cfg.mla)
        new_cache = {"latent": latent}
    else:
        a, ck, cv = attn_mod.attn_chunk_packed(
            lp["attn"], h, seg, cache["k"], cache["v"],
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
            theta=run.theta, window=run.window,
            softcap=cfg.attn.logit_softcap, qk_norm=cfg.attn.qk_norm,
            pack_align=pack_align)
        new_cache = {"k": ck, "v": cv}
    x = x + a
    if run.ffn_kind == "moe":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        f, _ = moe_mod.moe_apply(lp["moe"], h, cfg.moe, cfg.act)
        x = x + f
    elif run.ffn_kind == "dense":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + ffn(lp["ffn"], h, cfg.act)
    return x, new_cache


def _attn_layer_chunk_packed_paged(cfg, run, lp, x, seg, cache, bt,
                                   pack_align):
    """One attention layer of a PACKED prefill stream against the page pool."""
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    if cfg.mla.enabled:
        if "latent_scale" in cache:
            a, latent, lscale = mla_mod.mla_chunk_packed_paged(
                lp["attn"], h, seg, cache["latent"], bt,
                n_heads=cfg.n_heads, m=cfg.mla,
                scales=cache["latent_scale"])
            new_cache = {"latent": latent, "latent_scale": lscale}
        else:
            a, latent = mla_mod.mla_chunk_packed_paged(
                lp["attn"], h, seg, cache["latent"], bt,
                n_heads=cfg.n_heads, m=cfg.mla)
            new_cache = {"latent": latent}
    else:
        a, new_cache = attn_mod.attn_chunk_packed_paged(
            lp["attn"], h, seg, cache, bt,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
            theta=run.theta, window=run.window,
            softcap=cfg.attn.logit_softcap, qk_norm=cfg.attn.qk_norm,
            pack_align=pack_align)
    x = x + a
    if run.ffn_kind == "moe":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        f, _ = moe_mod.moe_apply(lp["moe"], h, cfg.moe, cfg.act)
        x = x + f
    elif run.ffn_kind == "dense":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + ffn(lp["ffn"], h, cfg.act)
    return x, new_cache


def _ssm_layer_prefill(cfg, run, lp, x, want_cache: bool):
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    o, (conv_state, state) = ssm_mod.ssm_prefill(lp["ssm"], h, cfg.d_model, cfg.ssm)
    x = x + o
    if run.ffn_kind == "dense":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + ffn(lp["ffn"], h, cfg.act)
    return x, ((conv_state, state) if want_cache else None)


def _ssm_layer_decode(cfg, run, lp, x, cache, pos):
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    o, conv_state, state = ssm_mod.ssm_decode(
        lp["ssm"], h, cache["conv"], cache["state"], cfg.d_model, cfg.ssm)
    x = x + o
    if run.ffn_kind == "dense":
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + ffn(lp["ffn"], h, cfg.act)
    return x, {"conv": conv_state, "state": state}


def _shared_attn_apply(cfg, sp, x, embed0, positions, cache, pos, phase: str):
    """Zamba2 shared block.  Returns (x, new_cache)."""
    h = cfg.hybrid
    inp = jnp.concatenate([x, embed0], axis=-1) if h.concat_embedding else x
    d_in = inp.shape[-1]
    nh = h.shared_attn_n_heads
    dh = d_in // nh
    y = rmsnorm(sp["ln1"], inp, cfg.norm_eps)
    if phase == "decode":
        a, ck, cv = attn_mod.attn_decode(
            sp["attn"], y, cache["k"], cache["v"], pos,
            n_heads=nh, n_kv_heads=nh, d_head=dh,
            theta=cfg.attn.rope_theta, window=jnp.int32(0))
        new_cache = {"k": ck, "v": cv}
    else:
        a, (k, v) = attn_mod.attn_prefill(
            sp["attn"], y, positions,
            n_heads=nh, n_kv_heads=nh, d_head=dh,
            theta=cfg.attn.rope_theta, window=jnp.int32(0))
        new_cache = {"k": k, "v": v}
    inp = inp + a
    y = rmsnorm(sp["ln2"], inp, cfg.norm_eps)
    inp = inp + ffn(sp["ffn"], y, cfg.act)
    x = x + matmul(inp, sp["down"])
    return x, new_cache


# ---------------------------------------------------------------------------
# full-model passes
# ---------------------------------------------------------------------------

def forward(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            *, phase: str = "train", cache: Optional[List[Any]] = None,
            pos=None, remat: bool = False, return_hidden: bool = False,
            block_tables: Optional[List[Any]] = None):
    """Unified forward.

    phase == "train"/"prefill": batch["tokens"] [B,T] (or [B,K,T]); optional
        batch["vision_embeds"] [B,F,d].  Returns (logits, new_cache, aux_loss);
        new_cache is None for train.
    phase == "decode": batch["tokens"] [B,1] (or [B,K,1]); ``cache`` and
        ``pos`` required.  Returns (logits [B,1,...], new_cache, 0.0).
        With ``block_tables`` (one [B, W] table per run) ``cache`` is the
        PAGED pool from ``serving.kv_pool.KVPool`` and decode routes
        through the paged attention paths (requires ``supports_paged``).
    """
    plan = build_plan(cfg)
    want_cache = phase == "prefill"
    x = embed_tokens(params, cfg, batch["tokens"])
    if cfg.frontend == "vision" and "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(x.dtype)
        x = jnp.concatenate([ve, x], axis=1)
    x = constrain(x, "act_btd")
    B, T = x.shape[0], x.shape[1]
    if phase == "decode":
        positions = None
    else:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    embed0 = x if cfg.hybrid.enabled else None
    aux_total = jnp.float32(0.0)
    new_caches: List[Any] = []

    for r, run in enumerate(plan):
        rp = params["runs"][r]
        if run.kind == "shared_attn":
            c = cache[r] if cache is not None else None
            x, nc = _shared_attn_apply(cfg, params["shared_attn"], x, embed0,
                                       positions, c, pos, phase)
            new_caches.append(nc if (want_cache or phase == "decode") else None)
            continue

        if phase == "decode":
            c = cache[r]
            if run.kind == "attn" and block_tables is not None:
                bt = block_tables[r]

                def body(carry, xs, run=run, bt=bt):
                    xx, _ = carry
                    lp, lc = xs
                    xx, nc = _attn_layer_decode_paged(cfg, run, lp, xx, lc,
                                                      bt, pos)
                    return (xx, None), nc
            elif run.kind == "attn":
                def body(carry, xs, run=run):
                    xx, _ = carry
                    lp, lc = xs
                    xx, nc = _attn_layer_decode(cfg, run, lp, xx, lc, pos)
                    return (xx, None), nc
            else:
                def body(carry, xs, run=run):
                    xx, _ = carry
                    lp, lc = xs
                    xx, nc = _ssm_layer_decode(cfg, run, lp, xx, lc, pos)
                    return (xx, None), nc
            (x, _), ys = jax.lax.scan(body, (x, None), (rp, c))
            new_caches.append(ys)
        else:
            if run.kind == "attn":
                def body(carry, xs, run=run):
                    xx, _ = carry
                    (lp,) = xs
                    xx, kv, aux = _attn_layer_prefill(cfg, run, lp, xx,
                                                      positions, want_cache)
                    return (xx, None), (kv, aux)
            else:
                def body(carry, xs, run=run):
                    xx, _ = carry
                    (lp,) = xs
                    xx, kv = _ssm_layer_prefill(cfg, run, lp, xx, want_cache)
                    return (xx, None), (kv, jnp.float32(0.0))
            b = body
            if remat:
                b = jax.checkpoint(
                    body,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            (x, _), (kvs, auxs) = jax.lax.scan(b, (x, None), (rp,))
            aux_total = aux_total + jnp.sum(auxs)
            if want_cache:
                new_caches.append(_pack_prefill_cache(cfg, run, kvs, T))
            else:
                new_caches.append(None)
        x = constrain(x, "act_btd")

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        # caller applies the LM head itself (chunked cross-entropy path)
        out_cache = new_caches if (want_cache or phase == "decode") else None
        return x, out_cache, aux_total
    if phase == "prefill":
        # only the last position's logits are needed to start decoding
        logits = lm_logits(params, cfg, x[:, -1:, :])
    else:
        logits = lm_logits(params, cfg, x)
    out_cache = new_caches if (want_cache or phase == "decode") else None
    return logits, out_cache, aux_total


def _pack_prefill_cache(cfg: ModelConfig, run: RunSpec, kvs, T: int):
    """Convert scan-stacked prefill K/V into the decode cache layout.

    Windowed runs keep the last ``min(W, T)`` entries, rolled so slot ``s``
    holds the position with ``pos % W == s`` — consistent with the decode
    ring buffer for any T.
    """
    S = cache_len(run, T)

    def trim(x, axis=2):
        x = jax.lax.slice_in_dim(x, x.shape[axis] - S, x.shape[axis], axis=axis)
        if run.window > 0 and T > S and T % S != 0:
            x = jnp.roll(x, shift=T % S, axis=axis)
        return x

    if run.kind == "attn" and cfg.mla.enabled:
        (latent,) = kvs
        return {"latent": trim(latent)}
    if run.kind == "attn":
        k, v = kvs
        return {"k": trim(k), "v": trim(v)}
    conv_state, state = kvs
    return {"conv": conv_state, "state": state}


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """True iff every run can prefill incrementally against the decode arena.

    Attention runs (GQA, sliding-window, MLA) replay their history from the
    arena KV, so a prompt can be fed in chunks.  SSM and shared-attention
    runs carry a recurrent / rolled state across positions that
    ``ssm_prefill`` cannot currently resume from — those plans fall back to
    whole-prompt prefill (still arena-direct via ``prefill_into_arena``).
    """
    return all(run.kind == "attn" for run in build_plan(cfg))


def supports_paged(cfg: ModelConfig) -> bool:
    """True iff every run can live in the paged block-pool KV arena.

    Attention runs (GQA, sliding-window, MLA) index their cache by
    position, so positions can be relocated onto pages through a block
    table.  SSM and shared-attention runs carry recurrent / whole-sequence
    state that has no per-position granularity — those plans keep the
    dense arena."""
    return all(run.kind == "attn" for run in build_plan(cfg))


def forward_chunk(params: Params, cfg: ModelConfig, tokens, offsets,
                  lengths, slots, cache: List[Any],
                  block_tables: Optional[List[Any]] = None,
                  return_all_logits: bool = False):
    """Packed chunked prefill, writing K/V directly into the decode arena.

    tokens: [N, C] (or [N, K, C] multi-codebook) — N chunk rows padded to C
    tokens; row ``n`` holds prompt tokens [offsets[n], offsets[n]+lengths[n])
    of the request in arena slot ``slots[n]``.  ``cache`` is the full decode
    arena from ``init_cache(cfg, B, S)``; rows other than the addressed
    slots are untouched (padded rows scatter out of bounds and drop).
    With ``block_tables`` the arena is the PAGED pool (serving.kv_pool)
    and writes route through the per-run block tables instead.

    Returns (last_logits [N, 1, ...], new_cache): the logits of each row's
    last valid position — only meaningful for rows whose chunk completes
    the prompt.  With ``return_all_logits`` the logits of EVERY window
    position come back instead ([N, C, ...]) — the speculative-decode
    verify program scores each draft token against the position that
    predicts it (see serving/speculative.py); positions past a row's
    ``lengths`` are garbage the caller must mask.  Requires
    ``supports_chunked_prefill(cfg)``.
    """
    plan = build_plan(cfg)
    x = embed_tokens(params, cfg, tokens)
    x = constrain(x, "act_btd")
    N = x.shape[0]
    lengths = jnp.asarray(lengths, jnp.int32)
    new_caches: List[Any] = []
    for r, run in enumerate(plan):
        if run.kind != "attn":
            raise NotImplementedError(
                f"chunked prefill over {run.kind!r} runs; gate on "
                "supports_chunked_prefill() and use prefill_into_arena()")
        rp = params["runs"][r]
        bt = block_tables[r] if block_tables is not None else None

        def body(carry, xs, run=run, bt=bt):
            xx, _ = carry
            lp, lc = xs
            if bt is None:
                xx, nc = _attn_layer_chunk(cfg, run, lp, xx, offsets,
                                           lengths, slots, lc)
            else:
                xx, nc = _attn_layer_chunk_paged(cfg, run, lp, xx, offsets,
                                                 lengths, slots, lc, bt)
            return (xx, None), nc

        (x, _), ys = jax.lax.scan(body, (x, None), (rp, cache[r]))
        new_caches.append(ys)
        x = constrain(x, "act_btd")
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if return_all_logits:
        return lm_logits(params, cfg, x), new_caches             # [N, C, ...]
    last = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
    h_last = x[jnp.arange(N), last][:, None, :]                  # [N, 1, d]
    return lm_logits(params, cfg, h_last), new_caches


def forward_chunk_packed(params: Params, cfg: ModelConfig, tokens, starts,
                         offsets, lengths, slots, cache: List[Any],
                         block_tables: Optional[List[Any]] = None,
                         pack_align: int = 8):
    """PACKED chunked prefill: one flat token stream instead of [N, C] rows.

    tokens: [T] — N segments (one per request chunk) laid out back to back
    at ``starts`` [N] (non-decreasing, aligned to ``pack_align``; pad
    segments carry start == T).  Segment ``n`` holds prompt tokens
    [offsets[n], offsets[n]+lengths[n]) of the request in arena slot
    ``slots[n]``.  Only inter-segment alignment slack plus the final
    pow2-bucket tail is padding — mixed-length chunk batches no longer pay
    max-length padding on every row (the packing-prefetch scheduler shape;
    HALO keeps CiM prefill utilization high the same way).

    Returns (last_logits [N, 1, V], new_cache): logits of each segment's
    last valid position — meaningful only for segments completing their
    prompt, like ``forward_chunk``.  Requires supports_chunked_prefill()
    and a single codebook (packed streams are [T], not [K, T]).
    """
    if cfg.n_codebooks > 1:
        raise NotImplementedError("packed prefill is single-codebook only")
    plan = build_plan(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[-1]
    x = embed_tokens(params, cfg, tokens[None])                  # [1, T, d]
    x = constrain(x, "act_btd")
    seg = attn_mod.make_packed_segs(starts, offsets, lengths, slots, T)
    new_caches: List[Any] = []
    for r, run in enumerate(plan):
        if run.kind != "attn":
            raise NotImplementedError(
                f"packed prefill over {run.kind!r} runs; gate on "
                "supports_chunked_prefill()")
        rp = params["runs"][r]
        bt = block_tables[r] if block_tables is not None else None

        def body(carry, xs, run=run, bt=bt):
            xx, _ = carry
            lp, lc = xs
            if bt is None:
                xx, nc = _attn_layer_chunk_packed(cfg, run, lp, xx, seg,
                                                  lc, pack_align)
            else:
                xx, nc = _attn_layer_chunk_packed_paged(cfg, run, lp, xx,
                                                        seg, lc, bt,
                                                        pack_align)
            return (xx, None), nc

        (x, _), ys = jax.lax.scan(body, (x, None), (rp, cache[r]))
        new_caches.append(ys)
        x = constrain(x, "act_btd")
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    last = jnp.clip(seg.starts + seg.lengths - 1, 0, T - 1)      # [N]
    h_last = x[0, last][:, None, :]                              # [N, 1, d]
    return lm_logits(params, cfg, h_last), new_caches


def splice_arena(cfg: ModelConfig, cache: List[Any], piece: List[Any],
                 slot) -> List[Any]:
    """Write a single-request prefill cache (batch=1 ``piece``) into arena
    slot ``slot`` — the CiM -> CiD handoff for run families that cannot
    chunk (SSM recurrent state, shared attention).  Pure jnp (traceable
    ``slot``), so the whole handoff stays inside one jitted program.

    Attention pieces arrive from ``_pack_prefill_cache`` already trimmed /
    rolled to ring order; the last ``min(P, R)`` entries land in arena
    positions [0, pl) exactly as the decode ring expects.
    """
    plan = build_plan(cfg)
    slot = jnp.asarray(slot, jnp.int32)
    out: List[Any] = []
    for run, arena, p in zip(plan, cache, piece):
        if run.kind == "ssm":
            upd = {}
            for key in arena:
                starts = (0, slot) + (0,) * (arena[key].ndim - 2)
                upd[key] = jax.lax.dynamic_update_slice(
                    arena[key], p[key].astype(arena[key].dtype), starts)
            out.append(upd)
            continue
        d: Dict[str, Any] = {}
        for key in arena:
            a, pc = arena[key], p[key]
            # attn caches: [L, B, S, ...] (batch=1, seq=2);
            # shared_attn:  [B, S, ...]   (batch=0, seq=1)
            b_ax, ax = (1, 2) if run.kind == "attn" else (0, 1)
            pl = min(pc.shape[ax], a.shape[ax])
            pc = jax.lax.slice_in_dim(pc, pc.shape[ax] - pl, pc.shape[ax],
                                      axis=ax)
            starts = tuple(slot if i == b_ax else 0 for i in range(a.ndim))
            d[key] = jax.lax.dynamic_update_slice(
                a, pc.astype(a.dtype), starts)
        out.append(d)
    return out


def prefill_into_arena(params: Params, cfg: ModelConfig, batch, slot,
                       cache: List[Any]):
    """Whole-prompt prefill + arena splice as ONE jitted program (no
    host-side cache surgery).  Returns (last_logits [1, 1, ...], new_cache)."""
    logits, piece, _ = forward(params, cfg, batch, phase="prefill")
    return logits, splice_arena(cfg, cache, piece, slot)


def pad_cache(cfg: ModelConfig, cache: List[Any], prompt_len: int,
              max_len: int) -> List[Any]:
    """Grow a prefill cache (length == prompt_len) to ``max_len`` slots so
    decoding can append.  Windowed runs stay at ring size min(W, max_len);
    SSM states are length-independent."""
    plan = build_plan(cfg)
    out = []
    for run, c in zip(plan, cache):
        if run.kind == "ssm" or c is None:
            out.append(c)
            continue
        target = cache_len(run, max_len)

        def grow(x, axis=2 if run.kind == "attn" else 1):
            axis_ = 2 if run.kind == "attn" else 1
            cur = x.shape[axis_]
            if cur >= target:
                return x
            pad = [(0, 0)] * x.ndim
            pad[axis_] = (0, target - cur)
            return jnp.pad(x, pad)

        out.append(jax.tree.map(grow, c))
    return out


# convenience wrappers ------------------------------------------------------

def forward_train(params, cfg, batch, remat: bool = True):
    logits, _, aux = forward(params, cfg, batch, phase="train", remat=remat)
    return logits, aux


def prefill(params, cfg, batch):
    logits, cache, _ = forward(params, cfg, batch, phase="prefill")
    return logits, cache


def decode_step(params, cfg, batch, cache, pos):
    logits, cache, _ = forward(params, cfg, batch, phase="decode",
                               cache=cache, pos=pos)
    return logits, cache
