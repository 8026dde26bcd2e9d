"""Qwen3 configuration and weights for the program under test.

The benchmark, not the program, makes the weights: one jitted call draws
them on the device from the seed, in bf16, straight into the program's
parameter layout. ``reference_weights`` gives the same arrays to the
plain reference in the published layout: the program rotates interleaved
pairs ``(2i, 2i + 1)`` where the published model rotates ``(i, i + D/2)``,
so the reference's q and k columns (and their norm scales) are the
program's, permuted within each head. Attention scores are invariant
under that permutation, so both compute the same model.
"""

from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttnConfig, ModelConfig
from repro.models.layers import head_rmsnorm
from repro.models.transformer import init_params, padded_vocab

# ModelConfig field <- published config key
FIELDS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
          "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads", "d_head": "head_dim",
          "d_ff": "intermediate_size", "vocab_size": "vocab_size"}


def program_config(m: dict) -> ModelConfig:
    if m.get("use_sliding_window") or m.get("attention_bias") \
            or m.get("hidden_act") != "silu" or m.get("rope_scaling"):
        raise ValueError(f"{m['name']}: only plain Qwen3 dense decoders")
    return ModelConfig(
        name=m["name"], family="dense", dtype=m["dtype"], act="silu",
        norm_eps=m["rms_norm_eps"],
        tie_embeddings=bool(m["tie_word_embeddings"]),
        attn=AttnConfig(qk_norm=True, rope_theta=float(m["rope_theta"])),
        **{f: int(m[k]) for f, k in FIELDS.items()})


def as_served(m: dict) -> dict:
    """The configuration as the program runs it: its per-head q/k RMSNorm
    takes a fixed eps (``head_rmsnorm``'s default), not ``rms_norm_eps``."""
    eps = inspect.signature(head_rmsnorm).parameters["eps"].default
    return dict(m, qk_norm_eps=float(eps))


def rehearsal_sizes(m: dict) -> dict:
    """The configuration at the program's ``reduced()`` size: the CPU
    rehearsal's model."""
    r = program_config(m).reduced()
    return dict(m, **{k: getattr(r, f) for f, k in FIELDS.items()})


def seed_words(seed: int):
    """A seed of any size as three int32 words (a ``--seed`` may need
    more than 32 bits)."""
    return tuple(np.int32((seed >> s) & 0x7FFFFFFF) for s in (0, 31, 62))


def make_params(m: dict, seed: int):
    """The program's parameter tree, drawn on the device from ``seed`` in
    one jitted call."""
    cfg = program_config(m)
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    dtype = jnp.dtype(m["dtype"])
    V, Vp = int(m["vocab_size"]), padded_vocab(cfg)

    def draw(w0, w1, w2):
        key = jax.random.key(w0, impl="rbg")
        key = jax.random.fold_in(jax.random.fold_in(key, w1), w2)
        leaves, tree = jax.tree_util.tree_flatten_with_path(want)
        out = []
        for i, (path, spec) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            if "scale" in name or "_norm" in name:
                x = jnp.ones(spec.shape, dtype)
            elif "embed" in name or "lm_head" in name:
                x = 0.02 * jax.random.normal(k, spec.shape, jnp.float32)
                axis = 0 if "embed" in name else 1
                keep = jnp.arange(Vp) < V          # padded rows are zero
                x = jnp.where(keep[:, None] if axis == 0 else keep[None, :],
                              x, 0.0)
            else:
                fan_in = spec.shape[-2]
                x = jax.random.truncated_normal(
                    k, -3.0, 3.0, spec.shape, jnp.float32) / np.sqrt(fan_in)
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(draw)(*seed_words(seed))


def _perm(D: int) -> np.ndarray:
    return np.concatenate([np.arange(0, D, 2), np.arange(1, D, 2)])


def reference_weights(params, m: dict) -> dict:
    """The same weights in the published layout, for the reference."""
    H, Hkv, D = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    p = _perm(D)
    (run,) = params["runs"]
    a, f = run["attn"], run["ffn"]

    def heads(w, n):      # [L, d, n*D] -> columns permuted within each head
        L, d, _ = w.shape
        return w.reshape(L, d, n, D)[..., p].reshape(L, d, n * D)

    return {
        "embed": params["embed"],
        "head": None if m["tie_word_embeddings"] else params["lm_head"],
        "final_norm": params["final_norm"]["scale"],
        "layers": {
            "ln1": run["ln1"]["scale"], "ln2": run["ln2"]["scale"],
            "wq": heads(a["wq"], H), "wk": heads(a["wk"], Hkv),
            "wv": a["wv"], "wo": a["wo"],
            "q_norm": a["q_norm"][:, p], "k_norm": a["k_norm"][:, p],
            "w_gate": f["wi_gate"], "w_up": f["wi_up"], "w_down": f["wo"]},
    }
