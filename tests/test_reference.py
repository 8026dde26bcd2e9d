"""The float32 reference forward (models/reference.py) agrees with the
model's own full-sequence forward on every dense GQA plan, and refuses the
plans it does not cover."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, list_archs
from repro.models.reference import reference_logits
from repro.models.transformer import build_plan, forward_train, init_params


def _covered(name):
    cfg = get_config(name)
    return (not cfg.mla.enabled and cfg.n_codebooks <= 1
            and cfg.frontend != "vision"
            and all(r.kind == "attn" and r.ffn_kind == "dense"
                    for r in build_plan(cfg)))


DENSE = [a for a in list_archs() if _covered(a)]


@pytest.mark.parametrize("arch", DENSE)
def test_reference_matches_forward(arch):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    T = 40
    tokens = jax.random.randint(jax.random.PRNGKey(1), (T,), 0,
                                cfg.vocab_size)
    want, _ = forward_train(params, cfg, {"tokens": tokens[None]},
                            remat=False)
    out = np.arange(0, T, 3)
    got = reference_logits(params, cfg, tokens, out)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0, out]),
                               rtol=2e-4, atol=2e-4)


def test_reference_ignores_trailing_padding():
    """Causal: tokens after the read positions never change them — the
    chip smoke pads every sequence to one length to compile once."""
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(),
                              dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (24,), 0,
                                cfg.vocab_size)
    padded = np.concatenate([np.asarray(tokens), np.zeros(40, np.int32)])
    out = np.arange(24)
    np.testing.assert_allclose(
        np.asarray(reference_logits(params, cfg, tokens, out)),
        np.asarray(reference_logits(params, cfg, padded, out)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "mamba2-2.7b"])
def test_reference_refuses_uncovered_plans(arch):
    cfg = get_config(arch).reduced()
    with pytest.raises(NotImplementedError):
        reference_logits({}, cfg, np.zeros(4, np.int32), [0])
