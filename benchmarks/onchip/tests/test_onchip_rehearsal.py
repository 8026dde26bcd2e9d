"""The run command end to end on the CPU at the program's reduced() size
(``--cpu-rehearsal``): generator -> AsyncEngine -> metrics ->
correctness, for the closed-loop and the open-loop mix; and the same run
with the served path broken underneath, where ``correct`` must come out
false."""

import subprocess
import sys
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

import _path  # noqa: F401
import run
import spec
from repro.serving.engine import ServingEngine


def rehearse(workload, seed, trace=0, seconds=2.0):
    return run.run(SimpleNamespace(workload=workload, seed=seed,
                                   seconds=seconds, trace=trace,
                                   cpu_rehearsal=True))


def correct(out):
    return run.verdict(out.check, out.check["limit"])


def test_closed_loop_rehearsal_traced():
    out = rehearse("qwen3-8b-l12.halo-longctx", 2**31 + 5, trace=1)
    assert correct(out), out.check
    assert out.e2e["attempted"] >= 1 and out.e2e["failed"] == 0
    assert out.traces == 0 and out.in_window == 0
    assert 1 <= out.metrics["decode_batch_mean.halo"]["value"] <= 4
    assert out.metrics["ttft_p50_ms.halo"]["value"] > 0
    # a CPU run has no device trace: no device metric is reported
    assert "device_idle_frac" not in out.metrics
    assert out.trace is None


def test_open_loop_rehearsal():
    out = rehearse("qwen3-1.7b.chat-shared", 3)
    assert correct(out), out.check
    assert out.e2e["attempted"] >= 5 and out.e2e["failed"] == 0
    assert set(out.metrics) == {"output_tok_s", "itl_p95_ms", "ttft_p50_ms",
                                "setup_s"}
    assert out.metrics["output_tok_s"]["value"] > 0


def _altered_token(monkeypatch):
    sample = ServingEngine._sample

    def altered(self, logits, *args):
        toks = sample(self, logits, *args)
        return toks.at[0].set((toks[0] + 1) % logits.shape[-1])
    monkeypatch.setattr(ServingEngine, "_sample", altered)


def _state_unchanged(monkeypatch):
    decode = ServingEngine._decode_paged_impl

    def unchanged(self, params, tokens, cache, *args):
        toks, _ = decode(self, params, tokens, cache, *args)
        return toks, cache
    monkeypatch.setattr(ServingEngine, "_decode_paged_impl", unchanged)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
def test_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = rehearse("qwen3-8b-l12.halo-longctx", 9)
    assert out.check["requests"] > 0
    assert not correct(out), out.check


def test_without_a_tpu_the_run_fails():
    proc = subprocess.run(
        [sys.executable, run.__file__, "--workload",
         "qwen3-1.7b.chat-shared", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_control_goes_through_the_verdict(monkeypatch):
    """``control.py``'s readings are judged by ``run.verdict`` at the
    cell's limit: the sound run is correct and a control whose tokens
    lie further below the reference's best is not. At the rehearsal's
    size the int8 control flips too few tokens to read above the
    rehearsal limit, so the reference's quantizer is coarsened to 4 bits
    here; on the chip the int8 control itself reads above the limits."""
    import control
    ref = spec.load_cell("qwen3-1.7b.chat-shared").reference()

    def int4(w):
        w = w.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-30) / 7
        return jnp.clip(jnp.round(w / s), -7, 7) * s
    monkeypatch.setattr(ref, "_int8", int4)
    monkeypatch.setattr(spec.Cell, "reference", lambda self: ref)
    row = control.readings("qwen3-1.7b.chat-shared", 9, 2.0, False, True)
    assert row["sound"]["correct"] and row["served_configuration"]["correct"]
    assert row["control_ref_int8"]["mean_gap_logits"] > row["limit"]
    assert not row["control_ref_int8"]["correct"]
