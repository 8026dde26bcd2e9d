"""Disaggregated serving & tiered KV (PR 8).

The DisaggregatedExecutor pins prefill/decode programs to separate device
groups and accounts KV page ownership crossing the prefill -> decode
handoff (HALO's 2.5D interposer link); the host spill tier lets
preemption SWAP pages out and resume with zero recomputation.  Every
placement/tier variant must keep greedy token streams bit-identical —
placement and spill are performance knobs, never semantics.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models.transformer import init_params
from repro.serving import (
    ColocatedExecutor,
    DisaggregatedExecutor,
    SamplingParams,
    ServeConfig,
    ServingEngine,
    SpecConfig,
    make_executor,
)
from repro.serving.engine import RequestState
from repro.serving.scheduler import PhaseAwareConfig


def tiny_cfg(name="qwen3-1.7b"):
    return dataclasses.replace(get_config(name).reduced(), dtype="float32")


_PARAMS = {}


def cached_params(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = init_params(jax.random.PRNGKey(0), cfg)
    return _PARAMS[cfg.name]


def make_engine(cfg, max_batch=2, *, executor="colocated", paged=True,
                page_size=4, n_pages=32, host_spill_pages=0,
                prefix_cache=False, spec=None, kv_dtype="f32",
                max_len=96, prefill_chunk=8, max_prefill_tokens=16):
    sc = ServeConfig(max_batch=max_batch, max_len=max_len,
                     phase=PhaseAwareConfig(
                         max_decode_batch=max_batch,
                         prefill_chunk=prefill_chunk,
                         max_prefill_tokens=max_prefill_tokens),
                     paged=paged, page_size=page_size, n_pages=n_pages,
                     prefix_cache=prefix_cache, speculative=spec,
                     kv_dtype=kv_dtype, executor=executor,
                     host_spill_pages=host_spill_pages)
    return ServingEngine(cfg, cached_params(cfg), sc)


def prompts(cfg, n, L, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (L,), dtype=np.int32)
            for _ in range(n)]


def run_greedy(eng, ps, max_new=4):
    reqs = [eng.submit(p.copy(),
                       sampling=SamplingParams(max_new_tokens=max_new))
            for p in ps]
    eng.run_until_drained()
    return [r.generated for r in reqs]


# ---------------------------------------------------------------------------
# executor layer: construction, placement, and the migration accounting
# ---------------------------------------------------------------------------


def test_make_executor_validates_name():
    assert isinstance(make_executor("colocated", {}), ColocatedExecutor)
    assert isinstance(make_executor("disaggregated", {}),
                      DisaggregatedExecutor)
    with pytest.raises(ValueError, match="executor="):
        make_executor("remote", {})
    with pytest.raises(ValueError, match="executor="):
        ServeConfig(max_batch=1, max_len=8,
                    phase=PhaseAwareConfig(max_decode_batch=1),
                    executor="remote")


def test_phase_classification_and_placement():
    ex = DisaggregatedExecutor({})
    for kind in ("chunk", "whole", "packed", "packed_paged",
                 "chunk_paged", "verify"):
        assert ex.phase_of(kind) == "prefill"
    for kind in ("decode", "decode_paged"):
        assert ex.phase_of(kind) == "decode"
    # single-device host: both groups resolve to the same device, so
    # pinning is a no-op and streams stay bit-identical by construction
    assert ex.prefill_devices and ex.decode_devices
    assert ex.device_for("decode_paged") is not None
    assert ColocatedExecutor({}).device_for("decode_paged") is None
    assert not ColocatedExecutor({}).migrates_kv and ex.migrates_kv


def test_handoff_batches_per_tick():
    ex = DisaggregatedExecutor({})
    ex.begin_tick()
    ex.record_handoff(2, 100)
    ex.record_handoff(3, 200)             # same tick: one link transaction
    assert (ex.migrated_pages, ex.migrated_bytes) == (5, 300)
    assert ex.migration_batches == 1
    ex.begin_tick()
    ex.record_handoff(0, 0)               # empty handoff: not a batch
    assert ex.migration_batches == 1
    ex.record_handoff(1, 50)
    assert ex.migration_batches == 2


# ---------------------------------------------------------------------------
# bit-identity: colocated vs disaggregated, across attention families and
# the paper's model pair
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-1.7b",        # GQA
                                  "gemma3-1b",         # sliding-window ring
                                  "deepseek-v2-236b",  # MLA latents
                                  "llama2-7b",         # paper model (CiM)
                                  "qwen3-8b"])         # paper model (CiD)
def test_colocated_vs_disaggregated_bit_identity(arch):
    cfg = tiny_cfg(arch)
    ps = prompts(cfg, 3, 12, seed=5)
    ref = run_greedy(make_engine(cfg), ps)
    eng = make_engine(cfg, executor="disaggregated")
    assert run_greedy(eng, ps) == ref
    # every request's fresh KV crossed the link exactly once
    c = eng.counts()
    assert c["migrated_bytes"] > 0 and c["migrated_pages"] > 0
    assert eng.executor.migration_batches >= 1


@pytest.mark.parametrize("variant", ["prefix", "speculative", "int8kv",
                                     "dense"])
def test_disaggregated_identity_across_serving_modes(variant):
    cfg = tiny_cfg()
    kw = {}
    if variant == "prefix":
        kw = dict(prefix_cache=True)
    elif variant == "speculative":
        kw = dict(spec=SpecConfig(k=2))
    elif variant == "int8kv":
        kw = dict(kv_dtype="int8")
    elif variant == "dense":
        kw = dict(paged=False, prefill_chunk=16, max_prefill_tokens=32)
    ps = prompts(cfg, 3, 12, seed=9)
    ref = run_greedy(make_engine(cfg, **kw), ps)
    eng = make_engine(cfg, executor="disaggregated", **kw)
    assert run_greedy(eng, ps) == ref
    # dense handoffs move bytes but no pages (the arena has none)
    c = eng.counts()
    assert c["migrated_bytes"] > 0
    assert (c["migrated_pages"] == 0) == (variant == "dense")


def test_prefix_hits_shrink_migrated_bytes():
    """Cached prefixes are already decode-side resident: only the tail a
    request actually prefilled crosses the link, so a shared-prompt wave
    migrates fewer bytes than the cold wave that built the cache."""
    cfg = tiny_cfg()
    eng = make_engine(cfg, executor="disaggregated", prefix_cache=True,
                      n_pages=64)
    head = prompts(cfg, 1, 16, seed=1)[0]
    rng = np.random.default_rng(2)
    wave = [np.concatenate([head, rng.integers(0, cfg.vocab_size, (4,),
                                               dtype=np.int32)])
            for _ in range(2)]
    run_greedy(eng, wave[:1])
    cold = eng.counts()["migrated_bytes"]
    run_greedy(eng, wave[1:])
    warm = eng.counts()["migrated_bytes"] - cold
    assert 0 < warm < cold


# ---------------------------------------------------------------------------
# tiered KV: swap-resume vs recompute-resume
# ---------------------------------------------------------------------------


def _forced_preempt_drain(eng, ps, max_new=6):
    """Drive the engine, preempting a decoding request once mid-stream
    (deterministic — no reliance on pool-pressure timing)."""
    reqs = [eng.submit(p.copy(),
                       sampling=SamplingParams(max_new_tokens=max_new))
            for p in ps]
    fired = False
    for _ in range(500):
        if not (eng.queue or any(r is not None for r in eng.slot_req)):
            break
        eng.step()
        if not fired:
            victim = next(
                (r for r in eng.slot_req if r is not None
                 and r.state == RequestState.DECODING
                 and len(r.generated) >= 2), None)
            if victim is not None:
                eng._preempt(victim)
                fired = True
    assert fired, "no preemption fired — the scenario never ran"
    return [r.generated for r in reqs]


def test_swap_resume_is_bit_identical_with_zero_reprefill():
    cfg = tiny_cfg()
    ps = prompts(cfg, 3, 16, seed=11)
    total_prompt = sum(int(p.shape[-1]) for p in ps)
    ref = run_greedy(make_engine(cfg, n_pages=64), ps, max_new=6)

    swap = make_engine(cfg, n_pages=64, host_spill_pages=32)
    assert _forced_preempt_drain(swap, ps) == ref
    c = swap.counts()
    assert swap.swap_outs >= 1 and c["swap_resumes"] >= 1
    assert c["recompute_preemptions"] == 0
    assert c["swap_out_bytes"] > 0 and c["swap_in_bytes"] > 0
    # THE tiered-KV claim: the swapped request resumed from its host
    # pages — not one prompt token was prefilled a second time
    assert swap.prefill_tokens_executed == total_prompt
    # handle-free steady state: every host page returned to the tier
    assert c["host_resident_pages"] == 0
    swap.host_tier.check_invariants()

    rec = make_engine(cfg, n_pages=64, host_spill_pages=0)
    assert _forced_preempt_drain(rec, ps) == ref
    rc = rec.counts()
    assert rc["recompute_preemptions"] >= 1 and rc["swap_resumes"] == 0
    # recompute-on-resume re-prefills the victim's whole effective stream
    assert rec.prefill_tokens_executed > total_prompt


def test_swap_falls_back_to_recompute_when_tier_full():
    cfg = tiny_cfg()
    ps = prompts(cfg, 3, 16, seed=11)
    ref = run_greedy(make_engine(cfg, n_pages=64), ps, max_new=6)
    # a 1-page tier cannot hold any victim (>= 16 tokens = 4+ pages)
    eng = make_engine(cfg, n_pages=64, host_spill_pages=1)
    assert _forced_preempt_drain(eng, ps) == ref
    c = eng.counts()
    assert c["recompute_preemptions"] >= 1 and c["swap_resumes"] == 0
    assert eng.host_tier.used_pages() == 0


def test_abort_of_swapped_request_frees_host_pages():
    cfg = tiny_cfg()
    eng = make_engine(cfg, n_pages=64, host_spill_pages=32)
    reqs = [eng.submit(p.copy(),
                       sampling=SamplingParams(max_new_tokens=6))
            for p in prompts(cfg, 2, 16, seed=3)]
    victim = None
    for _ in range(200):
        eng.step()
        victim = next((r for r in eng.slot_req if r is not None
                       and r.state == RequestState.DECODING), None)
        if victim is not None:
            break
    eng._preempt(victim)
    assert victim.swap is not None and eng.host_tier.used_pages() > 0
    eng.abort(victim.req_id)
    assert victim.swap is None and eng.host_tier.used_pages() == 0
    eng.run_until_drained()
    assert all(r.state == RequestState.DONE for r in reqs)
    eng.host_tier.check_invariants()


# ---------------------------------------------------------------------------
# prefix cache: demote -> promote round trip through the host tier
# ---------------------------------------------------------------------------


def test_prefix_demote_promote_round_trip():
    cfg = tiny_cfg()
    eng = make_engine(cfg, prefix_cache=True, host_spill_pages=32,
                      n_pages=64)
    p = prompts(cfg, 1, 16, seed=21)[0]
    first = run_greedy(eng, [p], max_new=4)[0]
    assert eng.prefix.stats()["inserted_blocks"] > 0

    # evict everything: with the host tier attached, eviction DEMOTES
    # blocks (device pages freed, KV parked on host) instead of dropping
    freed = eng.prefix.evict(eng.pool, eng.pool.n_pages)
    s = eng.prefix.stats()
    assert freed > 0 and s["demoted_blocks"] > 0 and s["demoted_nodes"] > 0
    assert eng.host_tier.used_pages() > 0
    assert eng.pool.free_pages() == eng.pool.n_pages

    # re-hit: match promotes the demoted blocks back to fresh device
    # pages — the resubmit starts past the cached prefix and the stream
    # is identical to the cold run
    req = eng.submit(p.copy(), sampling=SamplingParams(max_new_tokens=4))
    eng.run_until_drained()
    assert req.cached_tokens > 0
    assert req.generated == first
    s = eng.prefix.stats()
    assert s["promoted_blocks"] > 0
    assert s["demoted_nodes"] < s["demoted_blocks"] or s["demoted_nodes"] == 0

    # promoted pages are externally owned: flush returns every page
    eng.prefix.flush(eng.pool)
    assert eng.pool.free_pages() == eng.pool.n_pages
    assert eng.host_tier.used_pages() == 0
    for pp in eng.pool.pools:
        pp.check_invariants()
    eng.host_tier.check_invariants()


def test_demoted_prefix_hit_identity_vs_cold_cache():
    """A stream served through promote must equal the same stream served
    by a cacheless engine — promotion restores the EXACT bytes."""
    cfg = tiny_cfg()
    ps = prompts(cfg, 2, 16, seed=33)
    ref = run_greedy(make_engine(cfg, n_pages=64), ps, max_new=5)
    eng = make_engine(cfg, prefix_cache=True, host_spill_pages=32,
                      n_pages=64)
    out0 = run_greedy(eng, ps[:1], max_new=5)
    eng.prefix.evict(eng.pool, eng.pool.n_pages)      # demote to host
    out1 = run_greedy(eng, ps[1:], max_new=5)
    # resubmit the first prompt: served THROUGH the promoted prefix
    out2 = run_greedy(eng, ps[:1], max_new=5)
    assert out0 + out1 == ref
    assert out2 == ref[:1]
    assert eng.prefix.stats()["promoted_blocks"] > 0


# ---------------------------------------------------------------------------
# hypothesis: interleavings conserve refcounts across BOTH tiers
# ---------------------------------------------------------------------------

try:
    import hypothesis  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:                       # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    class TieredHostOnlyEngine(ServingEngine):
        """Device programs and page I/O stubbed (tokens are all 0, page
        uploads are no-ops) so hypothesis can drive it fast; admission,
        page accounting, prefix attach/publish/demote/promote, swap
        in/out, preemption, and abort all run for real."""

        _CACHE_ARG = {"chunk": 5, "chunk_paged": 5, "whole": 3,
                      "packed": 6, "packed_paged": 6,
                      "decode": 2, "decode_paged": 2, "verify": 5}

        def _program(self, group, kind):
            cache_arg = self._CACHE_ARG[kind]

            def run(*args):
                import jax.numpy as jnp
                cache = args[cache_arg]
                if kind == "verify":
                    draft = np.asarray(args[7])
                    out = np.zeros((draft.shape[0], draft.shape[1] + 2),
                                   np.int32)
                    out[:, -1] = 1
                    return jnp.asarray(out), cache
                if kind in ("packed", "packed_paged"):
                    n = np.asarray(args[2]).shape[0]
                else:
                    n = 1 if kind == "whole" else np.asarray(args[1]).shape[0]
                return jnp.zeros((n,), jnp.int32), cache

            return run

        def _copy_pages(self, copies):
            self.cow_copies += len(copies)

        def _read_page(self, r, page):
            # host-tier leaf shapes without touching device arrays
            return {k: np.zeros((v.shape[0],) + tuple(v.shape[2:]),
                                v.dtype)
                    for k, v in self.host_tier._store[r].items()}

        def _write_page(self, r, page, data):
            pass

    @settings(max_examples=25, deadline=None)
    @given(ops=st.lists(
        st.tuples(st.integers(0, 4),      # 0 submit, 1 step, 2 abort,
                                          # 3 step+abort-youngest,
                                          # 4 step+preempt-youngest (swap)
                  st.integers(0, 7),      # prompt selector / abort target
                  st.integers(1, 30)),    # prompt length
        max_size=30))
    def test_tiered_interleavings_conserve_refcounts(ops):
        """ANY interleaving of submit / step / abort / preempt on a small
        paged pool with the prefix cache, speculation, AND the host tier
        on keeps refcount conservation in every device run pool and in
        the host tier, and ends with both tiers completely free."""
        cfg = tiny_cfg()
        eng = TieredHostOnlyEngine(cfg, cached_params(cfg), ServeConfig(
            max_batch=2, max_len=64,
            phase=PhaseAwareConfig(max_decode_batch=2, prefill_chunk=8,
                                   max_prefill_tokens=16),
            paged=True, page_size=4, n_pages=12, prefix_cache=True,
            speculative=SpecConfig(k=2), host_spill_pages=8))
        submitted = []
        for kind, sel, length in ops:
            if kind == 0:
                prompt = np.full((min(length, 30),), sel % 3, np.int32)
                try:
                    submitted.append(eng.submit(
                        prompt, sampling=SamplingParams(max_new_tokens=6)))
                except ValueError:
                    pass                  # longer than the pool: rejected
            elif kind == 1:
                eng.step()
            elif kind == 2 and submitted:
                eng.abort(submitted[sel % len(submitted)].req_id)
            elif kind == 3:
                eng.step()
                live = [r for r in eng.slot_req if r is not None]
                if live:
                    eng.abort(max(live, key=lambda r: r.req_id).req_id)
            elif kind == 4:
                eng.step()
                holders = [r for r in eng.slot_req if r is not None
                           and eng.pool.len_of(r.slot) > 0]
                if holders:
                    eng._preempt(max(holders, key=lambda r: r.req_id))
            for p in eng.pool.pools:
                p.check_invariants()
            eng.host_tier.check_invariants()
            # a swapped queue entry's host pages + the cache's demoted
            # blocks account for every used host page
            handle_pages = sum(
                len(pages) for r in eng.queue if r.swap is not None
                for pages in r.swap.pages)
            assert eng.host_tier.used_pages() >= handle_pages
        for _ in range(200):
            if not (eng.queue or any(r is not None for r in eng.slot_req)):
                break
            eng.step()
        eng.prefix.flush(eng.pool)
        for p in eng.pool.pools:
            p.check_invariants()
            assert p.free_pages() == p.n_pages, \
                "device pages leaked across the interleaving"
        eng.host_tier.check_invariants()
        assert eng.host_tier.used_pages() == 0, \
            "host-tier pages leaked across the interleaving"


# ---------------------------------------------------------------------------
# placement across real device groups (four virtual CPU devices)
# ---------------------------------------------------------------------------

_FOUR_DEVICES = r"""
import dataclasses
import jax, numpy as np
from repro.configs.base import get_config
from repro.models.transformer import init_params
from repro.serving import SamplingParams, ServeConfig, ServingEngine
from repro.serving.scheduler import PhaseAwareConfig

assert len(jax.devices()) == 4, jax.devices()
cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(), dtype="float32")
params = init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
ps = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32) for n in (19, 7, 12)]

def run(executor):
    eng = ServingEngine(cfg, params, ServeConfig(
        max_batch=2, max_len=64, phase=PhaseAwareConfig(
            max_decode_batch=2, prefill_chunk=8, max_prefill_tokens=16),
        paged=True, page_size=4, n_pages=32, executor=executor))
    reqs = eng.generate(ps, SamplingParams(max_new_tokens=5))
    return eng, [list(r.generated) for r in reqs]

_, want = run("colocated")
eng, got = run("disaggregated")
ex = eng.executor
assert got == want, (got, want)
pre, dec = ex.prefill_devices[0], ex.decode_devices[0]
assert (pre.id, dec.id) == (0, 2), (pre, dec)
# the weights were copied to the decode device once and reused
held = ex._params_on[dec]
assert held[0] is params
assert all(x.devices() == {dec} for x in jax.tree.leaves(held[1]))
params_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
pool_bytes = eng.pool.total_bytes()
# the pool moves at phase switches, counted; the weights only once
assert ex.moved_bytes >= params_bytes + pool_bytes, ex.moved_bytes
assert ex.max_tick_moved_bytes >= pool_bytes
before = ex.moved_bytes
eng.generate(ps[:1], SamplingParams(max_new_tokens=3))
assert ex._params_on[dec] is held
assert ex.moved_bytes - before < params_bytes + 4 * pool_bytes
assert {d.id for x in jax.tree.leaves(eng.cache) for d in x.devices()} <= {0, 2}
print("OK")
"""


def test_disaggregated_placement_on_four_devices():
    """Four devices, groups (0, 1) and (2, 3): prefill runs on device 0,
    decode on device 2, the streams equal the colocated ones, the weights
    are copied to the decode device once, and every pool move is counted
    in ``moved_bytes``."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    p = subprocess.run([sys.executable, "-c", _FOUR_DEVICES], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0 and "OK" in p.stdout, \
        f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
