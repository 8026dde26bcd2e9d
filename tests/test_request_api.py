"""Request-centric serving API: per-request SamplingParams (mixed
greedy/stochastic batches in one program), incremental RequestOutput
streaming, abort at every lifecycle stage, and the ttft/tpot guards."""

import dataclasses
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models.transformer import init_params
from repro.serving import (
    RequestOutput,
    RequestState,
    SamplingParams,
    ServeConfig,
    ServingEngine,
    SpecConfig,
)
from repro.serving.sampling import (
    sample_tokens_rows,
    row_keys,
    verify_draft,
    verify_draft_rows,
)
from repro.serving.scheduler import PhaseAwareConfig


def tiny_cfg(name="qwen3-1.7b"):
    return dataclasses.replace(get_config(name).reduced(), dtype="float32")


_PARAMS = {}


def cached_params(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = init_params(jax.random.PRNGKey(0), cfg)
    return _PARAMS[cfg.name]


def make_engine(cfg, max_batch=3, *, paged=False, prefix_cache=False,
                spec=None, page_size=8, n_pages=48, max_len=96,
                prefill_chunk=16, max_prefill_tokens=32, **sc_kw):
    sc = ServeConfig(max_batch=max_batch, max_len=max_len,
                     phase=PhaseAwareConfig(
                         max_decode_batch=max_batch,
                         prefill_chunk=prefill_chunk,
                         max_prefill_tokens=max_prefill_tokens),
                     paged=paged, page_size=page_size, n_pages=n_pages,
                     prefix_cache=prefix_cache, speculative=spec, **sc_kw)
    return ServingEngine(cfg, cached_params(cfg), sc)


def prompts(cfg, n, L, seed=0, repeat_suffix=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        p = rng.integers(0, cfg.vocab_size, (L,), dtype=np.int32)
        if repeat_suffix > 0:
            # tile a short block so the n-gram drafter has hits
            block = p[:repeat_suffix]
            p = np.tile(block, -(-L // repeat_suffix))[:L]
        out.append(p)
    return out


# ---------------------------------------------------------------------------
# SamplingParams: validation and the greedy/temperature unification
# ---------------------------------------------------------------------------


def test_sampling_params_defaults_and_validation():
    sp = SamplingParams()
    assert sp.greedy and sp.temperature == 0.0 and sp.stop == ()
    assert not SamplingParams(temperature=0.5).greedy
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    with pytest.raises(ValueError):
        SamplingParams(max_new_tokens=-1)
    # stop normalizes to an int tuple (hashable, device-independent)
    assert SamplingParams(stop=[np.int32(3), 7]).stop == (3, 7)


def test_serveconfig_legacy_fields_shim_and_warning():
    cfg = tiny_cfg()
    # defaults: no warning, default sampling is greedy
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = make_engine(cfg)
        assert not any(issubclass(x.category, DeprecationWarning)
                       for x in w)
    assert eng._default_sampling.greedy
    # legacy fields still work but warn, and map onto SamplingParams
    with pytest.warns(DeprecationWarning, match="SamplingParams"):
        eng = make_engine(cfg, greedy=False, temperature=0.7, top_k=5)
    sp = eng._default_sampling
    assert (sp.temperature, sp.top_k) == (0.7, 5) and not sp.greedy
    # legacy greedy=True maps to temperature 0 whatever temperature says
    assert ServeConfig(greedy=True, temperature=0.9).default_sampling() \
        .greedy


# ---------------------------------------------------------------------------
# vectorized sampling: per-row params in one program
# ---------------------------------------------------------------------------


def test_sample_tokens_rows_mixed_greedy_rows_are_argmax():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16)),
                         jnp.float32)
    keys = row_keys(jnp.arange(4, dtype=jnp.int32),
                    jnp.zeros((4,), jnp.int32))
    toks = sample_tokens_rows(
        logits, jnp.asarray([0.0, 1.0, 0.0, 1.0]),
        jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.float32), keys)
    am = np.argmax(np.asarray(logits), axis=-1)
    assert int(toks[0]) == am[0] and int(toks[2]) == am[2]


def test_sample_tokens_rows_per_row_top_k():
    # row 0: top_k=2 over [9, 8, 0, 0]; row 1: unrestricted over the
    # mirrored logits — candidate sets must stay per-row
    logits = jnp.array([[9.0, 8.0, 0.0, 0.0],
                        [0.0, 0.0, 8.0, 9.0]])
    seen0, seen1 = set(), set()
    for i in range(60):
        keys = row_keys(jnp.asarray([i, 1000 + i], jnp.int32),
                        jnp.zeros((2,), jnp.int32))
        a, b = np.asarray(sample_tokens_rows(
            logits, jnp.asarray([0.7, 0.7]), jnp.asarray([2, 0]),
            jnp.zeros((2,), jnp.float32), keys))
        seen0.add(int(a))
        seen1.add(int(b))
    assert seen0 <= {0, 1}
    assert seen1 <= {2, 3}               # peaked logits, any token legal


def test_sample_tokens_rows_reproducible_by_seed():
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(1, 32)),
                         jnp.float32)

    def draw(seed, counter):
        keys = row_keys(jnp.asarray([seed], jnp.int32),
                        jnp.asarray([counter], jnp.int32))
        return int(sample_tokens_rows(
            logits, jnp.asarray([0.9]), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.float32), keys)[0])

    assert draw(7, 3) == draw(7, 3)      # pure function of (seed, counter)
    draws = {draw(7, c) for c in range(20)}
    assert len(draws) > 1                # the counter advances the chain


def test_verify_draft_rows_greedy_rows_match_scalar():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    draft = jnp.asarray(rng.integers(0, 16, (3, 3)), jnp.int32)
    dlen = jnp.asarray([3, 2, 1], jnp.int32)
    t_ref, n_ref = verify_draft(logits, draft, dlen, greedy=True)
    keys = row_keys(jnp.arange(3, dtype=jnp.int32),
                    jnp.zeros((3,), jnp.int32))
    t_mix, n_mix = verify_draft_rows(
        logits, draft, dlen, jnp.asarray([0.0, 0.8, 0.0]),
        jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.float32), keys)
    # greedy rows (0, 2) are bit-identical to the scalar greedy rule
    for r in (0, 2):
        n = int(n_ref[r])
        assert int(n_mix[r]) == n
        assert np.asarray(t_mix[r][:n]).tolist() == \
            np.asarray(t_ref[r][:n]).tolist()
    assert 1 <= int(n_mix[1]) <= int(dlen[1]) + 1


# ---------------------------------------------------------------------------
# step() -> incremental RequestOutputs; stream()/generate() facades
# ---------------------------------------------------------------------------


def test_step_returns_incremental_outputs():
    cfg = tiny_cfg()
    eng = make_engine(cfg)
    rs = [eng.submit(p, max_new_tokens=5) for p in prompts(cfg, 3, 12)]
    streams, finals = {}, {}
    while eng.queue or any(r is not None for r in eng.slot_req):
        for out in eng.step():
            assert isinstance(out, RequestOutput)
            streams.setdefault(out.req_id, []).extend(out.new_token_ids)
            assert out.n_generated == len(streams[out.req_id])
            if out.finished:
                finals[out.req_id] = out.finish_reason
    for r in rs:
        assert streams[r.req_id] == r.generated
        assert finals[r.req_id] == "length" == r.finish_reason
    c = eng.counts()
    assert (c["queued"], c["active"], c["done"]) == (0, 0, 3)
    # a colocated engine with no host tier moves and spills nothing
    assert all(c[k] == 0 for k in (
        "migrated_pages", "migrated_bytes", "swap_out_bytes",
        "swap_in_bytes", "swap_resumes", "host_resident_pages"))


def test_stream_yields_before_drain_and_generate_orders():
    cfg = tiny_cfg()
    eng = make_engine(cfg)
    for p in prompts(cfg, 3, 10):
        eng.submit(p, max_new_tokens=6)
    pre_drain = 0
    for out in eng.stream():
        if not out.finished and eng.counts()["done"] == 0:
            pre_drain += 1
    assert pre_drain > 0                  # tokens observable mid-flight
    assert eng.counts()["done"] == 3

    eng2 = make_engine(cfg)
    rs = eng2.generate(prompts(cfg, 3, 10),
                       SamplingParams(max_new_tokens=6))
    assert [r.req_id for r in rs] == sorted(r.req_id for r in rs)
    assert all(r.state == RequestState.DONE for r in rs)
    with pytest.raises(ValueError):
        eng2.generate(prompts(cfg, 2, 8), [SamplingParams()])


def test_finish_reasons_eos_stop_length():
    cfg = tiny_cfg()
    p = prompts(cfg, 1, 12)[0]
    probe = make_engine(cfg)
    first = probe.generate([p.copy()],
                           SamplingParams(max_new_tokens=1))[0].generated[0]
    eng = make_engine(cfg)
    r_eos = eng.submit(p.copy(), sampling=SamplingParams(
        max_new_tokens=8, eos_id=first))
    r_stop = eng.submit(p.copy(), sampling=SamplingParams(
        max_new_tokens=8, stop=(first,)))
    r_len = eng.submit(p.copy(), sampling=SamplingParams(max_new_tokens=2))
    eng.run_until_drained()
    assert r_eos.finish_reason == "eos" and r_eos.generated == [first]
    assert r_stop.finish_reason == "stop" and r_stop.generated == [first]
    assert r_len.finish_reason == "length" and len(r_len.generated) == 2


def test_max_new_tokens_zero_and_latency_guards():
    cfg = tiny_cfg()
    eng = make_engine(cfg, paged=True)
    r = eng.generate([prompts(cfg, 1, 12)[0]],
                     SamplingParams(max_new_tokens=0))[0]
    assert r.state == RequestState.DONE and r.generated == []
    assert r.finish_reason == "length"
    assert math.isnan(r.ttft) and math.isnan(r.tpot)   # no sentinel garbage
    assert eng.pool.free_pages() == eng.pool.n_pages   # pages all returned
    # a request with exactly one token has a defined ttft and tpot
    eng2 = make_engine(cfg)
    r1 = eng2.generate([prompts(cfg, 1, 12)[0]],
                       SamplingParams(max_new_tokens=1))[0]
    assert r1.ttft > 0 and not math.isnan(r1.tpot)


# ---------------------------------------------------------------------------
# mixed-batch identity: greedy rows bit-identical to an all-greedy run
# across dense / paged / prefix-cache / speculative engines
# ---------------------------------------------------------------------------


MODES = ["dense", "paged", "prefix", "spec"]


def _mode_engine(cfg, mode, max_batch=2):
    # fewer slots than requests: later admissions see published prefix
    # pages (the cache has something to hit) and slots get recycled
    if mode == "dense":
        return make_engine(cfg, max_batch)
    if mode == "paged":
        return make_engine(cfg, max_batch, paged=True)
    if mode == "prefix":
        return make_engine(cfg, max_batch, paged=True, prefix_cache=True)
    return make_engine(cfg, max_batch, paged=True, spec=SpecConfig(k=3))


def _mode_prompts(cfg):
    # a shared 16-token head (prefix-cache hits) and a repeated suffix
    # (n-gram drafter hits) so every mode exercises its machinery
    head = prompts(cfg, 1, 16, seed=11, repeat_suffix=5)[0]
    return [np.concatenate([head, t]) for t in
            prompts(cfg, 4, 8, seed=12)]


@pytest.fixture(scope="module")
def greedy_reference():
    """All-greedy streams from the dense engine — the cross-mode oracle
    (dense==paged, cache on/off, spec on/off identities already hold)."""
    cfg = tiny_cfg()
    eng = make_engine(cfg, 4)
    rs = eng.generate([p.copy() for p in _mode_prompts(cfg)],
                      SamplingParams(max_new_tokens=10))
    return [r.generated for r in rs]


@pytest.mark.parametrize("mode", MODES)
def test_mixed_batch_greedy_rows_bit_identical(mode, greedy_reference):
    cfg = tiny_cfg()
    ps = _mode_prompts(cfg)
    sps = [SamplingParams(max_new_tokens=10) if i % 2 == 0 else
           SamplingParams(temperature=0.8, seed=40 + i, max_new_tokens=10)
           for i in range(len(ps))]
    eng = _mode_engine(cfg, mode)
    rs = eng.generate([p.copy() for p in ps], sps)
    for i, r in enumerate(rs):
        if sps[i].greedy:
            assert r.generated == greedy_reference[i], \
                f"{mode}: mixed batch changed greedy row {i}"
    if mode == "prefix":
        assert eng.prefix_stats()["hit_rate"] > 0
    if mode == "spec":
        assert eng.spec_windows > 0      # verify windows actually ran


def test_stochastic_rows_reproducible_across_modes_and_batches():
    """A seeded stochastic request draws from its own (seed, counter)
    chain: the same stream whatever engine layout or batch it rides in
    (speculative excluded — resampling consumes draws differently)."""
    cfg = tiny_cfg()
    ps = _mode_prompts(cfg)
    sps = [SamplingParams(max_new_tokens=10) if i % 2 == 0 else
           SamplingParams(temperature=0.8, seed=40 + i, max_new_tokens=10)
           for i in range(len(ps))]
    streams = {}
    for mode in ("dense", "paged", "prefix"):
        eng = _mode_engine(cfg, mode)
        rs = eng.generate([p.copy() for p in ps], sps)
        streams[mode] = [r.generated for i, r in enumerate(rs)
                         if not sps[i].greedy]
    assert streams["dense"] == streams["paged"] == streams["prefix"]
    # ...and solo: same request alone reproduces its batched stream
    solo = make_engine(cfg, 1).generate([ps[1].copy()], sps[1])[0]
    assert solo.generated == streams["dense"][0]


def test_mixed_batch_keeps_single_host_transfer(monkeypatch):
    """Per-slot sampling runs INSIDE the jitted program: a mixed decode
    tick still moves exactly one [B]-shaped token array to the host."""
    cfg = tiny_cfg()
    eng = make_engine(cfg, max_batch=3, max_prefill_tokens=64)
    ps = prompts(cfg, 3, 8)
    for i, p in enumerate(ps):
        eng.submit(p, sampling=SamplingParams(
            temperature=0.0 if i % 2 == 0 else 0.9, seed=i,
            max_new_tokens=8))
    eng.step()                            # prefill tick: all decoding
    assert all(r is not None and r.state == RequestState.DECODING
               for r in eng.slot_req)
    transfers = []
    orig = ServingEngine._to_host

    def counting(self, arr):
        transfers.append(np.asarray(arr).shape)
        return orig(self, arr)

    monkeypatch.setattr(ServingEngine, "_to_host", counting)
    eng.step()                            # pure mixed decode tick
    assert transfers == [(eng.sc.max_batch,)]


def test_mixed_batch_host_transfers_match_all_greedy():
    """Acceptance criterion: per-request sampling must not add host
    transfers — an equal-tick mixed run moves exactly as many arrays as
    the all-greedy run."""
    cfg = tiny_cfg()
    ps = prompts(cfg, 4, 12)
    runs = {}
    for label, stoch in (("greedy", False), ("mixed", True)):
        eng = make_engine(cfg, 4, paged=True)
        sps = [SamplingParams(temperature=0.9 if stoch and i % 2 else 0.0,
                              seed=i, max_new_tokens=8)
               for i in range(len(ps))]
        eng.generate([p.copy() for p in ps], sps)
        runs[label] = (eng.n_ticks, eng.host_transfers)
    assert runs["mixed"] == runs["greedy"]


# ---------------------------------------------------------------------------
# abort: every lifecycle stage releases pages, pins, and drafter state
# ---------------------------------------------------------------------------


def test_abort_waiting_request_never_runs():
    cfg = tiny_cfg()
    eng = make_engine(cfg, max_batch=1)
    a = eng.submit(prompts(cfg, 1, 8)[0], max_new_tokens=4)
    b = eng.submit(prompts(cfg, 1, 8, seed=1)[0], max_new_tokens=4)
    out = eng.abort(b.req_id)             # still WAITING in the queue
    assert out.finished and out.finish_reason == "abort"
    assert out.n_generated == 0 and b.finish_reason == "abort"
    assert math.isnan(b.ttft)
    eng.run_until_drained()
    assert len(a.generated) == 4 and b.generated == []
    assert eng.abort(b.req_id) is None    # already finished: no-op
    assert eng.abort(12345) is None       # unknown id: no-op


@pytest.mark.parametrize("stage", ["prefilling", "decoding", "mid_verify"])
def test_abort_stages_conserve_pages_and_survivors(stage):
    """Abort mid-PREFILL, mid-DECODE, and between speculative verify
    windows: pages return to the pool, pool invariants hold, and the
    surviving greedy streams are bit-identical to an abort-free run."""
    cfg = tiny_cfg()
    spec = SpecConfig(k=3) if stage == "mid_verify" else None
    ps = [np.concatenate(pair) for pair in zip(
        prompts(cfg, 3, 24, seed=21, repeat_suffix=5),
        prompts(cfg, 3, 8, seed=22))]
    ref_eng = make_engine(cfg, 3, paged=True, spec=spec)
    ref = [r.generated for r in ref_eng.generate(
        [p.copy() for p in ps], SamplingParams(max_new_tokens=10))]

    eng = make_engine(cfg, 3, paged=True, spec=spec)
    rs = [eng.submit(p.copy(), sampling=SamplingParams(max_new_tokens=10))
          for p in ps]
    victim = rs[1]
    if stage == "prefilling":
        eng.step()                        # chunk 16 of 32: mid-prefill
        assert victim.state == RequestState.PREFILLING
        eng.abort(victim.req_id)
    else:
        while victim.state != RequestState.DECODING:
            eng.step()
        if stage == "mid_verify":
            while not victim.generated:   # at least one window committed
                eng.step()
        eng.abort(victim.req_id)
    assert victim.slot == -1 and victim.finish_reason == "abort"
    for p_ in eng.pool.pools:
        p_.check_invariants()
    eng.run_until_drained()
    assert eng.pool.free_pages() == eng.pool.n_pages
    for i, r in enumerate(rs):
        if r is not victim:
            assert r.generated == ref[i], f"abort changed survivor {i}"


def test_abort_never_strands_prefix_pins():
    """Aborting requests that attached cached prefix pages must leave the
    cache's pins intact and reclaimable: after drain + flush, every page
    is free again, and surviving requests still hit the cache."""
    cfg = tiny_cfg()
    eng = make_engine(cfg, 2, paged=True, prefix_cache=True, n_pages=32)
    head = prompts(cfg, 1, 16, seed=31)[0]
    ps = [np.concatenate([head, t]) for t in prompts(cfg, 4, 8, seed=32)]
    # first request publishes the head; the rest attach to it
    r0 = eng.generate([ps[0].copy()], SamplingParams(max_new_tokens=4))[0]
    assert r0.finish_reason == "length"
    rs = [eng.submit(p.copy(), sampling=SamplingParams(max_new_tokens=6))
          for p in ps[1:]]
    eng.step()                            # attach + begin prefill
    eng.abort(rs[0].req_id)               # holder of shared pages aborts
    for p_ in eng.pool.pools:
        p_.check_invariants()
    eng.run_until_drained()
    assert all(r.cached_tokens > 0 for r in rs[1:])   # cache still serves
    assert eng.prefix_stats()["hit_rate"] > 0
    # cache pins are the only remaining references; flushing frees all
    eng.prefix.flush(eng.pool)
    assert eng.pool.free_pages() == eng.pool.n_pages
    for p_ in eng.pool.pools:
        p_.check_invariants()


def test_abort_releases_draft_pool_state():
    cfg = tiny_cfg()
    spec = SpecConfig(k=3, drafter="model", draft_arch="qwen3-1.7b")
    eng = make_engine(cfg, 2, paged=True, spec=spec)
    ps = prompts(cfg, 2, 12, seed=41)
    rs = [eng.submit(p.copy(), sampling=SamplingParams(max_new_tokens=8))
          for p in ps]
    while not rs[0].generated:            # drafter has slot state now
        eng.step()
    eng.abort(rs[0].req_id)
    assert eng.drafter.owner[0] == -1 or eng.drafter.lens[0] == 0
    eng.run_until_drained()
    assert eng.drafter.pool.free_pages() == eng.drafter.pool.n_pages
    assert eng.pool.free_pages() == eng.pool.n_pages


def test_derived_seeds_are_plain_ints_without_overflow():
    """Regression: the derived-seed mix used np.uint32 scalar arithmetic,
    which overflows for any ServeConfig.seed >= 2 (NumPy 2 warns per
    submit, and raises OverflowError for a negative seed)."""
    cfg = tiny_cfg()
    with warnings.catch_warnings():
        warnings.simplefilter("error")            # any warning -> failure
        for base in (2, 12345, -3):
            eng = make_engine(cfg, seed=base)
            reqs = [eng.submit(p, max_new_tokens=1)
                    for p in prompts(cfg, 3, 6)]
            seeds = [r.seed for r in reqs]
            assert all(0 <= s < 2**31 for s in seeds)
            assert len(set(seeds)) == len(seeds)  # distinct per request


# ---------------------------------------------------------------------------
# hypothesis: abort interleaved with submit/step/preempt/evict conserves
# refcounts (host-only engine: device programs stubbed, accounting real)
# ---------------------------------------------------------------------------


class HostOnlyEngine(ServingEngine):
    """ServingEngine with the device programs stubbed out: every sampled
    token is 0 and the KV arrays are never touched, but admission, page
    accounting, COW, prefix attach/publish, preemption, speculative
    grow/truncate, and abort all run for real — fast enough to drive
    under hypothesis."""

    _CACHE_ARG = {"chunk": 5, "chunk_paged": 5, "whole": 3,
                  "packed": 6, "packed_paged": 6,
                  "decode": 2, "decode_paged": 2, "verify": 5}

    def _program(self, group, kind):
        cache_arg = self._CACHE_ARG[kind]

        def run(*args):
            cache = args[cache_arg]
            if kind == "verify":
                draft = np.asarray(args[7])
                out = np.zeros((draft.shape[0], draft.shape[1] + 2),
                               np.int32)
                out[:, -1] = 1            # accept nothing, emit one token
                return jnp.asarray(out), cache
            if kind in ("packed", "packed_paged"):
                n = np.asarray(args[2]).shape[0]  # one row per segment
            else:
                n = 1 if kind == "whole" else np.asarray(args[1]).shape[0]
            return jnp.zeros((n,), jnp.int32), cache

        return run

    def _copy_pages(self, copies):
        self.cow_copies += len(copies)    # accounting only, no device copy


def test_same_tick_preemption_still_reports_gained_tokens():
    """Regression: a request that completed prefill (gaining its seeding
    token) and was then chosen as a preemption victim later in the SAME
    tick ended the tick back in the queue — outside both the slot-holder
    and retired-this-tick lists — so its token never appeared in any
    RequestOutput and the reassembled stream disagreed with
    ``Request.generated``."""
    cfg = tiny_cfg()

    class PreemptAfterPrefill(HostOnlyEngine):
        preempt_next = False

        def _run_prefill_tick(self, plan):
            super()._run_prefill_tick(plan)
            if self.preempt_next:
                for r in self.slot_req:
                    if r is not None and r.state == RequestState.DECODING \
                            and r.generated:
                        self.preempt_next = False
                        self._preempt(r)
                        break

    eng = PreemptAfterPrefill(cfg, cached_params(cfg), ServeConfig(
        max_batch=2, max_len=64,
        phase=PhaseAwareConfig(max_decode_batch=2, prefill_chunk=8,
                               max_prefill_tokens=16),
        paged=True, page_size=4, n_pages=16))
    eng.preempt_next = True
    r = eng.submit(prompts(cfg, 1, 8)[0], max_new_tokens=4)
    streamed = []
    for out in eng.stream():
        streamed.extend(out.new_token_ids)
    assert r.n_preempted == 1             # the scenario actually fired
    assert r.state == RequestState.DONE
    assert streamed == r.generated        # nothing dropped, nothing doubled


try:
    import hypothesis  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:                       # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    from hypothesis import example, given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(ops=st.lists(
        st.tuples(st.integers(0, 3),      # 0 submit, 1 step, 2 abort,
                                          # 3 step+abort-youngest
                  st.integers(0, 7),      # prompt selector / abort target
                  st.integers(1, 30)),    # prompt length
        max_size=30))
    # two same-prefix prompts too long to prefill side by side: the stall
    # breaker's victim must not re-attach its cached pages every tick
    @example(ops=[(0, 0, 25), (0, 0, 24)])
    def test_abort_interleavings_conserve_refcounts(ops):
        """ANY interleaving of submit / step / abort on a small paged
        pool with the prefix cache and n-gram speculation on (so
        attach/publish, COW, window grow/truncate, and preemption all
        fire) keeps every run pool's refcount conservation, and ends
        with every page free once the cache is flushed."""
        cfg = tiny_cfg()
        eng = HostOnlyEngine(cfg, cached_params(cfg), ServeConfig(
            max_batch=2, max_len=64,
            phase=PhaseAwareConfig(max_decode_batch=2, prefill_chunk=8,
                                   max_prefill_tokens=16),
            paged=True, page_size=4, n_pages=12, prefix_cache=True,
            speculative=SpecConfig(k=2)))
        submitted = []
        for kind, sel, length in ops:
            if kind == 0:
                # low-diversity prompts: shared prefixes are common, so
                # attach/publish/COW paths all run
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
            for p in eng.pool.pools:
                p.check_invariants()
        for _ in range(200):
            if not (eng.queue or any(r is not None for r in eng.slot_req)):
                break
            eng.step()
        eng.prefix.flush(eng.pool)
        for p in eng.pool.pools:
            p.check_invariants()
            assert p.free_pages() == p.n_pages, \
                "pages leaked across the interleaving"
