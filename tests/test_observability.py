"""Observability layer (PR 9): metrics registry, lifecycle tracing,
SLO/goodput, and the bench regression gate.

The load-bearing claims: the registry IS the engine's counter state
(``counts()``/``spec_stats()`` are views, never copies), the trace
RECONCILES with the registry (summing span args reproduces the lifetime
counters exactly), and tracing is identity-preserving (tracer on vs off
yields bit-identical greedy streams at an equal compile count).
"""

import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models.transformer import init_params
from repro.serving import (
    SLO,
    MetricsRegistry,
    SamplingParams,
    ServeConfig,
    ServingEngine,
    Tracer,
    quantile,
    slo_attainment,
)
from repro.serving.engine import RequestState
from repro.serving.metrics import counter_attr, gauge_attr
from repro.serving.scheduler import PhaseAwareConfig
from repro.serving.tracing import PID, TICK_TID


def tiny_cfg(name="qwen3-1.7b"):
    return dataclasses.replace(get_config(name).reduced(), dtype="float32")


_PARAMS = {}


def cached_params(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = init_params(jax.random.PRNGKey(0), cfg)
    return _PARAMS[cfg.name]


def make_engine(cfg, max_batch=2, *, executor="colocated", paged=True,
                page_size=4, n_pages=32, host_spill_pages=0,
                prefix_cache=False, spec=None, max_len=96,
                prefill_chunk=8, max_prefill_tokens=16, tracer=None):
    sc = ServeConfig(max_batch=max_batch, max_len=max_len,
                     phase=PhaseAwareConfig(
                         max_decode_batch=max_batch,
                         prefill_chunk=prefill_chunk,
                         max_prefill_tokens=max_prefill_tokens),
                     paged=paged, page_size=page_size, n_pages=n_pages,
                     prefix_cache=prefix_cache, speculative=spec,
                     executor=executor, host_spill_pages=host_spill_pages)
    return ServingEngine(cfg, cached_params(cfg), sc, tracer=tracer)


def prompts(cfg, n, L, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (L,), dtype=np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# metrics registry (host-only, no jax)
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    m = MetricsRegistry()
    assert m.counter("nope") == 0 and m.gauge("nope") == 0
    m.inc("c")
    m.inc("c", 4)
    m.set_gauge("g", 7.5)
    m.observe("h", 0.003, buckets=(0.001, 0.01, 0.1))
    m.observe("h", 0.02)    # buckets fixed at first observe
    m.observe("h", 99.0)    # lands in +Inf
    snap = m.snapshot()
    assert snap["counters"] == {"c": 5}
    assert snap["gauges"] == {"g": 7.5}
    h = snap["histograms"]["h"]
    # cumulative le-buckets: nothing <= 1ms, one <= 10ms, two <= 100ms,
    # all three <= +Inf
    assert h["buckets"] == [[0.001, 0], [0.01, 1], [0.1, 2],
                            [math.inf, 3]]
    assert h["count"] == 3 and h["sum"] == pytest.approx(99.023)
    assert m.values(["c", "ghost"]) == {"c": 5, "ghost": 0}


def test_registry_disabled_gates_instrumentation_not_state():
    m = MetricsRegistry(enabled=False)
    m.inc("c")
    m.set_gauge("g", 1.0)
    m.observe("h", 0.5)
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    # the property store path (engine STATE) is unconditional
    m.set_counter("c", 3)
    m.force_gauge("g", 2.0)
    assert m.counter("c") == 3 and m.gauge("g") == 2.0


def test_histogram_rejects_bad_buckets_and_skips_nan():
    m = MetricsRegistry()
    with pytest.raises(ValueError, match="buckets"):
        m.observe("h", 1.0, buckets=())
    with pytest.raises(ValueError, match="buckets"):
        m.observe("h", 1.0, buckets=(2.0, 1.0))
    m.observe("ok", float("nan"))
    assert "ok" not in m.snapshot()["histograms"] or \
        m.snapshot()["histograms"]["ok"]["count"] == 0


def test_prometheus_render():
    m = MetricsRegistry()
    m.inc("serving_ticks_total", 3)
    m.set_gauge("serving_requests_active", 2)
    m.observe("serving_ttft_seconds", 0.004, buckets=(0.001, 0.01))
    m.observe("serving_ttft_seconds", 5.0)
    text = m.render()
    assert "# TYPE serving_ticks_total counter" in text
    assert "serving_ticks_total 3" in text
    assert "# TYPE serving_requests_active gauge" in text
    assert "# TYPE serving_ttft_seconds histogram" in text
    assert 'serving_ttft_seconds_bucket{le="0.001"} 0' in text
    assert 'serving_ttft_seconds_bucket{le="0.01"} 1' in text
    assert 'serving_ttft_seconds_bucket{le="+Inf"} 2' in text
    assert "serving_ttft_seconds_count 2" in text
    assert "serving_ttft_seconds_sum 5.004" in text
    assert text.endswith("\n")
    assert MetricsRegistry().render() == ""


def test_counter_attr_routes_through_registry():
    class Thing:
        hits = counter_attr("thing_hits_total")
        level = gauge_attr("thing_level")

        def __init__(self):
            self.metrics = MetricsRegistry(enabled=False)
            self.hits = 0

    t = Thing()
    t.hits += 2
    t.hits += 3
    t.level = 9
    # the attribute and the registry are the SAME cell — even disabled
    # (state store is unconditional)
    assert t.hits == 5 and t.metrics.counter("thing_hits_total") == 5
    assert t.level == 9 and t.metrics.gauge("thing_level") == 9


def test_quantile():
    assert quantile([3.0, 1.0, 2.0], 0.5) == 2.0
    assert quantile([1.0, 2.0], 0.5) == 1.5
    # NaN/None dropped, not zeroed
    assert quantile([1.0, float("nan"), 3.0, None], 0.5) == 2.0
    assert math.isnan(quantile([], 0.5))
    assert math.isnan(quantile([float("nan")], 0.9))
    xs = [0.3, 7.0, 1.5, 2.2, 9.9, 4.1, 0.01]
    for q in (0.0, 0.25, 0.5, 0.9, 1.0):
        assert quantile(xs, q) == pytest.approx(
            float(np.quantile(xs, q, method="linear")))
    with pytest.raises(ValueError, match="quantile"):
        quantile([1.0], 1.5)


# ---------------------------------------------------------------------------
# SLO arithmetic (synthetic timelines — no engine)
# ---------------------------------------------------------------------------


def test_slo_validates_deadlines():
    SLO()                                   # both absent is fine
    SLO(ttft_ms=100.0)
    with pytest.raises(ValueError, match="ttft_ms"):
        SLO(ttft_ms=0.0)
    with pytest.raises(ValueError, match="tpot_ms"):
        SLO(tpot_ms=-5.0)


def test_slo_attainment_arithmetic():
    nan = float("nan")
    slo = SLO(ttft_ms=100.0, tpot_ms=10.0)
    assert slo_attainment(0.05, 0.005, slo) == (True, True, True)
    assert slo_attainment(0.2, 0.005, slo) == (False, False, True)
    assert slo_attainment(0.05, 0.02, slo) == (False, True, False)
    assert slo_attainment(0.2, 0.02, slo) == (False, False, False)
    # deadline boundary is inclusive (<=)
    assert slo_attainment(0.1, 0.01, slo)[0]
    # NaN fails a present deadline, passes an absent one
    assert slo_attainment(nan, 0.005, slo) == (False, False, True)
    assert slo_attainment(nan, nan, SLO(tpot_ms=10.0)) == \
        (False, True, False)
    assert slo_attainment(nan, nan, SLO()) == (True, True, True)


# ---------------------------------------------------------------------------
# tracer (fake clock — deterministic timeline)
# ---------------------------------------------------------------------------


def make_clock(start=100.0):
    state = {"t": start}

    def clock():
        state["t"] += 1.0
        return state["t"]

    return clock


def test_tracer_event_schema():
    tr = Tracer(clock=make_clock())           # t0 = 101
    t0, t1 = tr.now(), tr.now()               # 102, 103
    tr.begin_request(5, t0, prompt_len=8)
    tr.request_span(5, "prefill_chunk", t0, t1, take=8, offset=0)
    tr.tick_span(t0, t1, index=0, preemptions=0)
    tr.instant("first_token", t1, req_id=5)
    tr.instant("compile", t1, group="decode")
    tr.end_request(5, t1, reason="length")
    evs = tr.events()
    meta = [e for e in evs if e["ph"] == "M"]
    # process_name + "ticks" thread + one "req 5" thread, named ONCE
    assert [m["args"]["name"] for m in meta] == \
        ["serving-engine", "ticks", "req 5"]
    b, = [e for e in evs if e["ph"] == "b"]
    e, = [e for e in evs if e["ph"] == "e"]
    assert b["cat"] == e["cat"] == "request" and b["id"] == e["id"] == 5
    assert b["tid"] == e["tid"] == 6         # tid = req_id + 1
    assert b["ts"] == pytest.approx(1e6) and b["args"]["prompt_len"] == 8
    span, tick = [e for e in evs if e["ph"] == "X"]
    assert span["cat"] == "phase" and span["name"] == "prefill_chunk"
    assert span["dur"] == pytest.approx(1e6)  # 1 fake-second
    assert tick["cat"] == "tick" and tick["tid"] == TICK_TID
    ft, comp = [e for e in evs if e["ph"] == "i"]
    assert ft["s"] == "t" and ft["tid"] == 6 and comp["tid"] == TICK_TID
    assert all(ev["pid"] == PID for ev in evs)
    doc = tr.to_json()
    assert doc["traceEvents"] is evs and doc["displayTimeUnit"] == "ms"
    json.dumps(doc)                          # must be serializable


def test_tracer_span():
    tr = Tracer(clock=make_clock())           # t0 = 101
    with tr.span("serve.plan") as span:       # enters at 102, exits at 103
        pass
    ev, = [e for e in tr.events() if e["ph"] == "X"]
    assert ev == {"ph": "X", "cat": "span", "pid": PID, "tid": TICK_TID,
                  "ts": pytest.approx(1e6), "dur": pytest.approx(1e6),
                  "name": "serve.plan"}
    assert (span.t0, span.t1) == (102.0, 103.0)
    with pytest.raises(KeyError):             # closes on the way out
        with tr.span("serve.emit"):
            raise KeyError("x")
    assert [e["name"] for e in tr.events() if e["ph"] == "X"] == \
        ["serve.plan", "serve.emit"]
    off = Tracer(enabled=False)
    with off.span("serve.plan"):
        pass
    assert off.events() == []


def test_disabled_tracer_span_reaches_the_profiler(tmp_path):
    """A disabled tracer's span is still a profiler annotation: under
    ``jax.profiler.trace`` it lands among the host events, by its plain
    name."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        with Tracer(enabled=False).span("serve.plan"):
            jax.numpy.ones(4).sum().block_until_ready()
    path, = Path(tmp_path).rglob("*.xplane.pb")
    names = [e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("serve.plan") == 1


def test_tracer_disabled_is_inert():
    tr = Tracer(enabled=False)
    assert tr.now() == 0.0
    tr.begin_request(0, 0.0)
    tr.request_span(0, "decode", 0.0, 1.0)
    tr.tick_span(0.0, 1.0)
    tr.instant("preempt", 0.0)
    tr.end_request(0, 0.0)
    assert tr.events() == []


def test_tracer_clamps_pre_epoch_timestamps(tmp_path):
    tr = Tracer(clock=make_clock())
    tr.request_span(0, "queued", -5.0, tr.now())  # t_submit predates t0
    span = [e for e in tr.events() if e["ph"] == "X"][0]
    assert span["ts"] == 0.0 and span["dur"] >= 0.0
    out = tmp_path / "t.json"
    tr.write(str(out))
    assert json.loads(out.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# engine integration: identity, reconciliation, registry-as-state
# ---------------------------------------------------------------------------


def _forced_preempt_drain(eng, ps, max_new=6):
    """Drive the engine, preempting a decoding request once mid-stream
    (deterministic — no reliance on pool-pressure timing)."""
    reqs = [eng.submit(p.copy(),
                       sampling=SamplingParams(max_new_tokens=max_new),
                       slo=SLO(ttft_ms=60_000.0, tpot_ms=60_000.0))
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


def test_tracing_identity_and_trace_registry_reconciliation():
    cfg = tiny_cfg()
    ps = prompts(cfg, 3, 16, seed=11)
    kw = dict(executor="disaggregated", n_pages=64, host_spill_pages=32)
    off = make_engine(cfg, **kw)
    ref = _forced_preempt_drain(off, ps)

    tracer = Tracer()
    eng = make_engine(cfg, tracer=tracer, **kw)
    # identity-preserving: bit-identical streams, zero extra compiles
    assert _forced_preempt_drain(eng, ps) == ref
    assert eng.executor.compile_count == off.executor.compile_count

    evs = tracer.events()
    ticks = [e for e in evs if e.get("cat") == "tick"]
    spans = [e for e in evs if e.get("cat") == "phase"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)

    # the conservation laws: span/tick args sum to the lifetime counters
    assert len(ticks) == eng.n_ticks
    assert sum(s["args"]["take"] for s in by_name["prefill_chunk"]) \
        == eng.prefill_tokens_executed
    assert sum(s["args"]["tokens"] for s in by_name.get("decode", [])) \
        == eng.decode_tokens_emitted
    for key, want in (
            ("preemptions", eng.preemptions),
            ("migrated_bytes", eng.executor.migrated_bytes),
            ("migrated_pages", eng.executor.migrated_pages),
            ("swap_out_bytes", eng.counts()["swap_out_bytes"]),
            ("swap_in_bytes", eng.counts()["swap_in_bytes"]),
            ("prefill_tokens", eng.prefill_tokens_executed)):
        assert sum(t["args"][key] for t in ticks) == want, key

    # lifecycle envelopes: every request opened and closed exactly once
    begins = [e for e in evs if e["ph"] == "b"]
    ends = [e for e in evs if e["ph"] == "e"]
    assert len(begins) == len(ends) == len(ps)
    assert {e["id"] for e in begins} == {e["id"] for e in ends}
    # the preempted request shows the full story on its own track:
    # preempt instant, swap spans, and a second queued span
    preempts = [e for e in evs if e["ph"] == "i"
                and e["name"] == "preempt"]
    assert len(preempts) == eng.preemptions >= 1
    assert preempts[0]["args"]["swapped"] is True
    assert len(by_name["swap_out"]) == len(by_name["swap_in"]) >= 1
    assert sum(s["args"]["bytes"] for s in by_name["swap_out"]) \
        == eng.counts()["swap_out_bytes"]
    victim_tid = preempts[0]["tid"]
    assert len([s for s in by_name["queued"]
                if s["tid"] == victim_tid]) == 2
    # well-formed: every event serializes, durations non-negative
    json.dumps(tracer.to_json())
    assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")
    assert all(e["ts"] >= 0 for e in evs if "ts" in e)
    # compute-phase spans nest inside the tick that ran them ("queued"
    # opens at submit time, before any tick exists; swap spans may be
    # caller-driven between ticks, as the forced preempt here is)
    windows = [(t["ts"], t["ts"] + t["dur"]) for t in ticks]
    eps = 1.0                                # µs of float slack
    for s in spans:
        if s["name"] in ("queued", "swap_out", "swap_in"):
            continue
        assert any(lo - eps <= s["ts"] and s["ts"] + s["dur"] <= hi + eps
                   for lo, hi in windows), \
            f"{s['name']} span outside every tick window"
    # the profiler-span twins: each tick event spans its serve.step
    # exactly, and every engine phase span lies inside one
    phases = [e for e in evs if e.get("cat") == "span"]
    steps = [(e["ts"], e["ts"] + e["dur"]) for e in phases
             if e["name"] == "serve.step"]
    assert steps == windows
    assert {"serve.admit", "serve.plan", "serve.emit",
            "serve.prefill.launch", "serve.decode.sync"} <= \
        {e["name"] for e in phases}
    for e in phases:
        assert any(lo <= e["ts"] and e["ts"] + e["dur"] <= hi
                   for lo, hi in steps), e["name"]
    # t_admit is the first admission, on the engine's clock: the swapped
    # victim's re-admission leaves it, and its first queued span ends
    # there
    reqs = sorted(eng.done, key=lambda r: r.req_id)
    assert len(reqs) == len(ps)
    for r in reqs:
        assert 0 < r.t_submit <= r.t_admit <= r.t_first_token
    victim, = [r for r in reqs if r.n_preempted]
    queued = sorted((s for s in by_name["queued"]
                     if s["tid"] == victim_tid), key=lambda s: s["ts"])
    assert victim_tid == victim.req_id + 1
    t_admit_us = (victim.t_admit - tracer._t0) * 1e6
    assert queued[0]["ts"] + queued[0]["dur"] == pytest.approx(t_admit_us)
    assert queued[1]["ts"] + queued[1]["dur"] > t_admit_us + eps


def test_one_tick_nests_its_phase_spans_in_order():
    """A mixed tick (one prompt prefills to completion beside a decoding
    request) runs each phase in its own span, in order, inside
    ``serve.step``; the tick event spans ``serve.step`` exactly."""
    cfg = tiny_cfg()
    tracer = Tracer()
    eng = make_engine(cfg, tracer=tracer)
    a, b = prompts(cfg, 2, 8, seed=3)
    ra = eng.submit(a, sampling=SamplingParams(max_new_tokens=4))
    while ra.state != RequestState.DECODING:
        eng.step()
    rb = eng.submit(b, sampling=SamplingParams(max_new_tokens=4))
    n0 = len(tracer.events())
    eng.step()
    assert rb.state == RequestState.DECODING and len(ra.generated) == 2
    evs = tracer.events()[n0:]
    phases = [e for e in evs if e.get("cat") == "span"]
    step, = [e for e in phases if e["name"] == "serve.step"]
    inner = sorted((e for e in phases if e is not step),
                   key=lambda e: e["ts"])
    assert [e["name"] for e in inner] == [
        "serve.admit", "serve.plan",
        "serve.prefill.prepare", "serve.prefill.launch", "serve.prefill.sync",
        "serve.decode.prepare", "serve.decode.launch", "serve.decode.sync",
        "serve.emit"]
    end = step["ts"] + step["dur"]
    for e, nxt in zip(inner, inner[1:] + [None]):
        assert step["ts"] <= e["ts"] and e["ts"] + e["dur"] <= end
        if nxt is not None:
            assert e["ts"] + e["dur"] <= nxt["ts"]
    tick, = [e for e in evs if e.get("cat") == "tick"]
    assert (tick["ts"], tick["dur"]) == (step["ts"], step["dur"])


def test_async_engine_spans_drain_and_fanout():
    import asyncio

    from repro.serving import AsyncEngine
    cfg = tiny_cfg()
    tracer = Tracer()
    eng = make_engine(cfg, tracer=tracer)

    async def go():
        async with AsyncEngine(eng) as fe:
            h = await fe.submit(prompts(cfg, 1, 8)[0], max_new_tokens=3)
            return [out async for out in h]
    outs = asyncio.run(go())
    assert outs[-1].finished
    phases = [e for e in tracer.events() if e.get("cat") == "span"]
    steps = [(e["ts"], e["ts"] + e["dur"]) for e in phases
             if e["name"] == "serve.step"]
    fanouts = [e for e in phases if e["name"] == "serve.frontend.fanout"]
    assert len(fanouts) == len(steps) == eng.n_ticks
    assert any(e["name"] == "serve.frontend.drain" for e in phases)
    # the owner loop's work lies outside every engine tick
    for e in phases:
        if e["name"].startswith("serve.frontend."):
            assert not any(lo < e["ts"] < hi for lo, hi in steps)


def test_t_admit_survives_a_preemption():
    cfg = tiny_cfg()
    eng = make_engine(cfg)
    r = eng.submit(prompts(cfg, 1, 8)[0], max_new_tokens=6)
    assert r.t_admit == 0.0
    while len(r.generated) < 2:
        eng.step()
    t_admit = r.t_admit
    assert r.t_submit <= t_admit <= r.t_first_token
    eng._preempt(r)
    eng.run_until_drained()
    assert r.n_preempted == 1 and r.state == RequestState.DONE
    assert r.t_admit == t_admit


def test_counts_and_spec_stats_are_registry_views():
    # a mixed paged/prefix/speculative run: conservation must hold with
    # cached prefill tokens SKIPPED and decode tokens arriving via
    # verify windows rather than single-token decode spans
    cfg = tiny_cfg()
    from repro.serving import SpecConfig
    tracer = Tracer()
    eng = make_engine(cfg, spec=SpecConfig(k=2), n_pages=64,
                      prefix_cache=True, tracer=tracer)
    head = prompts(cfg, 1, 8, seed=9)[0]
    # drain sequentially so the second request HITS the first's cached
    # head (concurrent prefills would race the radix-tree insert)
    for p in prompts(cfg, 2, 12, seed=3):
        eng.submit(np.concatenate([head, p]),
                   sampling=SamplingParams(max_new_tokens=6))
        eng.run_until_drained()
    spans = [e for e in tracer.events() if e.get("cat") == "phase"]
    assert eng.prefix_stats()["hit_tokens"] > 0   # the cache actually hit
    assert sum(s["args"]["take"] for s in spans
               if s["name"] == "prefill_chunk") \
        == eng.prefill_tokens_executed
    assert (sum(s["args"]["tokens"] for s in spans
                if s["name"] == "decode")
            + sum(s["args"]["emitted"] for s in spans
                  if s["name"] == "verify_window")) \
        == eng.decode_tokens_emitted
    m = eng.metrics
    assert eng.decode_tokens_emitted \
        == m.counter("serving_decode_tokens_total") > 0
    ss = eng.spec_stats()
    assert ss["windows"] == m.counter("serving_spec_windows_total") > 0
    assert ss["accepted"] == m.counter("serving_spec_accepted_total")
    snap = eng.metrics_snapshot()
    assert snap["gauges"]["serving_requests_done"] == 2
    assert snap["gauges"]["serving_requests_active"] == 0
    assert snap["counters"]["serving_ticks_total"] == eng.n_ticks
    # latency histograms observed once per retired request / tick
    assert snap["histograms"]["serving_ttft_seconds"]["count"] == 2
    assert snap["histograms"]["serving_tick_wall_seconds"]["count"] \
        == eng.n_ticks
    # TickRecord deltas and lifetime counters tell one story
    assert sum(t.preemptions for t in eng.tick_log) == eng.preemptions
    assert sum(t.spec_drafted for t in eng.tick_log) \
        == m.counter("serving_spec_drafted_total")


def test_slo_goodput_end_to_end():
    cfg = tiny_cfg()
    eng = make_engine(cfg, n_pages=64)
    ps = prompts(cfg, 3, 12, seed=5)
    # generous deadline, impossible deadline, no SLO at all
    eng.submit(ps[0].copy(), sampling=SamplingParams(max_new_tokens=4),
               slo=SLO(ttft_ms=120_000.0, tpot_ms=120_000.0))
    eng.submit(ps[1].copy(), sampling=SamplingParams(max_new_tokens=4),
               slo=SLO(ttft_ms=1e-6))
    eng.submit(ps[2].copy(), sampling=SamplingParams(max_new_tokens=4))
    eng.run_until_drained()
    g = eng.goodput()
    assert g == {"slo_total": 2, "slo_attained": 1, "ttft_violations": 1,
                 "tpot_violations": 0, "goodput": 0.5}
    c = eng.counts()
    assert (c["slo_total"], c["slo_attained"], c["goodput"]) == (2, 1, 0.5)


def test_goodput_vacuous_and_abort_excluded():
    cfg = tiny_cfg()
    eng = make_engine(cfg, n_pages=64)
    assert eng.goodput()["goodput"] == 1.0       # no SLO'd requests ever
    r = eng.submit(prompts(cfg, 1, 12)[0],
                   sampling=SamplingParams(max_new_tokens=4),
                   slo=SLO(ttft_ms=1e-6))
    eng.abort(r.req_id)                          # client gave up pre-run
    eng.run_until_drained()
    # the aborted request neither met nor missed its deadline
    assert eng.goodput() == {"slo_total": 0, "slo_attained": 0,
                             "ttft_violations": 0, "tpot_violations": 0,
                             "goodput": 1.0}


def test_submit_rejects_non_slo():
    cfg = tiny_cfg()
    eng = make_engine(cfg)
    with pytest.raises(TypeError, match="slo"):
        eng.submit(prompts(cfg, 1, 8)[0], slo={"ttft_ms": 5.0})


def test_check_drained_failure_carries_diagnostics():
    cfg = tiny_cfg()
    eng = make_engine(cfg, n_pages=64)
    eng.submit(prompts(cfg, 1, 12)[0],
               sampling=SamplingParams(max_new_tokens=8))
    with pytest.raises(RuntimeError) as ei:
        eng.run_until_drained(max_ticks=1)
    msg = str(ei.value)
    assert "max_ticks=1" in msg
    assert "states=" in msg and "counts=" in msg and "last_tick=" in msg
    assert "decoding" in msg or "prefilling" in msg


# ---------------------------------------------------------------------------
# bench regression gate (stdlib-only script, loaded from scripts/)
# ---------------------------------------------------------------------------


def _load_gate():
    path = Path(__file__).resolve().parent.parent \
        / "scripts" / "check_bench_regression.py"
    spec = importlib.util.spec_from_file_location("check_bench_regression",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_json(path, rows):
    path.write_text(json.dumps({
        "bench": "serving", "suites": ["s"],
        "rows": [{"name": n, "value": v, "unit": u, "paper": None}
                 for n, v, u in rows]}))
    return str(path)


def test_regression_gate_passes_and_fails(tmp_path, capsys):
    gate = _load_gate()
    base = _bench_json(tmp_path / "base.json",
                       [("a.compiled_shapes", 4.0, "count"),
                        ("a.ttft_p50_ms", 12.0, "ms"),
                        ("a.pad_waste_frac", 0.25, "frac")])
    same = _bench_json(tmp_path / "same.json",
                       [("a.compiled_shapes", 4.0, "count"),
                        ("a.ttft_p50_ms", 900.0, "ms"),   # timing: no gate
                        ("a.pad_waste_frac", 0.26, "frac")])  # 4% < 5%
    assert gate.main(["--compare", f"{base}={same}"]) == 0

    drift = _bench_json(tmp_path / "drift.json",
                        [("a.compiled_shapes", 8.0, "count"),
                         ("a.ttft_p50_ms", 12.0, "ms"),
                         ("a.pad_waste_frac", 0.25, "frac")])
    assert gate.main(["--compare", f"{base}={drift}"]) == 1
    assert "compiled_shapes" in capsys.readouterr().err
    # warn-only mode reports the same drift but exits 0
    assert gate.main(["--compare", f"{base}={drift}", "--warn-only"]) == 0
    assert "::warning" in capsys.readouterr().out
    # per-row override admits the intended change
    assert gate.main(["--compare", f"{base}={drift}",
                      "--tolerance", "a.compiled_shapes=1.5"]) == 0


def test_regression_gate_row_lifecycle(tmp_path, capsys):
    gate = _load_gate()
    base = _bench_json(tmp_path / "base.json",
                       [("a.rows", 10.0, "rows"), ("a.nan", float("nan"),
                                                   "count")])
    missing = _bench_json(tmp_path / "missing.json",
                          [("a.nan", float("nan"), "count")])
    assert gate.main(["--compare", f"{base}={missing}"]) == 1
    assert "missing" in capsys.readouterr().err
    extra = _bench_json(tmp_path / "extra.json",
                        [("a.rows", 10.0, "rows"),
                         ("a.nan", float("nan"), "count"),
                         ("a.new_metric", 1.0, "count")])
    assert gate.main(["--compare", f"{base}={extra}"]) == 0
    assert "new row" in capsys.readouterr().out
    # NaN -> number on a structural row is drift, not a silent pass
    flip = _bench_json(tmp_path / "flip.json",
                       [("a.rows", 10.0, "rows"), ("a.nan", 3.0, "count")])
    assert gate.main(["--compare", f"{base}={flip}"]) == 1
    unit = _bench_json(tmp_path / "unit.json",
                       [("a.rows", 10.0, "MB"), ("a.nan", float("nan"),
                                                 "count")])
    assert gate.main(["--compare", f"{base}={unit}"]) == 1
    assert gate.main(["--compare", f"{base}=/nonexistent.json"]) == 1
