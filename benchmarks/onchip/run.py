#!/usr/bin/env python3
"""On-chip serving benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--cpu-rehearsal]

Drives the normal serving path from the benchmark's own load generator,
in this one process: ``AsyncEngine.submit`` -> ``ServingEngine`` ->
``PhaseScheduler`` -> paged ``KVPool`` and prefix cache -> colocated
``Executor`` -> the ``packed_paged`` / ``decode_paged`` programs and
their Pallas kernels. Steps:

1. set-up (``setup_s``: process start to window open): weights drawn on
   the device from the seed; the engine and its page pool; every
   packed-prefill and decode shape the cell's traffic can produce run
   once (the persistent compile cache is ``.jax_cache/`` in the
   checkout); the first requests until the window opens;
2. the window: ``--seconds`` of client-side timestamps; with
   ``--trace 1`` the profiler records from ``trace_s`` before its close
   until it holds a prefill and a decode tick (the load goes on past the
   close until then);
3. the follow-up: requests of the window are followed to their first
   token, up to the mix's ``ttft_cap_s``;
4. correctness: the engine is freed, and a seeded sample of the
   requests the window finished, the longest among them, is compared by
   teacher forcing with the float32 reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``check``, each compared number beside its limit.
Without a TPU, or with fewer chips than the cell asks for, or on a chip
missing from the peaks table, the run exits non-zero and prints no
result. ``--cpu-rehearsal`` runs every step on the CPU at the program's
``reduced()`` size and prints a ``rehearsal`` line instead, never a
result.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Callable, Dict, List, Optional, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

import generator  # noqa: E402
import measure  # noqa: E402
import spec  # noqa: E402
from measure import Rec  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- set-up --------------------------------------------------------------------

def merged_mix(traffic: Dict, rehearsal: bool) -> Dict:
    mix = {k: v for k, v in traffic.items() if k != "rehearsal"}
    if rehearsal:
        mix.update(traffic.get("rehearsal", {}))
    return mix


class CompileCounter:
    """Counts JAX traces and backend compiles through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.traces = 0
        self.compiles = 0
        self.compile_s = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1
            elif event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration
        jax.monitoring.register_event_duration_secs_listener(listen)

    def snapshot(self) -> Tuple[int, int]:
        return self.traces, self.compiles


def build_engine(cfg, params, serving: Dict, max_context: int,
                 weights_dtype: str = "f32"):
    from repro.serving.engine import ServeConfig, ServingEngine
    from repro.serving.scheduler import PhaseAwareConfig
    sc = ServeConfig(
        max_batch=serving["max_batch"], max_len=max_context,
        phase=PhaseAwareConfig(
            max_decode_batch=serving["max_batch"],
            prefill_chunk=serving["prefill_chunk"],
            max_prefill_tokens=serving["max_prefill_tokens"]),
        paged=True, page_size=serving["page_size"],
        n_pages=serving["n_pages"], prefix_cache=serving["prefix_cache"],
        weights_dtype=weights_dtype)
    return ServingEngine(cfg, params, sc)


def warm_shapes(engine, serving: Dict) -> Tuple[List[int], List[int]]:
    """Every packed-stream length and decode row count the traffic can
    produce: streams of up to ``max_batch`` segments, each aligned to the
    engine's pack alignment, ``max_prefill_tokens`` real tokens in all;
    decode rows on the power-of-two ladder up to ``max_batch``."""
    from repro.serving.scheduler import align_up, bucket_pow2, bucket_tokens
    align, nb = engine._pack_align, serving["max_batch"]
    top = align_up(serving["max_prefill_tokens"] + (nb - 1) * (align - 1),
                   align)
    streams = sorted({max(bucket_tokens(n, align), align)
                      for n in range(align, top + 1, align)})
    rows = sorted({bucket_pow2(n, nb) for n in range(1, nb + 1)})
    return streams, rows


def warm_up(engine, serving: Dict) -> Tuple[List[int], List[int]]:
    """Run each packed-prefill and decode program of the cell's shapes
    once, with the engine's own argument types, writing nothing (every
    row's slot and block table is the sentinel)."""
    import jax.numpy as jnp
    streams, rows = warm_shapes(engine, serving)
    nb = serving["max_batch"]
    pg, dg = engine.scheduler.groups_for()
    for T in streams:
        starts = np.full((nb,), T, np.int32)
        starts[0] = 0
        lens = np.zeros((nb,), np.int32)
        lens[0] = T
        pp, _ = engine._pack_params([], nb)
        engine._note_compile(pg, "packed_paged", (T, nb), True)
        toks, engine.cache = engine._program(pg, "packed_paged")(
            engine.params, jnp.asarray(np.zeros((T,), np.int32)),
            jnp.asarray(starts), jnp.asarray(np.zeros((nb,), np.int32)),
            jnp.asarray(lens), jnp.asarray(np.full((nb,), nb, np.int32)),
            engine.cache, engine.pool.block_tables(), *pp, True)
        np.asarray(toks)
    for n in rows:
        pp, _ = engine._pack_params([], n)
        engine._note_compile(dg, "decode_paged", (n,), True)
        toks, engine.cache = engine._program(dg, "decode_paged")(
            engine.params, jnp.asarray(np.zeros((n, 1), np.int32)),
            engine.cache, jnp.asarray(np.zeros((n,), np.int32)),
            engine.pool.block_tables(rows=[], n=n), *pp, True)
        np.asarray(toks)
    return streams, rows


# -- the window ----------------------------------------------------------------

@dataclass
class Tick:
    """One engine tick as the benchmark saw it: its start on the host
    clock, the prefill segments it ran as (cached tokens, new tokens),
    and each decoded row's live context (counting the token decoded)."""
    t0: float
    segments: List[Tuple[int, int]] = field(default_factory=list)
    ctx: List[int] = field(default_factory=list)
    traced: bool = False


class Recorder:
    """Wraps ``engine.step`` to record each tick's work from the public
    request state, and starts and stops the profiler between ticks: it
    starts ``trace_s`` before the window closes and stops at the first
    tick past the close by which it has traced at least one prefill and
    one decode tick (the load runs on until then)."""

    def __init__(self, engine, clock, trace_s: float, trace_dir: str = ""):
        self.engine, self.clock = engine, clock
        self.ticks: List[Tick] = []
        self.trace_s, self.trace_dir = trace_s, trace_dir
        self.tracing = False
        self.trace_done = False
        self.traced_kinds = set()
        self.w1: Optional[float] = None
        self._step = engine.step
        engine.step = self.step

    def annotate(self, name: str):
        if self.trace_dir:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def start_trace(self) -> None:
        import jax
        jax.profiler.start_trace(self.trace_dir)
        self.tracing = True

    def stop_trace(self) -> None:
        import jax
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False
            self.trace_done = True

    def step(self):
        from repro.serving.types import RequestState
        eng = self.engine
        if (self.trace_dir and not self.tracing and not self.trace_done
                and self.w1 is not None
                and self.clock() >= self.w1 - self.trace_s):
            self.start_trace()
        live = [r for r in eng.slot_req if r is not None]
        before = {r.req_id: (r, r.prefill_pos, len(r.generated),
                             r.state == RequestState.PREFILLING)
                  for r in live + list(eng.queue)}
        t0 = self.clock()
        with self.annotate("bench.step"):
            outs = self._step()
        tick = Tick(t0=t0, traced=self.tracing)
        rec = eng.tick_log[-1]
        for rid in rec.prefill_reqs:
            r, pos0, _, was_prefilling = before[rid]
            start = pos0 if was_prefilling else r.cached_tokens
            if r.prefill_pos > start:
                tick.segments.append((start, r.prefill_pos - start))
        for rid in rec.decode_reqs:
            r, _, n0, _ = before[rid]
            if len(r.generated) > n0:
                tick.ctx.append(r.prompt_len + n0)
        self.ticks.append(tick)
        if self.tracing:
            self.traced_kinds |= ({"prefill"} if tick.segments else set()) \
                | ({"decode"} if tick.ctx else set())
            if self.clock() >= self.w1 and len(self.traced_kinds) == 2:
                self.stop_trace()
        return outs

    @property
    def trace_pending(self) -> bool:
        return bool(self.trace_dir) and not self.trace_done


async def drive(engine, plan: generator.Plan, mix: Dict, seconds: float,
                rec: Recorder, clock) -> Tuple[List[Rec], float, float]:
    """Serve the plan through ``AsyncEngine``; returns every request's
    client record and the window ``[w0, w1)``."""
    from repro.serving.frontend import AsyncEngine
    from repro.serving.sampling import SamplingParams
    recs: List[Rec] = []
    st = SimpleNamespace(w0=None, closed=False, firsts=0)
    opened = asyncio.Event()
    n_sessions = len(plan.sessions)

    def first_of_session() -> None:
        st.firsts += 1
        if st.firsts == n_sessions:
            st.w0 = clock()
            rec.w1 = st.w0 + seconds
            opened.set()

    async def one(fe, req: generator.Req, due: Optional[float],
                  on_first: Optional[Callable] = None) -> Rec:
        r = Rec(prompt_len=len(req.prompt), max_new=req.max_new, due=due,
                prompt=req.prompt)
        recs.append(r)
        r.sent = clock()
        try:
            h = await fe.submit(req.prompt, sampling=SamplingParams(
                temperature=0.0, max_new_tokens=req.max_new))
        except Exception as e:                 # refused at submit
            r.finish = "error"
            log(f"submit failed: {e!r}")
            return r
        r.request = h.request
        async for out in h:
            t = clock()
            with rec.annotate("bench.client"):
                if out.new_token_ids and not r.t_tok and on_first:
                    on_first()
                r.t_tok.extend([t] * len(out.new_token_ids))
                r.tokens.extend(int(x) for x in out.new_token_ids)
                if out.finished:
                    r.finish = out.finish_reason
        return r

    async def session(fe, s: int) -> None:
        for i, req in enumerate(plan.sessions[s]):
            if st.closed:
                return
            await one(fe, req, None, first_of_session if i == 0 else None)

    async def arrivals(fe) -> None:
        tasks = []
        for req in plan.arrivals:
            due = st.w0 + req.due_s
            if due >= st.w0 + seconds and not rec.trace_pending:
                break
            delay = due - clock()
            if delay > 0:
                await asyncio.sleep(delay)
            with rec.annotate("bench.client"):
                tasks.append(asyncio.create_task(one(fe, req, due)))
        await asyncio.gather(*tasks)

    async with AsyncEngine(engine) as fe:
        if plan.loop == "closed":
            tasks = [asyncio.create_task(session(fe, s))
                     for s in range(n_sessions)]
            await opened.wait()
        else:
            st.w0 = clock() + mix["lead_in_s"]
            rec.w1 = st.w0 + seconds
            tasks = [asyncio.create_task(arrivals(fe))]
        w0, w1 = st.w0, st.w0 + seconds
        await asyncio.sleep(max(w1 - clock(), 0))
        cap = w1 + mix["ttft_cap_s"]
        while rec.trace_pending and clock() < cap:
            await asyncio.sleep(0.01)
        st.closed = True
        while clock() < cap and any(
                not r.t_tok and r.finish is None
                for r in measure.window_requests(recs, w0, w1)):
            await asyncio.sleep(0.01)
        for r in recs:
            r.cut = r.finish is None
        for t in tasks:
            t.cancel()
        rec.stop_trace()
    await asyncio.gather(*tasks, return_exceptions=True)
    return recs, w0, w1


# -- correctness ---------------------------------------------------------------

def sample_requests(recs: List[Rec], w0: float, k: int, seed: int
                    ) -> List[Rec]:
    """A seeded sample of the requests finished in or after the window,
    always holding the longest (prompt plus output)."""
    done = [r for r in recs if r.finish == "length" and not r.cut
            and r.t_tok and r.t_tok[-1] >= w0]
    if not done:
        return []
    done.sort(key=lambda r: -(r.prompt_len + len(r.tokens)))
    rest = done[1:]
    rng = generator.seed_rng(seed, 2)
    pick = [rest[i] for i in sorted(rng.permutation(len(rest))[:k - 1])]
    return [done[0]] + pick


def teacher_forced(ref, weights, m: Dict, mix: Dict, r: Rec,
                   weight_bits: int = 16) -> np.ndarray:
    """Reference logits [n_tokens, V] at the positions that produced each
    of ``r``'s served tokens, fed the prompt and the served tokens; every
    request of the mix is padded to its longest, so the reference
    compiles once per cell."""
    gen = np.asarray(r.tokens, np.int32)
    seq = np.concatenate([np.asarray(r.prompt, np.int32), gen[:-1]])
    out = np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(gen))
    return ref.logits_at(weights, m, seq, out, weight_bits,
                         length=generator.max_context(mix),
                         rows=mix["output"]["hi"])


def below_best(lg: np.ndarray, picked: np.ndarray) -> np.ndarray:
    """How far each picked token's logit lies below the row's best."""
    return lg.max(-1) - lg[np.arange(len(picked)), picked]


def check(ref, weights, m: Dict, mix: Dict, sample: List[Rec],
          limit: float, control: bool = False,
          m_served: Optional[Dict] = None) -> Dict:
    """The mean gap by which a served token's float32-reference logit lies
    below the reference's best, over the sample's tokens (the number
    compared), and the widest such gap. With ``control``, also the
    control's readings: at the same positions, the tokens that the
    reference computed with int8 weights puts first. With ``m_served``,
    also the served tokens' gaps against the reference computed with that
    configuration (the model as the program runs it)."""
    gaps, ctl, served, spread = [], [], [], []
    for r in sample:
        lg = teacher_forced(ref, weights, m, mix, r)
        if not np.isfinite(lg).all():
            gaps.append(np.array([math.inf]))
            break
        gaps.append(below_best(lg, np.asarray(r.tokens)))
        spread.append(float(np.mean(lg.max(-1) - lg.mean(-1))))
        if control:
            lo = teacher_forced(ref, weights, m, mix, r, weight_bits=8)
            ctl.append(below_best(lg, lo.argmax(-1)))
        if m_served is not None:
            alt = teacher_forced(ref, weights, m_served, mix, r)
            served.append(below_best(alt, np.asarray(r.tokens)))
    out = dict(gap_readings(gaps, len(sample)), limit=limit,
               mean_token_below_max=float(np.mean(spread)) if spread
               else math.nan)
    if control:
        out["control_int8_reference"] = gap_readings(ctl, len(ctl))
    if m_served is not None:
        out["served_configuration"] = gap_readings(served, len(served))
    return out


def gap_readings(gaps: List[np.ndarray], requests: int) -> Dict:
    every = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"mean_gap_logits": float(every.mean()) if len(every) else math.inf,
            "max_gap_logits": float(every.max()) if len(every) else math.inf,
            "tokens": len(every), "requests": requests}


def verdict(reading: Dict, limit: float) -> bool:
    """``correct``: some requests were compared, and the mean gap of their
    tokens lies within the limit. The same verdict judges the program's
    runs and each control's reading."""
    return bool(reading["requests"] > 0
                and reading["mean_gap_logits"] <= limit)


# -- the run -------------------------------------------------------------------

def device_info(devs, chips: int) -> Dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak(devs, chips: int) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(args, weights_dtype: str = "f32", control: bool = False,
        mix_overrides: Optional[Dict] = None):
    """One run. For the tools beside it only: ``weights_dtype="int8"``
    serves the program's own int8 weight path and ``control`` adds the
    reference control's readings (``control.py``); ``mix_overrides``
    replaces keys of the traffic mix (``sweep.py``)."""
    import jax
    cell = spec.load_cell(args.workload)
    devs = jax.devices()
    peaks = None
    if not args.cpu_rehearsal:
        if devs[0].platform != "tpu":
            raise SystemExit(f"no TPU: JAX sees {devs[0].platform} devices "
                             "(--cpu-rehearsal runs the reduced CPU path)")
        if len(devs) < cell.chips:
            raise SystemExit(f"{cell.name} needs {cell.chips} chips, JAX "
                             f"sees {len(devs)}")
        from peaks import peaks_for
        peaks = peaks_for(devs[0].device_kind)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)
    counter = CompileCounter()
    adapter, ref = cell.adapter(), cell.reference()
    m = adapter.rehearsal_sizes(cell.config) if args.cpu_rehearsal \
        else dict(cell.config)
    mix = dict(merged_mix(cell.traffic, args.cpu_rehearsal),
               **(mix_overrides or {}))
    serving = mix["serving"]
    clock = time.monotonic

    t = clock()
    params = adapter.make_params(m, args.seed)
    jax.block_until_ready(params)
    t_weights = clock() - t
    engine = build_engine(adapter.program_config(m), params, serving,
                          generator.max_context(mix), weights_dtype)
    if weights_dtype != "f32":
        params = None           # the engine holds its own quantized copy
    t = clock()
    streams, rows = warm_up(engine, serving)
    t_warm = clock() - t
    plan = generator.build(mix, int(m["vocab_size"]), args.seed,
                           args.seconds, mix["ttft_cap_s"])
    trace_dir = tempfile.mkdtemp(prefix="onchip-trace-") if args.trace \
        else ""
    recorder = Recorder(engine, clock, mix["trace_s"], trace_dir)
    compiles0 = counter.snapshot()
    executor0 = engine.compile_count

    recs, w0, w1 = asyncio.run(drive(engine, plan, mix, args.seconds,
                                     recorder, clock))
    setup_s = w0 - T_PROCESS
    traces = counter.traces - compiles0[0]
    in_window = engine.compile_count - executor0
    mem = memory_peak(devs, cell.chips)
    e2e = measure.end_to_end(recs, w0, w1)
    late = measure.lateness(recs)
    for r in recs:
        if r.request is not None:
            r.cached_tokens = int(r.request.cached_tokens)
    log(f"set-up {setup_s:.3f}s: weights {t_weights:.3f}s, warm-up "
        f"{t_warm:.3f}s ({len(streams)} packed streams {streams}, decode "
        f"rows {rows}), {counter.compiles} backend compiles "
        f"({counter.compile_s:.3f}s) before the window")
    log(f"window {w1 - w0:.3f}s: {e2e['attempted']} requests, "
        f"{e2e['failed']} failed, {measure.tokens_in_window(recs, w0, w1)} "
        f"tokens, {e2e['n_gaps']} gaps, {len(recorder.ticks)} ticks; "
        f"traces in the window {traces}, new phase-program shapes "
        f"{in_window}; generator lateness p50 "
        f"{1e3 * measure.quantile(late, 0.5):.3f} ms max "
        f"{1e3 * max(late, default=0.0):.3f} ms")
    log("client numbers: " + ", ".join(
        f"{k} {e2e[k]}" for k in ("output_tok_s", "itl_p95_ms",
                                  "ttft_p50_ms", "ttft_p90_ms")))

    trace = None
    if trace_dir:
        import tracefile
        try:
            trace = tracefile.Trace.from_raw(tracefile.load_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = SimpleNamespace(
        model=m, peaks=peaks, itemsize=2, recs=recs, w0=w0, w1=w1,
        ticks=[k for k in recorder.ticks if w0 <= k.t0 < w1],
        traced=[k for k in recorder.ticks if k.traced], trace=trace)
    metrics: Dict[str, Dict] = {}
    if args.trace:
        for met in cell.per_layer:
            v = cell.reader(met["name"])(ctx)
            if v is not None:
                metrics[met["name"]] = {"value": v, "unit": met["unit"]}
    else:
        numbers = dict(e2e, setup_s=setup_s)
        for met in cell.end_to_end:
            metrics[met["name"]] = {"value": numbers[met["name"]],
                                    "unit": met["unit"]}

    # correctness: after the memory peak is read, with the pool freed
    sample = sample_requests(recs, w0, mix["check_requests"], args.seed)
    del engine, recorder
    gc.collect()
    t = clock()
    if params is None:
        params = adapter.make_params(m, args.seed)
    weights = adapter.reference_weights(params, m)
    limits = cell.config["check"]
    result = check(ref, weights, m, mix, sample, limits[
        "rehearsal_mean_gap_logits" if args.cpu_rehearsal
        else "mean_gap_logits"], control,
        adapter.as_served(m) if control else None)
    result["seconds"] = clock() - t
    result["longest_context"] = max(
        (r.prompt_len + len(r.tokens) for r in sample), default=0)
    return SimpleNamespace(cell=cell, devs=devs, metrics=metrics, e2e=e2e,
                           recs=recs, w0=w0, w1=w1,
                           mem=mem, trace=trace, check=result,
                           traces=traces, in_window=in_window)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run every step on the CPU at reduced() size; "
                         "prints no result")
    args = ap.parse_args(argv)
    out = run(args)
    c = out.check
    correct = verdict(c, c["limit"])
    log(f"reference: {c['tokens']} served tokens of {c['requests']} "
        f"requests (longest context {c['longest_context']}) in "
        f"{c['seconds']:.3f}s; a mean token sits "
        f"{c.get('mean_token_below_max', math.nan):.4f} below the max")
    log(f"widest gap {c['max_gap_logits']:.6f} (not compared)")
    log(f"check: mean_gap_logits {c['mean_gap_logits']:.6f} limit "
        f"{c['limit']}")
    check_key = {"mean_gap_logits": {"value": c["mean_gap_logits"],
                                     "limit": c["limit"]}}
    if args.cpu_rehearsal:
        print(json.dumps({"rehearsal": True, "correct": correct,
                          "attempted": out.e2e["attempted"],
                          "failed": out.e2e["failed"],
                          "numbers": {k: v["value"]
                                      for k, v in out.metrics.items()},
                          "traces_in_window": out.traces,
                          "check": check_key}))
        return 0
    device = device_info(out.devs, out.cell.chips)
    device["memory_peak_bytes"] = out.mem
    line = {"correct": correct, "attempted": out.e2e["attempted"],
            "failed": out.e2e["failed"], "metrics": out.metrics,
            "device": device}
    if args.trace:
        if out.trace is None:
            raise SystemExit("the trace holds no engine tick or no chip")
        device["busy_s"] = out.trace.busy_s
        device["window_s"] = out.trace.window_s
        line["breakdown"] = {"device_ops": out.trace.top_ops(),
                             "idle_gaps": out.trace.idle_gaps()}
    line["check"] = check_key
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
