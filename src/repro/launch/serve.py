"""Serving driver: batched requests through the phase-disaggregated engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --requests 16 --prompt-len 48 --max-new 24 --strategy halo \
      --prefill-chunk 16 --max-prefill-tokens 32

Reports per-request TTFT/TPOT and the per-tick phase occupancy that the
chunked-prefill scheduler produces (fraction of ticks running prefill and
decode together — HALO's interleaved CiM/CiD utilization at serving level).
``--paged`` swaps the dense arena for the block-pool KV cache
(serving/kv_pool.py): capacity becomes pool-bounded (``--n-pages`` x
``--page-size`` tokens, so prompts may exceed --max-len), exhaustion
preempts the youngest request, and the report adds resident KV bytes +
preemption counts.  ``--kv-dtype int8`` stores KV pages quantized (GQA
k/v or MLA latents, per-token scale pages); ``--kv-dtype int4`` packs
GQA pages two nibbles per byte for ~4x KV-byte reduction.
``--weights-dtype int8`` quantizes matmul weights per output channel at
engine build and routes decode-shaped matmuls through the fused
dequantizing GEMV kernel — the TPU analogue of HALO's int8 CiD banks.
``--prefix-cache`` (with ``--paged``) reuses shared-prompt KV pages
copy-on-write through a radix prefix cache; ``--shared-prefix N`` gives
every request the same N-token prompt head so the cache has something to
hit, and the report adds hit rate + prefill tokens skipped.
``--speculative`` (with ``--paged``) turns on the draft/verify loop
(serving/speculative.py): ``--spec-k`` draft tokens per decode tick from
the model-free n-gram drafter, or from a small draft model with
``--draft <arch>``; the report adds acceptance rate and tokens/tick.

Sampling is PER REQUEST (``SamplingParams``): ``--temperature 0`` IS
greedy — the CLI no longer rewrites 0 to 1e-6 — and ``--temperature``,
``--top-k``, ``--top-p``, ``--stop-token`` (repeatable) apply to every
request.  ``--mixed-sampling`` makes odd-indexed requests sample at the
given temperature while even-indexed ones stay greedy — a mixed batch
runs in ONE program per tick, and the report's finish-reason counts
show what ended each stream.

Observability (docs/serving.md §Observability): ``--trace-out t.json``
records request-lifecycle spans and per-tick phase events as Chrome
trace-event JSON (open in Perfetto), ``--metrics m.prom`` dumps the
engine's metrics registry as Prometheus text, and ``--slo-ttft-ms`` /
``--slo-tpot-ms`` attach per-request deadlines so the report includes
goodput (fraction of requests meeting their SLO).

``--traffic`` replaces the fixed submit-everything-at-once batch with a
seeded arrival trace replayed through the ASYNC front-end
(serving/frontend.py + serving/traffic.py): ``--rate-rps`` arrivals per
second for ``--duration-s`` seconds (``--arrival onoff`` for bursty
ON-OFF instead of Poisson), prompt/output lengths drawn per request up
to --prompt-len/--max-new, paced in real time (``--time-scale`` scales
the clock; 0 submits in trace order with no waiting — deterministic).
``--admission`` turns on shed-before-thrash admission control: requests
whose projected TTFT busts their deadline are refused at submit
(``--admission-tick-cost-s`` fixes the projection's seconds-per-tick —
deterministic decisions — instead of the live tick-wall EMA;
``--max-pending-tokens`` adds the structural backpressure cap).  The
report becomes the traffic scorecard: goodput under SLO, TTFT/TPOT
percentiles over served requests, shed/defer rates, preemption counts.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--strategy", default="halo",
                    choices=["halo", "cent", "attacc"])
    ap.add_argument("--prefill-chunk", type=int, default=2048,
                    help="tokens per prefill chunk (chunked prefill)")
    ap.add_argument("--max-prefill-tokens", type=int, default=8192,
                    help="per-tick prefill token budget")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 enables device-side sampling "
                         "(per-request SamplingParams — no epsilon rewrite)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling threshold (0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stop-token", type=int, action="append", default=[],
                    metavar="ID",
                    help="extra stop-token id (repeatable; finish reason "
                         "'stop')")
    ap.add_argument("--mixed-sampling", action="store_true",
                    help="odd-indexed requests sample at --temperature, "
                         "even-indexed ones stay greedy (exercises "
                         "per-request sampling heterogeneity in one "
                         "program)")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative decoding: draft/verify loop over "
                         "the paged arena (paged only)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft tokens per verify window (default 4)")
    ap.add_argument("--draft", default=None, metavar="ARCH",
                    help="draft-model arch (e.g. qwen3-1.7b); default is "
                         "the model-free n-gram drafter")
    ap.add_argument("--paged", action="store_true",
                    help="paged block-pool KV arena (capacity = pool, "
                         "not max_len; preemption on exhaustion)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged)")
    ap.add_argument("--n-pages", type=int, default=64,
                    help="pages per run pool (paged)")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=["f32", "int8", "int4"],
                    help="int8: quantized KV pages (GQA k/v or MLA latents) "
                         "with per-token scale pages; int4: packed GQA "
                         "pages, two nibbles per byte (paged only)")
    ap.add_argument("--weights-dtype", default="f32",
                    choices=["f32", "int8"],
                    help="int8: per-channel weight quantization at engine "
                         "build; decode-shaped matmuls run the fused "
                         "dequant GEMV (HALO's int8 CiD datapath)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache: shared-prompt KV pages are "
                         "reused copy-on-write (paged only)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of system prompt shared by every request "
                         "(exercises the prefix cache)")
    ap.add_argument("--executor", default="colocated",
                    choices=["colocated", "disaggregated"],
                    help="disaggregated: prefill/decode programs pinned to "
                         "separate device groups, KV migrates at the "
                         "prefill->decode handoff (HALO's 2.5D link)")
    ap.add_argument("--host-spill-pages", type=int, default=0,
                    help="host-memory KV tier size in pages per run: "
                         "preemption swaps pages out instead of "
                         "recomputing, prefix evictions demote to host "
                         "(paged only; 0 = off)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(request lifecycle spans + per-tick phase "
                         "events; open in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write the metrics registry as Prometheus-style "
                         "text exposition after the run")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="per-request TTFT deadline in ms; enables the "
                         "goodput / SLO-attainment report")
    ap.add_argument("--slo-tpot-ms", type=float, default=None,
                    help="per-request TPOT deadline in ms; enables the "
                         "goodput / SLO-attainment report")
    ap.add_argument("--traffic", action="store_true",
                    help="replace the fixed batch with a seeded arrival "
                         "trace replayed through the async front-end "
                         "(one client task per arrival); --requests is "
                         "ignored, the trace is --rate-rps x --duration-s")
    ap.add_argument("--rate-rps", type=float, default=None,
                    help="mean arrivals per second (traffic; default 4)")
    ap.add_argument("--duration-s", type=float, default=None,
                    help="trace horizon in seconds (traffic; default 2)")
    ap.add_argument("--arrival", default=None,
                    choices=["poisson", "onoff"],
                    help="arrival process (traffic; default poisson; "
                         "onoff = bursty exponential ON/OFF dwells)")
    ap.add_argument("--time-scale", type=float, default=None,
                    help="replay clock multiplier (traffic; default 1 = "
                         "real time; 0 = submit in trace order with no "
                         "waiting — deterministic)")
    ap.add_argument("--admission", action="store_true",
                    help="SLO-aware admission control: shed requests "
                         "whose projected TTFT busts their deadline "
                         "instead of letting the pool thrash")
    ap.add_argument("--admission-tick-cost-s", type=float, default=None,
                    help="fix the admission projection's seconds-per-tick "
                         "(deterministic decisions) instead of the live "
                         "tick-wall EMA")
    ap.add_argument("--max-pending-tokens", type=int, default=None,
                    help="structural backpressure cap on queued prefill "
                         "tokens: beyond it best-effort submits defer, "
                         "deadline-carrying ones shed")
    args = ap.parse_args(argv)
    if (args.draft or args.spec_k is not None) and not args.speculative:
        ap.error("--draft/--spec-k require --speculative")
    if args.speculative and not args.paged:
        ap.error("--speculative requires --paged (the draft/verify loop "
                 "runs over the paged arena)")
    if args.mixed_sampling and args.temperature <= 0.0:
        ap.error("--mixed-sampling needs --temperature > 0 (the stochastic "
                 "half samples at that temperature)")
    if args.kv_dtype != "f32" and not args.paged:
        ap.error("--kv-dtype int8/int4 requires --paged (quantized pages "
                 "live in the block-pool arena)")
    if args.host_spill_pages and not args.paged:
        ap.error("--host-spill-pages requires --paged (the spill tier "
                 "stores device pool pages)")
    if args.spec_k is None:
        args.spec_k = 4
    if not args.traffic and any(v is not None for v in (
            args.rate_rps, args.duration_s, args.arrival, args.time_scale)):
        ap.error("--rate-rps/--duration-s/--arrival/--time-scale require "
                 "--traffic")
    if not args.admission and (args.admission_tick_cost_s is not None
                               or args.max_pending_tokens is not None):
        ap.error("--admission-tick-cost-s/--max-pending-tokens require "
                 "--admission")
    if args.traffic and args.mixed_sampling:
        ap.error("--traffic and --mixed-sampling are exclusive (trace "
                 "requests share one SamplingParams)")
    if args.rate_rps is None:
        args.rate_rps = 4.0
    if args.duration_s is None:
        args.duration_s = 2.0
    if args.arrival is None:
        args.arrival = "poisson"
    if args.time_scale is None:
        args.time_scale = 1.0

    import jax
    from repro.configs.base import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.transformer import init_params
    from repro.serving.engine import ServeConfig, ServingEngine
    from repro.serving.metrics import SLO
    from repro.serving.sampling import SamplingParams
    from repro.serving.scheduler import AdmissionConfig, PhaseAwareConfig
    from repro.serving.tracing import Tracer

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        cfg = dataclasses.replace(cfg, dtype="float32")

    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    spec = None
    if args.speculative:
        from repro.serving.speculative import SpecConfig
        spec = SpecConfig(
            k=args.spec_k,
            drafter="model" if args.draft else "ngram",
            draft_arch=args.draft)
    admission = None
    if args.admission:
        admission = AdmissionConfig(
            tick_cost_s=args.admission_tick_cost_s,
            max_pending_tokens=args.max_pending_tokens)
    sc = ServeConfig(
        max_batch=args.max_batch, max_len=args.max_len,
        phase=PhaseAwareConfig(strategy=args.strategy,
                               max_decode_batch=args.max_batch,
                               prefill_chunk=args.prefill_chunk,
                               max_prefill_tokens=args.max_prefill_tokens),
        seed=args.seed,
        paged=args.paged, page_size=args.page_size, n_pages=args.n_pages,
        kv_dtype=args.kv_dtype, weights_dtype=args.weights_dtype,
        prefix_cache=args.prefix_cache,
        speculative=spec,
        executor=args.executor, host_spill_pages=args.host_spill_pages,
        admission=admission)
    # tracing is opt-in: enabled=False keeps the hot loop at one branch
    # per instrumentation point and the token streams bit-identical
    tracer = Tracer(enabled=bool(args.trace_out))
    slo = None
    if args.slo_ttft_ms is not None or args.slo_tpot_ms is not None:
        slo = SLO(ttft_ms=args.slo_ttft_ms, tpot_ms=args.slo_tpot_ms)
    engine = ServingEngine(cfg, params, sc, tracer=tracer)

    rng = np.random.default_rng(args.seed)
    stop = tuple(args.stop_token)
    mode_s = "mixed" if args.mixed_sampling else (
        "greedy" if args.temperature <= 0.0 else
        f"t={args.temperature}")
    t0 = time.monotonic()
    if args.traffic:
        import asyncio

        from repro.serving.frontend import AsyncEngine
        from repro.serving.traffic import (TenantSpec, TrafficConfig,
                                           replay, synthesize)
        if cfg.n_codebooks > 1:
            ap.error("--traffic supports single-codebook archs only "
                     "(trace prompts are 1-D token sequences)")
        if args.shared_prefix >= args.prompt_len:
            ap.error("--traffic needs --shared-prefix < --prompt-len "
                     "(a prompt needs at least one non-shared token)")
        # prompt/output lengths draw uniformly from [half, full] so the
        # trace exercises mixed shapes the way real traffic does
        p_lo = max(args.shared_prefix + 1, (args.prompt_len + 1) // 2)
        tenant = TenantSpec(
            name="cli", rate_rps=args.rate_rps, arrival=args.arrival,
            prompt_len=(min(p_lo, args.prompt_len), args.prompt_len),
            output_len=(max(1, (args.max_new + 1) // 2), args.max_new),
            shared_prefix_len=args.shared_prefix,
            n_prefixes=2 if args.shared_prefix else 1,
            slo=slo)
        events = synthesize(TrafficConfig(
            tenants=(tenant,), duration_s=args.duration_s,
            seed=args.seed, vocab_size=cfg.vocab_size))
        sp = SamplingParams(temperature=args.temperature,
                            top_k=args.top_k, top_p=args.top_p,
                            seed=args.seed, stop=stop)

        async def _go():
            async with AsyncEngine(engine) as fe:
                return await replay(fe, events,
                                    time_scale=args.time_scale,
                                    sampling=sp)

        report = asyncio.run(_go())
        wall = time.monotonic() - t0
        print(f"arch={cfg.name} strategy={args.strategy} "
              f"chunk={args.prefill_chunk} chunked={engine.chunked} "
              f"sampling={mode_s} "
              f"traffic[{args.arrival} rate={args.rate_rps:g}rps "
              f"dur={args.duration_s:g}s scale={args.time_scale:g}] "
              f"admission={'on' if admission else 'off'}")
        print(report.render())
    else:
        shared = rng.integers(0, cfg.vocab_size,
                              (min(args.shared_prefix, args.prompt_len),),
                              dtype=np.int32)
        for i in range(args.requests):
            L = args.prompt_len
            if cfg.n_codebooks > 1:
                prompt = rng.integers(0, cfg.vocab_size,
                                      (cfg.n_codebooks, L), dtype=np.int32)
            else:
                tail = rng.integers(0, cfg.vocab_size, (L - len(shared),),
                                    dtype=np.int32)
                prompt = np.concatenate([shared, tail])
            # per-request sampling: --temperature 0 IS greedy (no 1e-6
            # rewrite); --mixed-sampling keeps even-indexed requests
            # greedy
            temp = args.temperature
            if args.mixed_sampling and i % 2 == 0:
                temp = 0.0
            engine.submit(prompt, sampling=SamplingParams(
                temperature=temp, top_k=args.top_k, top_p=args.top_p,
                seed=args.seed + i, max_new_tokens=args.max_new,
                stop=stop),
                slo=slo)
        done = engine.run_until_drained()
        wall = time.monotonic() - t0

        # NaN-guarded latency stats: a request that never emitted a token
        # (max_new 0, abort, stop on submit) reports NaN ttft/tpot and is
        # excluded here; its finish_reason is surfaced below instead
        ttfts = [r.ttft for r in done if not np.isnan(r.ttft)]
        tpots = [r.tpot for r in done if not np.isnan(r.tpot)]
        reasons = {}
        for r in done:
            reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
        total_new = sum(len(r.generated) for r in done)
        reasons_s = " ".join(f"{k}={v}" for k, v in sorted(
            reasons.items(), key=lambda kv: str(kv[0])))
        print(f"arch={cfg.name} strategy={args.strategy} "
              f"chunk={args.prefill_chunk} chunked={engine.chunked} "
              f"sampling={mode_s} "
              f"requests={len(done)} tokens={total_new} wall={wall:.2f}s")
        ttft_p50 = np.median(ttfts) * 1e3 if ttfts else float("nan")
        tpot_p50 = np.median(tpots) * 1e3 if tpots else float("nan")
        print(f"TTFT p50={ttft_p50:.1f}ms  "
              f"TPOT p50={tpot_p50:.1f}ms  "
              f"throughput={total_new / wall:.1f} tok/s  "
              f"finish[{reasons_s}]")
    occ = engine.phase_occupancy()
    decode_ticks = [t.wall_s for t in engine.tick_log
                    if t.decode_reqs and not t.prefill_reqs]
    print(f"ticks={engine.n_ticks} "
          f"occupancy prefill={occ['prefill']:.2f} decode={occ['decode']:.2f} "
          f"mixed={occ['mixed']:.2f}  "
          f"decode-tick p50="
          f"{np.median(decode_ticks)*1e3 if decode_ticks else 0:.1f}ms  "
          f"host-transfers={engine.host_transfers}")
    kv = engine.kv_bytes()
    mode = (f"paged[{args.n_pages}x{args.page_size},{engine.pool.page_dtype}]"
            if args.paged else f"dense[max_len={args.max_len}]")
    print(f"kv={mode} weights={args.weights_dtype} "
          f"reserved={kv['reserved']/1e6:.2f}MB "
          f"peak-resident={kv['peak_resident']/1e6:.2f}MB "
          f"preemptions={engine.preemptions}")
    if args.prefix_cache:
        ps = engine.prefix_stats()
        print(f"prefix-cache hit-rate={ps['hit_rate']:.2f} "
              f"tokens-from-cache={ps['hit_tokens']:.0f} "
              f"prefill-executed={ps['prefill_tokens_executed']:.0f} "
              f"cow-copies={ps['cow_copies']:.0f} "
              f"evicted-pages={ps['cache_evicted_pages']:.0f}")
    if args.speculative:
        ss = engine.spec_stats()
        drafter = args.draft or "ngram"
        print(f"speculative drafter={drafter} k={args.spec_k} "
              f"windows={ss['windows']:.0f} "
              f"acceptance={ss['acceptance_rate']:.2f} "
              f"tokens/tick={ss['tokens_per_tick']:.2f}")
    if args.executor == "disaggregated":
        xs = engine.executor.stats()
        print(f"disaggregated prefill-devices={xs['prefill_devices']} "
              f"decode-devices={xs['decode_devices']} "
              f"migrated-pages={xs['migrated_pages']} "
              f"migrated={xs['migrated_bytes']/1e6:.2f}MB "
              f"handoff-batches={xs['migration_batches']}")
    if args.host_spill_pages:
        c = engine.counts()
        print(f"host-tier pages={args.host_spill_pages} "
              f"swap-out={c['swap_out_bytes']/1e6:.2f}MB "
              f"swap-in={c['swap_in_bytes']/1e6:.2f}MB "
              f"swap-resumes={c['swap_resumes']} "
              f"recompute-resumes={c['recompute_preemptions']} "
              f"resident-pages={c['host_resident_pages']}")
    if slo is not None:
        g = engine.goodput()
        slo_s = " ".join(
            f"{k}={v}" for k, v in (("ttft_ms", args.slo_ttft_ms),
                                    ("tpot_ms", args.slo_tpot_ms))
            if v is not None)
        print(f"slo[{slo_s}] attained={g['slo_attained']}/{g['slo_total']} "
              f"goodput={g['goodput']:.2f} "
              f"ttft-violations={g['ttft_violations']} "
              f"tpot-violations={g['tpot_violations']}")
    if args.trace_out:
        engine.tracer.write(args.trace_out)
        print(f"trace: {len(engine.tracer.events())} events -> "
              f"{args.trace_out}")
    if args.metrics:
        snap = engine.metrics_snapshot()  # refreshes gauges before render
        with open(args.metrics, "w") as f:
            f.write(engine.metrics.render())
        print(f"metrics: {len(snap['counters'])} counters "
              f"{len(snap['gauges'])} gauges "
              f"{len(snap['histograms'])} histograms -> {args.metrics}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
