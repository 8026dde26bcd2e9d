#!/usr/bin/env python3
"""Chip smoke: serve qwen3-1.7b at published width on one TPU chip.

    python3 chip_smoke.py                  # one chip: the served path
    python3 chip_smoke.py --chips 4        # four chips: disaggregated vs colocated
    python3 chip_smoke.py --cpu-rehearsal  # CPU, reduced size; prints no result

One chip.  Builds the full-width ``qwen3-1.7b`` in bf16 with seeded
random weights and a 2,048-page x 16-token paged KV pool with the prefix
cache on, and replays five greedy requests through ``AsyncEngine`` (the
normal entry point: ``ServingEngine``, the scheduler, the paged pool,
packed prefill, paged decode, the executor).  Prompts run 600-3,000
tokens; the 3,000-token one spans two 2,048-token chunks, so the prefill
kernel walks history pages; two requests share a 1,024-token prefix, the
second submitted once the first is prefilled, so it hits the cache.  Each
request generates 32 tokens.  Then:

* every served token is checked against a plain float32 ``jax.numpy``
  forward of the same weights (``models/reference.py``) by teacher
  forcing: the token's reference logit must lie within ``LOGIT_TOL`` of
  the reference maximum at its position (logits, not tokens: random
  weights make near-ties);
* the same requests are replayed a second time (prefix cache flushed
  first, so the second pass plans the same ticks) — it must add zero
  compiles (``Executor.compile_count``) and the same streams;
* the packed-prefill and paged-decode kernel routes must both be taken,
  and no jnp fallback.

Four chips (``--chips 4``).  Serves the same requests once colocated on
one chip and once with ``executor="disaggregated"`` (prefill programs on
device 0 of group 0-1, decode programs on device 2 of group 2-3), checks
the streams are identical, and prints where each phase's outputs land and
the bytes the placement moved per tick.  No other phase runs.

The last line of a passing chip run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase exits non-zero.  Without a TPU the script exits
non-zero before doing anything; ``--cpu-rehearsal`` runs the same phases
at ``reduced()`` size on the CPU (Pallas in interpret mode) and never
prints that line.  The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` in the
checkout.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import List, Sequence, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

# A served token's float32-reference logit may sit at most this far below
# the reference maximum at its position.  The served model runs in bf16
# (weights, activations, KV pages); over 28 layers that moves logits by a
# few hundredths, while a token picked by broken attention sits several
# logits below the maximum (the script prints both scales).
LOGIT_TOL = 0.25
ARCH = "qwen3-1.7b"
SEED = 0


@dataclass(frozen=True)
class Setup:
    reduced: bool
    n_pages: int
    prefill_chunk: int
    shared: int                     # shared-prefix tokens
    first: Tuple[int, ...]          # prompt lengths; [0] holds the prefix
    second: Tuple[int, ...]         # after first[0] is prefilled; [0] shares
    ref_len: int                    # reference sequences pad to this
    page_size: int = 16
    max_new: int = 32
    max_batch: int = 8


FULL = Setup(reduced=False, n_pages=2048, prefill_chunk=2048, shared=1024,
             first=(1500, 3000, 600), second=(1800, 900), ref_len=3072)
REHEARSAL = Setup(reduced=True, n_pages=128, prefill_chunk=128, shared=64,
                  first=(100, 300, 40), second=(120, 60), ref_len=384)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def make_prompts(setup: Setup, vocab: int):
    rng = np.random.default_rng(SEED)
    shared = rng.integers(0, vocab, setup.shared, dtype=np.int32)

    def wave(lengths):
        out = []
        for i, n in enumerate(lengths):
            head = shared if i == 0 else np.zeros(0, np.int32)
            tail = rng.integers(0, vocab, n - len(head), dtype=np.int32)
            out.append(np.concatenate([head, tail]))
        return out
    return wave(setup.first), wave(setup.second)


def build_engine(cfg, params, setup: Setup, executor: str):
    from repro.serving.engine import ServeConfig, ServingEngine
    from repro.serving.scheduler import PhaseAwareConfig
    sc = ServeConfig(
        max_batch=setup.max_batch, max_len=setup.ref_len,
        phase=PhaseAwareConfig(max_decode_batch=setup.max_batch,
                               prefill_chunk=setup.prefill_chunk,
                               max_prefill_tokens=setup.prefill_chunk),
        seed=SEED, paged=True, page_size=setup.page_size,
        n_pages=setup.n_pages, prefix_cache=True, executor=executor)
    return ServingEngine(cfg, params, sc)


def serve(engine, first, second, max_new) -> List[List[int]]:
    """One pass through AsyncEngine; returns the token streams in
    submission order.  The second wave is posted once the first request
    (the prefix holder) has its first token, i.e. its prompt pages are in
    the prefix cache."""
    from repro.serving.frontend import AsyncEngine
    from repro.serving.sampling import SamplingParams
    sp = SamplingParams(temperature=0.0, max_new_tokens=max_new)

    async def go():
        async with AsyncEngine(engine) as fe:
            hs = [await fe.submit(p, sampling=sp) for p in first]
            await hs[0].__anext__()
            hs += [await fe.submit(p, sampling=sp) for p in second]
            await fe.drain()
            return hs

    hs = asyncio.run(go())
    for h in hs:
        if h.finish_reason != "length" or len(h.token_ids()) != max_new:
            fail(f"request {h.req_id} ended {h.finish_reason!r} after "
                 f"{len(h.token_ids())} tokens (want 'length', {max_new})")
    return [[int(t) for t in h.token_ids()] for h in hs]


def compile_seconds():
    """Running total of trace + lower + backend-compile seconds."""
    import jax
    total = [0.0]

    def listen(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            total[0] += duration
    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def check_reference(cfg, params, setup, prompts, streams) -> None:
    """Teacher-forced float32 reference: every served token's reference
    logit within LOGIT_TOL of the reference maximum at its position."""
    import jax
    from repro.models.reference import reference_logits
    ref = jax.jit(lambda p, t, o: reference_logits(p, cfg, t, o))
    worst, spread, n = 0.0, [], 0
    t0 = time.monotonic()
    for prompt, gen in zip(prompts, streams):
        seq = np.zeros(setup.ref_len, np.int32)
        full = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
        seq[:len(full)] = full
        out = np.arange(len(prompt) - 1, len(prompt) - 1 + len(gen))
        lg = np.asarray(ref(params, seq, out))                   # [n, V]
        if not np.isfinite(lg).all():
            fail("non-finite reference logits")
        gap = lg.max(-1) - lg[np.arange(len(gen)), gen]
        worst = max(worst, float(gap.max()))
        spread.append(float(np.mean(lg.max(-1) - lg.mean(-1))))
        n += len(gen)
        bad = np.nonzero(gap > LOGIT_TOL)[0]
        if len(bad):
            fail(f"served token {int(bad[0])} of a {len(prompt)}-token "
                 f"prompt sits {float(gap[bad[0]]):.4f} below the f32 "
                 f"reference max (tolerance {LOGIT_TOL})")
    print(f"reference: {n} served tokens checked against the float32 "
          f"forward (teacher forcing) in {time.monotonic() - t0:.2f}s; "
          f"max gap {worst:.6f} <= tol {LOGIT_TOL}; a mean token sits "
          f"{np.mean(spread):.3f} below the max")


def device_line(devs) -> str:
    d = devs[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}})


def memory_line(dev) -> str:
    ms = dev.memory_stats() or {}
    peak, limit = ms.get("peak_bytes_in_use"), ms.get("bytes_limit")
    return (f"device memory: peak_bytes_in_use={peak} bytes_limit={limit}"
            if peak is not None else "device memory: not reported")


def one_chip(cfg, params, setup: Setup, on_tpu: bool) -> None:
    from repro.models.attention import (attention_route_counts,
                                        reset_attention_route_counts)
    import jax
    first, second = make_prompts(setup, cfg.vocab_size)
    prompts = first + second
    engine = build_engine(cfg, params, setup, "colocated")
    kv = engine.kv_bytes()
    print(f"pool: {setup.n_pages} pages x {setup.page_size} tokens, page "
          f"dtype {engine.pool.page_dtype} (kv_dtype="
          f"{engine.sc.kv_dtype!r}), {kv['reserved']} bytes; packed-stream "
          f"alignment {engine._pack_align}")
    print(f"requests: prompts {[len(p) for p in prompts]} tokens, "
          f"{setup.shared}-token shared prefix on requests 0 and "
          f"{len(first)}, {setup.max_new} new tokens each, greedy")
    comp = compile_seconds()

    reset_attention_route_counts()
    c0, t0 = comp(), time.monotonic()
    streams = serve(engine, first, second, setup.max_new)
    wall1, comp1 = time.monotonic() - t0, comp() - c0
    routes = attention_route_counts()
    hits1 = engine.prefix_stats()["hit_tokens"]
    compiles1 = engine.compile_count
    print(f"pass 1 (cold): {wall1:.3f}s wall, {comp1:.3f}s of it "
          f"tracing+compiling, {compiles1} phase-program compiles, "
          f"{sum(map(len, streams))} tokens generated, prefix-cache hit "
          f"tokens {hits1:.0f}")
    print(f"kernel routes (traced calls): {json.dumps(routes, sort_keys=True)}")
    if hits1 < setup.shared:
        fail(f"prefix cache served {hits1:.0f} tokens, want >= "
             f"{setup.shared}")

    engine.prefix.flush(engine.pool)
    reset_attention_route_counts()
    c0, t0 = comp(), time.monotonic()
    again = serve(engine, first, second, setup.max_new)
    wall2, comp2 = time.monotonic() - t0, comp() - c0
    new = engine.compile_count - compiles1
    hits2 = engine.prefix_stats()["hit_tokens"] - hits1
    print(f"pass 2 (warm, timed): {wall2:.3f}s wall, {comp2:.3f}s "
          f"tracing+compiling, {new} new compiles, "
          f"{sum(map(len, again))} tokens generated, prefix-cache hit "
          f"tokens {hits2:.0f}, retraced kernel routes "
          f"{sum(attention_route_counts().values())}")
    print(memory_line(jax.devices()[0]))
    if new:
        fail(f"the second pass compiled {new} new phase programs")
    if again != streams:
        fail("the second pass served different streams")
    if on_tpu:
        for name in ("packed_prefill.kernel", "paged_decode.kernel"):
            if routes.get(name, 0) <= 0:
                fail(f"route {name} never taken")
        for name in ("packed_prefill.jnp", "paged_decode.jnp"):
            if routes.get(name, 0):
                fail(f"route {name} taken {routes[name]} times on the TPU")
    check_reference(cfg, params, setup, prompts, streams)
    print(memory_line(jax.devices()[0]))


def four_chips(cfg, params, setup: Setup) -> None:
    import jax
    first, second = make_prompts(setup, cfg.vocab_size)
    base = build_engine(cfg, params, setup, "colocated")
    t0 = time.monotonic()
    want = serve(base, first, second, setup.max_new)
    print(f"colocated (device {jax.devices()[0].id}): {len(want)} streams, "
          f"{sum(map(len, want))} tokens, {time.monotonic() - t0:.3f}s")
    del base

    engine = build_engine(cfg, params, setup, "disaggregated")
    ex = engine.executor
    landed = {}
    program = ex.program

    def traced_program(group, kind):
        fn = program(group, kind)

        def run(*args):
            out = fn(*args)
            landed.setdefault(kind, set()).update(
                d.id for leaf in jax.tree.leaves(out) for d in leaf.devices())
            return out
        return run
    ex.program = traced_program
    t0 = time.monotonic()
    got = serve(engine, first, second, setup.max_new)
    wall = time.monotonic() - t0
    xs = ex.stats()
    print(f"disaggregated: prefill group {[d.id for d in ex.prefill_devices]}"
          f", decode group {[d.id for d in ex.decode_devices]}; "
          f"{sum(map(len, got))} tokens, {wall:.3f}s")
    for kind, ids in sorted(landed.items()):
        print(f"  {kind} outputs land on devices {sorted(ids)}")
    print(f"  placement moved {xs['moved_bytes']} bytes over "
          f"{engine.n_ticks} ticks: {xs['moved_bytes'] / engine.n_ticks:.0f}"
          f" per tick on average, {xs['max_tick_moved_bytes']} at most; "
          f"handoff accounting {xs['migrated_bytes']} bytes in "
          f"{xs['migration_batches']} batches")
    if got != want:
        bad = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
        fail(f"disaggregated streams differ from colocated: requests {bad}")
    print("streams identical: disaggregated == colocated "
          f"({len(got)} requests)")
    for kind in ("packed_paged", "decode_paged"):
        if kind not in landed:
            fail(f"no {kind} program ran")


def main(argv: Sequence[str] = ()) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run every phase on the CPU at reduced() size; "
                         "prints no result line")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        fail(f"no TPU: JAX sees {devs[0].platform} devices (use "
             "--cpu-rehearsal for the reduced CPU run)")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
             f"{len(devs)}")
    setup = REHEARSAL if args.cpu_rehearsal else FULL

    from repro.configs.base import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.transformer import init_params

    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    print(f"compile cache: {use_compile_cache()}")
    cfg = get_config(ARCH)
    if setup.reduced:
        cfg = cfg.reduced()
    t0 = time.monotonic()
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
          f"H={cfg.n_heads} Hkv={cfg.n_kv_heads} D={cfg.d_head} "
          f"ff={cfg.d_ff} V={cfg.vocab_size} {cfg.dtype}; {n} params, "
          f"seeded random weights ({time.monotonic() - t0:.3f}s)")

    if args.chips == 4:
        four_chips(cfg, params, setup)
    else:
        one_chip(cfg, params, setup, on_tpu)
    if args.cpu_rehearsal:
        print("cpu rehearsal passed (reduced size; not a chip result)")
        return 0
    print(device_line(devs))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
