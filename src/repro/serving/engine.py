"""Serving engine: continuous batching that EXECUTES the phase scheduler's
plan — chunked prefill, arena-direct KV writes, device-side sampling.

The engine owns a small table of jitted programs, keyed by (worker group,
phase kind).  On a production mesh the two groups are distinct worker
pools running differently-sharded executables (HALO: CiM for prefill
GEMMs, CiD for decode GEMVs); here they are separate jit instances and
the strategy (``halo`` / ``cent`` / ``attacc``) decides which group's
program serves each phase — exactly what ``TickPlan`` carries.

One engine tick = one ``PhaseScheduler.plan_tick`` executed verbatim:

  1. admit       — waiting requests claim free decode slots;
  2. prefill     — the plan's (request, n_tokens) chunks are packed into
                   ONE padded batch and run through the prefill-group
                   program, which writes K/V directly into the decode
                   arena at each request's slot and offset (the HALO
                   CiM -> CiD handoff, formerly a host-side splice loop).
                   Long prompts therefore prefill across several ticks,
                   interleaved with decode — the TTFT/TPOT trade-off;
  3. decode      — one batched token step for every DECODING slot, with
                   greedy / temperature / top-k sampling INSIDE the jitted
                   program: one [B]-shaped host transfer per tick instead
                   of a per-slot ``int(jnp.argmax(...))`` sync.

SSM / shared-attention plans cannot resume a recurrent state mid-prompt,
so their prefill falls back to whole-prompt — still a single jitted
program that splices the state into the arena on device
(``prefill_into_arena``); the scheduler plans those prompts as atomic
chunks.  Per-request TTFT/TPOT and a per-tick ``tick_log`` (phase
occupancy, groups, wall time) feed benchmarks/serving_bench.py.

PAGED MODE (``ServeConfig(paged=True)``): the dense ``[L, B, max_len]``
arena is replaced by the block-pool cache of ``serving/kv_pool.py`` —
``n_pages`` pages of ``page_size`` tokens per attention run, mapped per
slot through block tables.  Capacity becomes a POOL property: ``submit``
accepts any prompt the pool can hold (one 16k request or eight 2k ones),
the scheduler admits prefill tokens only while free pages cover them
(decode's one-token growth is reserved first), and when the pool
exhausts mid-decode the YOUNGEST page-holding request is preempted —
its pages return to the pool and it re-queues as WAITING with its
generated tokens folded into the prompt (recompute-on-resume), so the
oldest request always finishes.  Decode attention routes through the
Pallas paged flash-decode kernel; ``kv_dtype="int8"`` stores GQA pages
AND MLA latent pages int8 with per-token f32 scales in parallel page
arrays; ``kv_dtype="int4"`` packs GQA pages two nibbles per byte
(~4x resident-KV reduction; MLA latents stay int8 — see kv_pool.py).

PREFIX CACHE (``ServeConfig(prefix_cache=True)``, requires paged): a
radix index over page-aligned token-block hashes
(``serving/prefix_cache.py``) maps each new prompt's longest cached
prefix to physical pages.  Admission attaches those pages to the slot's
block tables (refcounted sharing — no allocation, no compute) and starts
``prefill_pos`` past the matched tokens; completed prefills publish
their prompt pages back.  Pages are copy-on-write: before any program
writes a shared page (a ring wrap, a shared partially-filled tail) the
engine moves the slot to a private copy (``KVPool.ensure_writable`` +
one device page copy).  Cached pages are reclaimable capacity — evicted
LRU-first whenever live work needs pages, BEFORE any live request is
preempted.  Greedy token streams are bit-identical with the cache on or
off; only the prefill work executed changes.

SPECULATIVE DECODING (``ServeConfig(speculative=SpecConfig(...))``,
requires paged): every decode tick, a drafter proposes up to k tokens
per decoding request — the model-free n-gram prompt-lookup drafter by
default, or a small draft model with its own paged KV pool — and ONE
verify window of the target model (a k+1-token prefill-shaped chunk on
the CiM-analogue verify group, logits at every window position) accepts
the longest agreeing prefix and emits one correction/bonus token on top.
Rejected tokens' KV rolls back via ``KVPool.truncate`` (shared /
prefix-cache-pinned pages survive; COW already privatized the writer).
Greedy streams are bit-identical with speculation on or off; only the
number of ticks changes.  See serving/speculative.py and
docs/serving.md §Speculative decoding.

THE REQUEST API: the unit of the serving interface is the REQUEST, not
the batch run.  ``submit(prompt, sampling=SamplingParams(...))`` carries
per-request temperature (0 = greedy) / top-k / top-p / seed / token
budget / stop conditions; the jitted phase programs take per-slot ``[B]``
parameter arrays so one compiled program serves a batch mixing greedy
and stochastic requests (greedy rows bit-identical to an all-greedy
run), still one host transfer per tick.  ``step()`` returns incremental
``RequestOutput``s (new tokens, cumulative counts, finish reason:
length/eos/stop/abort) and ``stream()`` / ``generate()`` are the
streaming/batch facades over the tick loop.  ``abort(req_id)`` cancels a
request at ANY lifecycle stage — WAITING, PREFILLING, or DECODING
(speculative verify state included) — releasing its pages, prefix-cache
attachments, and draft-pool state.  The old engine-wide ``ServeConfig``
sampling fields survive as deprecated per-request defaults.

THREE LAYERS (PR 8): this module is the host-only ENGINE CORE — request
lifecycle, tick planning, batch packing, and page/tier accounting.  The
jitted program table, compile counting, and device placement live in the
EXECUTOR layer (``serving/executor.py``: ``ColocatedExecutor`` is
today's single-group behavior, ``DisaggregatedExecutor`` pins prefill
and decode programs to separate device groups and accounts KV-page
migration at the prefill -> decode handoff — the 2.5D-link analogue).
The KV TIERS live in ``serving/kv_pool.py``: the device ``PagePool``
plus an optional host-memory spill tier (``ServeConfig(host_spill_pages
> 0)``) that turns preemption into page SWAP instead of
recompute-on-resume and lets evicted prefix-cache blocks demote to host
and promote on re-hit.  The ``Request``/``RequestOutput``/``TickRecord``
/``ServeConfig`` dataclasses moved to ``serving/types.py``; they are
re-exported here so existing ``from repro.serving.engine import ...``
callers keep working.

This is a single-host engine; launch/serve.py instantiates it either on
the host CPU (examples, tests) or under the production mesh with the
decode shardings from distributed/sharding.py.
"""

from __future__ import annotations

import math
import time
import warnings
from collections import deque
from dataclasses import dataclass, replace
from typing import (
    Any, Callable, Deque, Dict, Iterator, List, Optional, Sequence, Tuple,
    Union,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.attention import packed_kernel_align
from repro.models.transformer import (
    forward,
    forward_chunk,
    forward_chunk_packed,
    init_cache,
    prefill_into_arena,
    supports_chunked_prefill,
    supports_paged,
)
from repro.serving.executor import make_executor
from repro.serving.kv_pool import HostTier, KVPool
from repro.serving.metrics import (
    SLO,
    MetricsRegistry,
    counter_attr,
    gauge_attr,
    slo_attainment,
)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.tracing import Tracer
from repro.serving.sampling import (
    SamplingParams,
    row_keys,
    sample_tokens_rows,
    verify_draft,
    verify_draft_rows,
)
from repro.serving.scheduler import (
    AdmissionController,
    PhaseScheduler,
    TickPlan,
    bucket_pow2 as _bucket,
    pack_chunks,
)
from repro.serving.speculative import build_drafter
from repro.serving.types import (                             # noqa: F401
    Request,
    RequestOutput,
    RequestState,
    ServeConfig,
    TickRecord,
)

# back-compat: these names were defined here before serving/types.py split
# them out, and external code imports them from this module
__all__ = [
    "Request", "RequestOutput", "RequestState", "ServeConfig",
    "ServingEngine", "TickRecord",
]


@dataclass
class _Call:
    """One phase-program call, prepared: every device argument built, so
    launching it is the jitted call alone.  The program takes
    ``(params, *head, cache, *tail, all_greedy)``."""
    kind: str
    shape: Tuple[int, ...]            # the compile key's shape
    head: Tuple[Any, ...]             # device arguments before the cache
    tail: Tuple[Any, ...]             # ... and after it
    all_greedy: bool


@dataclass
class _SwapHandle:
    """Where a swapped-out request's KV lives while it waits in the queue
    (``Request.swap``): per-run host-tier page lists in block-table row
    order, plus the slot state a swap-in must restore verbatim."""
    length: int                       # logical tokens the pages back
    pages: List[List[int]]            # per run: host page ids, row order
    prefill_pos: int
    cached_tokens: int
    pos: int                          # slot_pos at swap-out (-1 mid-prefill)
    state: RequestState               # PREFILLING or DECODING


class ServingEngine:
    # Lifetime counters live in the METRICS REGISTRY (serving/metrics.py):
    # each attribute below is a view over one named registry cell, so the
    # legacy dict APIs (counts() / spec_stats() / prefix_stats()) and
    # MetricsRegistry.snapshot() / render() can never disagree.  The
    # executor, HostTier, and PrefixCache share the same registry (the
    # engine passes it at construction), covering their counters too.
    host_transfers = counter_attr("serving_host_transfers_total")
    aborted = counter_attr("serving_aborted_total")
    admission_shed = counter_attr("serving_admission_shed_total")
    admission_deferred = counter_attr("serving_admission_deferred_total")
    preemptions = counter_attr("serving_preemptions_total")
    swap_outs = counter_attr("serving_swap_outs_total")
    swap_resumes = counter_attr("serving_swap_resumes_total")
    recompute_preemptions = counter_attr("serving_recompute_preemptions_total")
    prefill_tokens_executed = counter_attr("serving_prefill_tokens_total")
    cow_copies = counter_attr("serving_cow_copies_total")
    cache_evicted_pages = counter_attr("serving_cache_evicted_pages_total")
    spec_windows = counter_attr("serving_spec_windows_total")
    spec_drafted = counter_attr("serving_spec_drafted_total")
    spec_accepted = counter_attr("serving_spec_accepted_total")
    decode_tokens_emitted = counter_attr("serving_decode_tokens_total")
    decode_slot_ticks = counter_attr("serving_decode_slot_ticks_total")
    prefill_launches = counter_attr("serving_prefill_launches_total")
    prefill_rows_executed = counter_attr("serving_prefill_rows_total")
    prefill_history_pages = counter_attr(
        "serving_prefill_history_pages_total")
    kv_resident_peak = gauge_attr("serving_kv_resident_peak_bytes")
    _n_ticks = counter_attr("serving_ticks_total")
    _n_prefill_ticks = counter_attr("serving_prefill_ticks_total")
    _n_decode_ticks = counter_attr("serving_decode_ticks_total")
    _n_mixed_ticks = counter_attr("serving_mixed_ticks_total")

    # the counters step() diffs to fill each TickRecord's per-tick fields
    _TICK_DELTA_KEYS = (
        "serving_preemptions_total",
        "serving_spec_drafted_total",
        "serving_spec_accepted_total",
        "serving_swap_out_bytes_total",
        "serving_swap_in_bytes_total",
    )

    def __init__(self, cfg: ModelConfig, params: Any, sc: ServeConfig,
                 *, mesh=None, metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        # the registry IS the engine's counter state — construct it before
        # anything that counts.  Pass a DEDICATED registry per engine (the
        # per-tick deltas assume nobody else moves these counters); pass a
        # Tracer(enabled=True) to record the Chrome trace timeline
        # (serving/tracing.py) — tracing is OFF by default and leaves
        # greedy token streams bit-identical when on.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self.cfg = cfg
        if sc.weights_dtype not in ("f32", "int8"):
            raise ValueError(f"weights_dtype={sc.weights_dtype!r} "
                             "(expected 'f32' or 'int8')")
        if sc.weights_dtype == "int8":
            # serving quantizes EVERY matmul leaf (min_size=0): HALO's CiD
            # computes int8 end to end, and the decode GEMV kernel reads
            # the int8 bytes directly (models/layers.matmul routing)
            from repro.serving.quantized_weights import quantize_params
            params = quantize_params(params, min_size=0)
        self.params = params
        self.sc = sc
        self.mesh = mesh
        if sc.legacy_sampling_overridden():
            warnings.warn(
                "ServeConfig's engine-wide sampling fields (greedy/"
                "temperature/top_k/top_p) are deprecated: pass per-request "
                "SamplingParams via submit(..., sampling=...).  The values "
                "given are used as the default SamplingParams for submits "
                "that pass none.", DeprecationWarning, stacklevel=2)
        self._default_sampling = sc.default_sampling()
        self.scheduler = PhaseScheduler(sc.phase)
        B, S = sc.max_batch, sc.max_len
        self.paged = sc.paged
        if sc.paged:
            if not supports_paged(cfg):
                raise ValueError(
                    f"{cfg.name}: paged serving needs an all-attention plan "
                    "(SSM / shared-attention runs keep the dense arena)")
            if sc.phase.prefill_chunk <= 0:
                raise ValueError("paged serving requires chunked prefill "
                                 "(prefill_chunk > 0)")
            self.pool: Optional[KVPool] = KVPool(
                cfg, n_slots=B, n_pages=sc.n_pages, page_size=sc.page_size,
                kv_dtype=sc.kv_dtype)
            self.cache = self.pool.caches
        else:
            if sc.kv_dtype != "f32":
                raise ValueError(
                    f"kv_dtype={sc.kv_dtype!r} requires paged=True (the "
                    "dense engine stores the arena in the model dtype)")
            if sc.prefix_cache:
                raise ValueError("prefix_cache=True requires paged=True "
                                 "(prefix reuse shares physical pages "
                                 "through the block tables)")
            self.pool = None
            self.cache = init_cache(cfg, B, S)
        # host-memory spill tier (tiered KV): preemption swaps pages out
        # instead of recomputing, prefix-cache evictions demote to host
        if sc.host_spill_pages < 0:
            raise ValueError(f"host_spill_pages={sc.host_spill_pages} < 0")
        if sc.host_spill_pages and not sc.paged:
            raise ValueError("host_spill_pages > 0 requires paged=True "
                             "(the spill tier stores device pool pages)")
        self.host_tier: Optional[HostTier] = (
            HostTier(self.pool, sc.host_spill_pages, metrics=self.metrics)
            if sc.paged and sc.host_spill_pages > 0 else None)
        self.prefix: Optional[PrefixCache] = None
        if sc.paged and sc.prefix_cache:
            tiered = self.host_tier is not None
            self.prefix = PrefixCache(
                sc.page_size, self.pool.shareable_capacity(),
                demote=self._demote_pages if tiered else None,
                promote=self._promote_pages if tiered else None,
                discard=self._discard_host_pages if tiered else None,
                metrics=self.metrics)
        self.spec = sc.speculative
        self.drafter = None
        if self.spec is not None:
            if not sc.paged:
                raise ValueError(
                    "speculative decoding requires paged=True (the "
                    "draft/verify loop writes and rolls back through the "
                    "paged arena's block tables)")
            if cfg.n_codebooks > 1:
                raise ValueError("speculative decoding does not support "
                                 "multi-codebook heads")
            self.drafter = build_drafter(self.spec, cfg, n_slots=B,
                                         n_pages=sc.n_pages,
                                         page_size=sc.page_size)
            # rings bound rollback: a draft written at position p >= R
            # would overwrite live history at p - R that a rejection
            # cannot restore — speculation stops there (see
            # KVPool.rollback_bound) and decode falls back to one token
            self._rollback_bound = self.pool.rollback_bound()
        self.slot_pos = np.full((B,), -1, np.int64)     # next write position
        self.slot_req: List[Optional[Request]] = [None] * B
        self.queue: List[Request] = []
        self.done: List[Request] = []
        # admission control (ServeConfig.admission): submit() consults the
        # controller against the live backlog — shed requests retire
        # immediately (finish_reason "shed"), deferred ones park here and
        # are reconsidered at every step() until the backlog has room
        self.admission: Optional[AdmissionController] = (
            AdmissionController(sc.admission, sc.phase)
            if sc.admission is not None else None)
        self.deferred: List[Request] = []
        # live seconds-per-tick estimate (EMA over TickRecord.wall_s) the
        # controller projects TTFT with when no fixed tick_cost_s is set
        self._tick_wall_ema = 0.0
        self._tick_wall_n = 0
        # bounded record of recent ticks (a long-lived engine must not grow
        # per-tick state without bound); occupancy uses running counters
        self.tick_log: Deque[TickRecord] = deque(maxlen=65_536)
        # baseline for TickRecord's registry deltas, carried ACROSS ticks
        # (see step()): counter movement between ticks lands in the next
        # record, so the tick_log sums conserve the lifetime totals
        self._tick_delta_base = self.metrics.values(self._TICK_DELTA_KEYS)
        self._n_ticks = 0
        self._n_prefill_ticks = 0
        self._n_decode_ticks = 0
        self._n_mixed_ticks = 0
        self.host_transfers = 0          # device->host syncs (see _to_host)
        self.aborted = 0                 # requests cancelled via abort()
        self.admission_shed = 0          # submits refused by admission
        self.admission_deferred = 0      # submits parked by the backlog cap
        self.preemptions = 0             # lifetime pool evictions (paged)
        self.kv_resident_peak = 0        # peak allocated KV bytes (paged)
        # tiered-KV counters: how preemptions resumed (swap vs recompute)
        self.swap_outs = 0               # victims whose pages went to host
        self.swap_resumes = 0            # swapped requests resumed from host
        self.recompute_preemptions = 0   # fell back to recompute-on-resume
        self.prefill_tokens_executed = 0  # chunk tokens actually computed
        self.cow_copies = 0              # device page copies (COW)
        self.cache_evicted_pages = 0     # pages reclaimed from the cache
        # speculative decoding counters (spec_stats)
        self.spec_windows = 0            # verify windows executed
        self.spec_drafted = 0            # draft tokens verified
        self.spec_accepted = 0           # draft tokens accepted
        self.decode_tokens_emitted = 0   # tokens from decode/verify phases
        self.decode_slot_ticks = 0       # (request, tick) decode occupancies
        # the dense arena pins its full footprint up front; computed here
        # because the cache arrays are donated (buffers move every call).
        # The per-token/per-slot split prices the dense prefill->decode
        # handoff for the disaggregated executor: seq-axis leaves
        # ([L, B, S, ...]) move length x token-bytes, recurrent-state
        # leaves move their whole per-slot footprint once
        self._dense_kv_bytes = 0
        self._dense_token_bytes = 0
        self._dense_state_bytes = 0
        if not sc.paged:
            for c in self.cache:
                for leaf in c.values():
                    self._dense_kv_bytes += leaf.nbytes
                    if (leaf.ndim >= 3 and leaf.shape[1] == B
                            and leaf.shape[2] == S):
                        self._dense_token_bytes += leaf.nbytes // (B * S)
                    else:
                        self._dense_state_bytes += leaf.nbytes // B
        self._next_id = 0
        self.chunked = (supports_chunked_prefill(cfg)
                        and sc.phase.prefill_chunk > 0)
        # packed prefill needs the chunked attention path (arena-direct
        # writes at (slot, offset)) and a flat single-codebook stream
        self._packed = (sc.packed_prefill and self.chunked
                        and cfg.n_codebooks <= 1)
        # segment alignment of the packed stream: where the Pallas packed
        # prefill kernel runs on the page pool (TPU, float KV, no softcap)
        # it is the kernel's query tile, so the default paged path reaches
        # the kernel.  The dense arena keeps the user's alignment (the
        # kernel serves it only where that is a multiple of the tile).
        tile = packed_kernel_align(
            cfg.attn.logit_softcap, quantized=sc.kv_dtype != "f32") \
            if sc.paged and not cfg.mla.enabled else 0
        self._pack_align = max(sc.phase.pack_align, tile)
        self.prefill_launches = 0        # prefill phase-program calls
        self.prefill_rows_executed = 0   # token rows computed (incl. pad)
        # live history pages the packed paged prefill walks: per segment
        # and attention run, the pages of min(offset, ring) cached tokens
        self.prefill_history_pages = 0
        # the EXECUTOR owns the jitted program table, compile accounting
        # and device placement (serving/executor.py); the engine stays
        # host-only and reaches it through _program/_note_compile below
        self.executor = make_executor(sc.executor, {
            "chunk": self._prefill_chunk_impl,
            "whole": self._prefill_whole_impl,
            "decode": self._decode_impl,
            "chunk_paged": self._prefill_chunk_paged_impl,
            "decode_paged": self._decode_paged_impl,
            "packed": self._prefill_packed_impl,
            "packed_paged": self._prefill_packed_paged_impl,
            "verify": self._verify_impl,
        }, mesh=mesh, metrics=self.metrics)
        # run -> jitted COW page copy (donated in-place, one per run shape)
        self._copy_programs: Dict[int, Callable] = {}
        # run -> jitted host-page upload (donated; swap-in / promote path)
        self._upload_programs: Dict[int, Callable] = {}

    # -- program table (owned by the executor) ---------------------------------
    @property
    def _programs(self) -> Dict[Tuple[str, str], Callable]:
        return self.executor.programs

    @property
    def compile_count(self) -> int:
        return self.executor.compile_count

    def _program(self, group: str, kind: str) -> Callable:
        """Jitted program for (worker group, phase kind) — built and cached
        by the executor layer.  Kept as an engine method so subclasses
        (tests' host-only engines) can stub program dispatch in one place."""
        return self.executor.program(group, kind)

    def _note_compile(self, group: str, kind: str, shape: Tuple[int, ...],
                      all_greedy: bool) -> None:
        before = self.executor.compile_count
        self.executor.note_compile(group, kind, shape, all_greedy)
        if self.tracer.enabled and self.executor.compile_count > before:
            self.tracer.instant("compile", self.tracer.now(), group=group,
                                kind=kind, shape=list(shape),
                                all_greedy=bool(all_greedy))

    # -- jitted bodies ---------------------------------------------------------
    def _sample(self, logits, temps, top_ks, top_ps, seeds, counters,
                all_greedy):
        """logits [N, 1, V] (or [N, 1, K, V]) -> int32 tokens [N] / [N, K].

        Per-row sampling params ([N] arrays); a row with temperature <= 0
        is greedy, so one program serves mixed batches.  ``all_greedy``
        is static — the common greedy tick never builds keys or sorts."""
        lg = logits[:, -1]
        if all_greedy:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return sample_tokens_rows(lg, temps, top_ks, top_ps,
                                  row_keys(seeds, counters))

    def _prefill_chunk_impl(self, params, tokens, offsets, lengths, slots,
                            cache, temps, top_ks, top_ps, seeds, counters,
                            all_greedy):
        """Packed chunk prefill: K/V written arena-direct at (slot, offset)."""
        logits, new_cache = forward_chunk(params, self.cfg, tokens, offsets,
                                          lengths, slots, cache)
        return self._sample(logits, temps, top_ks, top_ps, seeds, counters,
                            all_greedy), new_cache

    def _prefill_whole_impl(self, params, tokens, slot, cache, temps,
                            top_ks, top_ps, seeds, counters, all_greedy):
        """Whole-prompt prefill + on-device arena splice (SSM / hybrid)."""
        logits, new_cache = prefill_into_arena(
            params, self.cfg, {"tokens": tokens}, slot, cache)
        return self._sample(logits, temps, top_ks, top_ps, seeds, counters,
                            all_greedy), new_cache

    def _prefill_chunk_paged_impl(self, params, tokens, offsets, lengths,
                                  slots, cache, block_tables, temps, top_ks,
                                  top_ps, seeds, counters, all_greedy):
        """Packed chunk prefill into the page pool (block-table scatter)."""
        logits, new_cache = forward_chunk(params, self.cfg, tokens, offsets,
                                          lengths, slots, cache,
                                          block_tables=block_tables)
        return self._sample(logits, temps, top_ks, top_ps, seeds, counters,
                            all_greedy), new_cache

    def _prefill_packed_impl(self, params, tokens, starts, offsets, lengths,
                             slots, cache, temps, top_ks, top_ps, seeds,
                             counters, all_greedy):
        """Packed-stream chunk prefill (dense arena): the tick's chunks as
        one flat [T] token stream of bq-aligned segments — one launch,
        one compiled shape per bucketed T."""
        logits, new_cache = forward_chunk_packed(
            params, self.cfg, tokens, starts, offsets, lengths, slots,
            cache, pack_align=self._pack_align)
        return self._sample(logits, temps, top_ks, top_ps, seeds, counters,
                            all_greedy), new_cache

    def _prefill_packed_paged_impl(self, params, tokens, starts, offsets,
                                   lengths, slots, cache, block_tables,
                                   temps, top_ks, top_ps, seeds, counters,
                                   all_greedy):
        """Packed-stream chunk prefill into the page pool."""
        logits, new_cache = forward_chunk_packed(
            params, self.cfg, tokens, starts, offsets, lengths, slots,
            cache, block_tables=block_tables,
            pack_align=self._pack_align)
        return self._sample(logits, temps, top_ks, top_ps, seeds, counters,
                            all_greedy), new_cache

    def _verify_impl(self, params, tokens, offsets, lengths, slots, cache,
                     block_tables, draft, temps, top_ks, top_ps, seeds,
                     counters, all_greedy):
        """Speculative verify: ONE chunk forward of the target model over
        each row's [last_committed, d_1, .., d_k] window against the
        paged arena (K/V written arena-direct like any prefill chunk),
        returning logits at EVERY window position; accept/resample runs
        on device with PER-ROW sampling params (greedy rows accept the
        argmax prefix — bit-identical to their non-speculative decode —
        stochastic rows run Leviathan residual resampling with their own
        key chain) so the host sees one packed [N, C+1] array — C
        candidate tokens plus the emission count."""
        logits, new_cache = forward_chunk(params, self.cfg, tokens, offsets,
                                          lengths, slots, cache,
                                          block_tables=block_tables,
                                          return_all_logits=True)
        draft_len = jnp.asarray(lengths, jnp.int32) - 1
        if all_greedy:
            toks, n_emit = verify_draft(logits, draft, draft_len,
                                        greedy=True)
        else:
            toks, n_emit = verify_draft_rows(
                logits, draft, draft_len, temps, top_ks, top_ps,
                row_keys(seeds, counters))
        return jnp.concatenate([toks, n_emit[:, None]], axis=1), new_cache

    def _decode_paged_impl(self, params, tokens, cache, pos, block_tables,
                           temps, top_ks, top_ps, seeds, counters,
                           all_greedy):
        """One-token decode over the page pool.

        No merge-with-mask pass: inactive slots carry all-sentinel block
        table rows, so their K/V scatters DROP — the page pool is only
        ever written through an allocated page, which is the paged
        analogue of the dense path's ``jnp.where(slot_mask, new, old)``.
        """
        logits, new_cache, _ = forward(params, self.cfg, {"tokens": tokens},
                                       phase="decode", cache=cache, pos=pos,
                                       block_tables=block_tables)
        return self._sample(logits, temps, top_ks, top_ps, seeds, counters,
                            all_greedy), new_cache

    def _decode_impl(self, params, tokens, cache, pos, slot_mask, temps,
                     top_ks, top_ps, seeds, counters, all_greedy):
        logits, new_cache, _ = forward(params, self.cfg, {"tokens": tokens},
                                       phase="decode", cache=cache, pos=pos)
        # frozen slots keep their old cache (mask out writes of idle slots).
        # attn caches are [L, B, ...] (batch at axis 1); shared_attn caches
        # are [B, ...] (batch leading) — pick the axis whose size matches.
        B = slot_mask.shape[0]

        def merge(old, new):
            ax = 1 if (old.ndim >= 2 and old.shape[1] == B) else 0
            shape = [1] * old.ndim
            shape[ax] = B
            b = slot_mask.reshape(shape)
            return jnp.where(b, new, old)

        merged = jax.tree.map(merge, cache, new_cache)
        return self._sample(logits, temps, top_ks, top_ps, seeds, counters,
                            all_greedy), merged

    # -- public API -----------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: Optional[int] = None,
               eos_id: Optional[int] = None, *,
               sampling: Optional[SamplingParams] = None,
               slo: Optional[SLO] = None,
               priority: Optional[int] = None) -> Request:
        """Queue one request.

        ``sampling`` carries the per-request parameters (temperature=0 is
        greedy); omitted, the ``ServeConfig`` legacy defaults apply.  The
        positional ``max_new_tokens`` / ``eos_id`` arguments are kept for
        existing callers and override the corresponding ``sampling``
        fields when given.  ``slo`` attaches TTFT/TPOT deadlines
        (``repro.serving.SLO``, milliseconds): the request counts into
        the ``serving_slo_*`` attainment counters at retirement, and —
        SLO-aware scheduling (PR 10) — its TTFT deadline steers prefill
        ordering (EDF within a priority class) and, with
        ``ServeConfig.admission`` set, the admission decision.

        ``priority`` is a ``scheduler.PRIORITY_*`` lane (default
        STANDARD): slot admission and the prefill budget serve lower
        values first.

        With admission control on, the returned ``Request`` may come back
        ALREADY RETIRED (``finish_reason == "shed"``: projected TTFT
        busts the deadline under current load, or the prompt alone
        overflows the pending-token cap) or parked in ``self.deferred``
        (best-effort request over the cap; it joins the queue once the
        backlog drains).  Callers that must distinguish these check
        ``req.finish_reason`` / ``req in engine.deferred``."""
        sp = sampling if sampling is not None else self._default_sampling
        if max_new_tokens is not None:
            sp = replace(sp, max_new_tokens=max_new_tokens)
        if eos_id is not None:
            sp = replace(sp, eos_id=eos_id)
        req = Request(self._next_id, np.asarray(prompt, np.int32), sp)
        # effective seed: explicit, or derived from (engine seed, req_id) —
        # deterministic across runs, distinct across requests.  Python-int
        # arithmetic, masked to int31: uint32 scalar math would overflow
        # (NumPy 2 warns, and raises outright for a negative sc.seed)
        req.seed = sp.seed if sp.seed is not None else (
            (self.sc.seed * 2654435761 + req.req_id + 1) & 0x7FFFFFFF)
        req.prompt_len = int(req.prompt.shape[-1])
        if self.paged:
            # capacity is a POOL property: a prompt fits iff the pool can
            # hold it (+ 1 decode position) when running alone
            if not self.pool.fits(req.prompt_len + 1):
                raise ValueError(
                    f"prompt of {req.prompt_len} tokens cannot fit the "
                    f"paged pool ({self.pool.n_pages} pages x "
                    f"{self.pool.page_size} = {self.pool.capacity} tokens)")
        elif req.prompt_len >= self.sc.max_len:
            raise ValueError(
                f"prompt of {req.prompt_len} tokens does not fit "
                f"max_len={self.sc.max_len} (need >= 1 decode position)")
        if slo is not None and not isinstance(slo, SLO):
            raise TypeError(f"slo={slo!r} (expected repro.serving.SLO)")
        req.slo = slo
        if priority is not None:
            req.priority = int(priority)
        req.t_submit = time.monotonic()
        self._next_id += 1
        if self.tracer.enabled:
            self.tracer.begin_request(req.req_id, req.t_submit,
                                      prompt_len=req.prompt_len)
        if self.admission is not None:
            decision = self.admission.decide(
                req.prompt_len,
                ttft_deadline_s=(slo.ttft_ms / 1e3
                                 if slo is not None and slo.ttft_ms is not None
                                 else math.inf),
                backlog_tokens=self._pending_prefill_tokens(),
                decode_backlog_tokens=self._pending_decode_tokens(),
                n_live=(len(self.queue) + len(self.deferred)
                        + sum(r is not None for r in self.slot_req)),
                ema_value=self._tick_wall_ema,
                ema_ticks=self._tick_wall_n)
            if decision == "shed":
                return self._shed(req)
            if decision == "defer":
                self.admission_deferred += 1
                self.deferred.append(req)
                return req
        self.queue.append(req)
        return req

    def _shed(self, req: Request) -> Request:
        """Refuse ``req`` at admission: retired immediately with
        finish_reason "shed" (terminal — it never holds a slot or a
        page).  Unlike an abort (the CLIENT's choice, excluded from
        goodput), a shed is the engine declining demand: a
        deadline-carrying request counts into
        ``serving_slo_requests_total`` un-attained (its NaN TTFT fails
        the bound), so ``goodput()`` is measured over ALL submitted SLO
        demand and shedding only wins by letting the survivors meet
        their deadlines."""
        self.admission_shed += 1
        req.state = RequestState.DONE
        req.finish_reason = "shed"
        req.t_done = time.monotonic()
        self._account_latency(req)
        if self.tracer.enabled:
            self.tracer.end_request(req.req_id, req.t_done, reason="shed",
                                    generated=0)
        self.done.append(req)
        return req

    def _pending_prefill_tokens(self, include_deferred: bool = True) -> int:
        """Queued-but-uncomputed prefill tokens: the backlog a new prompt
        must wait behind — the admission controller's projection input
        and the structural ``max_pending_tokens`` cap's measure."""
        pend = sum(max(r.prompt_len - r.prefill_pos, 0) for r in self.queue)
        if include_deferred:
            pend += sum(max(r.prompt_len - r.prefill_pos, 0)
                        for r in self.deferred)
        pend += sum(max(self._effective_len(r) - r.prefill_pos, 0)
                    for r in self.slot_req
                    if r is not None and r.state == RequestState.PREFILLING)
        return pend

    def _pending_decode_tokens(self, include_deferred: bool = True) -> int:
        """Remaining generation budget over every live request — the
        decode-side queueing a new prompt waits behind (drains at
        ``max_decode_batch`` tokens per tick)."""
        rem = sum(max(r.max_new_tokens - len(r.generated), 0)
                  for r in self.queue)
        if include_deferred:
            rem += sum(max(r.max_new_tokens - len(r.generated), 0)
                       for r in self.deferred)
        rem += sum(max(r.max_new_tokens - len(r.generated), 0)
                   for r in self.slot_req if r is not None)
        return rem

    def _reconsider_deferred(self) -> None:
        """Re-evaluate deferred requests against the current backlog;
        admitted ones join the queue tail in deferral order.  Deferral is
        only ever STRUCTURAL (best-effort request over the pending-token
        cap — deadline busts shed instead, and a prompt that alone
        overflows the cap was shed at submit), so every deferred request
        re-enters as soon as enough backlog drains: no starvation."""
        if not self.deferred or self.admission is None:
            return
        backlog = self._pending_prefill_tokens(include_deferred=False)
        n_live = (len(self.queue)
                  + sum(r is not None for r in self.slot_req))
        still = []
        for req in self.deferred:
            decision = self.admission.decide(
                req.prompt_len, ttft_deadline_s=math.inf,
                backlog_tokens=backlog, n_live=n_live,
                ema_value=self._tick_wall_ema, ema_ticks=self._tick_wall_n)
            # decode backlog omitted: deferral is structural (prefill-
            # token cap only), a best-effort request has no deadline to
            # project against
            if decision == "admit":
                self.queue.append(req)
                backlog += max(req.prompt_len - req.prefill_pos, 0)
                n_live += 1
            else:
                still.append(req)
        self.deferred = still

    @property
    def tick_wall_ema(self) -> float:
        """EMA of ``TickRecord.wall_s`` over ticks that compiled nothing
        (0.0 until the first such tick) — the live steady-state tick-cost
        estimate admission projections use."""
        return self._tick_wall_ema

    def abort(self, req_id: int) -> Optional[RequestOutput]:
        """Cancel a request at ANY lifecycle stage.

        WAITING: dequeued.  PREFILLING / DECODING (speculative verify
        state included): its slot is vacated — paged pool references
        (owned AND prefix-cache-attached pages), draft-pool state, and
        the dense slot mask are all released; pages the prefix cache
        pinned stay cached (they are the cache's references, reclaimable
        as usual).  Returns the terminal ``RequestOutput``
        (finish_reason "abort"), or None for an unknown / already
        finished id.  Tokens already generated remain on the Request."""
        req = None
        for i, r in enumerate(self.queue):
            if r.req_id == req_id:
                req = self.queue.pop(i)
                if req.swap is not None:    # swapped-out KV dies with it
                    for r_idx, host_pages in enumerate(req.swap.pages):
                        self.host_tier.release(r_idx, host_pages)
                    req.swap = None
                break
        if req is None:
            # deferred (admission-parked) requests hold no slot, pages,
            # or swap state — cancellation is pure list removal
            for i, r in enumerate(self.deferred):
                if r.req_id == req_id:
                    req = self.deferred.pop(i)
                    break
        if req is None:
            for r in self.slot_req:
                if r is not None and r.req_id == req_id:
                    req = r
                    if self.drafter is not None:
                        self.drafter.release(r.slot)
                    if self.paged:
                        self.pool.release(r.slot)
                    self.slot_req[r.slot] = None
                    self.slot_pos[r.slot] = -1
                    r.slot = -1
                    break
        if req is None:
            return None
        self.aborted += 1
        req.state = RequestState.DONE
        req.finish_reason = "abort"
        req.t_done = time.monotonic()
        # aborts are client cancellations, not serving failures: they are
        # EXCLUDED from SLO attainment (goodput measures what the engine
        # did with requests it was allowed to finish)
        if self.tracer.enabled:
            self.tracer.end_request(req.req_id, req.t_done, reason="abort",
                                    generated=len(req.generated))
        self.done.append(req)
        return RequestOutput(req_id=req.req_id, new_token_ids=[],
                             n_generated=len(req.generated), finished=True,
                             finish_reason="abort")

    # -- helpers ----------------------------------------------------------------
    def _to_host(self, arr) -> np.ndarray:
        """The engine's single device->host transfer point.

        Each PHASE PROGRAM CALL moves at most one token array ([B] or
        [B, K]) through here — one for the packed prefill batch, one for
        the decode step (so a mixed tick makes two; the per-request
        whole-prompt fallback makes one per call).  What device-side
        sampling eliminates is the per-SLOT logits sync; tests monkeypatch
        this to pin that down.
        """
        self.host_transfers += 1
        return np.asarray(arr)

    def _pack_params(self, rows: Sequence[Tuple[int, Request]], n: int):
        """Pack per-request sampling params into [n]-shaped device arrays
        for one jitted phase call.  ``rows`` maps row index -> request
        (a packed-batch index for prefill/verify, the SLOT for decode);
        unmapped rows are greedy placeholders (temperature 0 — argmax,
        result discarded).  The counter is the index of the token being
        sampled (= tokens emitted so far), which keys the request's
        per-row PRNG chain (see sampling.row_keys).  Returns the arrays
        plus the static ``all_greedy`` flag."""
        temps = np.zeros((n,), np.float32)
        top_ks = np.zeros((n,), np.int32)
        top_ps = np.zeros((n,), np.float32)
        seeds = np.zeros((n,), np.int32)
        counters = np.zeros((n,), np.int32)
        all_greedy = True
        for i, r in rows:
            sp = r.sampling
            temps[i] = sp.temperature
            top_ks[i] = sp.top_k
            top_ps[i] = sp.top_p
            seeds[i] = r.seed
            counters[i] = len(r.generated)
            all_greedy = all_greedy and sp.greedy
        return (jnp.asarray(temps), jnp.asarray(top_ks), jnp.asarray(top_ps),
                jnp.asarray(seeds), jnp.asarray(counters)), all_greedy

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _admit(self) -> List[Request]:
        admitted = []
        free = self._free_slots()
        # SLO-aware slot order: (class, TTFT deadline, age).  Stable and
        # deterministic — all-default submissions sort (STANDARD, inf,
        # req_id), i.e. exactly the old FIFO.  The swap-resume head-wait
        # below is order-independent: deadlock freedom rests on a lone
        # request always fitting the pool, not on WHICH request is head
        if self.queue:
            self.queue.sort(
                key=lambda r: (r.priority, r.ttft_deadline_s, r.req_id))
        while free and self.queue:
            req = self.queue[0]
            if req.swap is not None:
                # swap-resume: the head's KV lives in the host tier; it
                # re-enters only when its device pages fit again.  On
                # failure the head WAITS (FIFO order preserved) — this is
                # deadlock-free because submit() guarantees a lone request
                # fits the pool and cached pages are always reclaimable
                if not self._try_swap_in(req, free[0]):
                    break
                self.queue.pop(0)
                free.pop(0)
                admitted.append(req)
                continue
            self.queue.pop(0)
            slot = free.pop(0)
            req.slot = slot
            req.state = RequestState.PREFILLING
            self.slot_req[slot] = req
            self._try_prefix_attach(req)
            admitted.append(req)
        if not admitted:
            return admitted
        t = time.monotonic()
        for req in admitted:
            req.t_admit = req.t_admit or t      # the first admission only
        if self.tracer.enabled:
            for req in admitted:
                # t is t_admit on a first admission
                self.tracer.request_span(
                    req.req_id, "queued", req.t_requeue or req.t_submit, t,
                    cached_tokens=req.cached_tokens,
                    n_preempted=req.n_preempted)
        return admitted

    def _by_id(self) -> Dict[int, Request]:
        return {r.req_id: r for r in self.slot_req if r is not None}

    # -- prefix cache ------------------------------------------------------------
    def _try_prefix_attach(self, req: Request) -> None:
        """Admission-time prefix lookup: point the slot's leading block-
        table rows at the longest cached prefix (shared, refcounted) and
        start prefill past it.  The match is capped at len - 1 so at
        least one token remains to prefill — the prompt's last-token
        logits seed decoding."""
        if self.prefix is None:
            return
        tokens = self._effective_tokens(req)
        matched, pages = self.prefix.match(
            tokens, max_tokens=int(tokens.shape[-1]) - 1)
        if matched <= 0:
            return
        self.pool.attach(req.slot, pages, matched)
        req.prefill_pos = matched
        req.cached_tokens = matched

    def _publish_prefix(self, req: Request) -> None:
        """Publish a freshly-prefilled request's PROMPT pages into the
        cache.  Ring purity gate: a sliding-window run's pages are
        position-pure only while the total prefilled length has not
        wrapped its ring — once it has, row 0 holds late positions and
        the prefix is unpublishable (see docs/serving.md §Prefix cache)."""
        if self.prefix is None:
            return
        if self._effective_len(req) > self.pool.shareable_capacity():
            return
        prompt = self._effective_tokens(req)[..., :req.prompt_len]
        self.prefix.insert(prompt, self.pool, req.slot)

    def _reclaim_cache(self, n_pages: int) -> int:
        """Evict LRU cached blocks until at least ``n_pages`` pages are
        actually FREE again (blocks still pinned by live slots are
        skipped — evicting them frees nothing and only loses future
        hits).  Cached pages are reclaimable capacity: this always runs
        before any live request is preempted."""
        if self.prefix is None:
            return 0
        freed = self.prefix.evict(self.pool, max(n_pages, 1))
        self.cache_evicted_pages += freed
        return freed

    def _copy_pages(self, copies) -> None:
        """Mirror ``KVPool.ensure_writable``'s accounting with the device
        copies: one donated in-place program per run moves page ``old``'s
        rows to ``new`` before the writer's program launches."""
        for r, old, new in copies:
            if r not in self._copy_programs:
                # pool leaves are [L, n_pages, P, ...]: pages live on axis 1
                self._copy_programs[r] = jax.jit(
                    lambda c, src, dst: jax.tree.map(
                        lambda x: x.at[:, dst].set(x[:, src]), c),
                    donate_argnums=(0,))
            self.cache[r] = self._copy_programs[r](
                self.cache[r], jnp.int32(old), jnp.int32(new))
        self.cow_copies += len(copies)

    def _ensure_writable(self, slot: int, start: int, end: int) -> bool:
        """COW every shared page a write to [start, end) would dirty,
        reclaiming cached pages for copy targets if needed.  False if
        copy targets remain unavailable (caller preempts or defers)."""
        copies = self.pool.ensure_writable(slot, start, end)
        if copies is None:
            # reclaim exactly the copy-target deficit (a multi-page chunk
            # may need several targets per run — one fixed-size reclaim
            # would drip-feed it through the stall breaker)
            self._reclaim_cache(self.pool.cow_deficit(slot, start, end))
            copies = self.pool.ensure_writable(slot, start, end)
        if copies is None:
            return False
        self._copy_pages(copies)
        return True

    # -- recompute-on-resume -----------------------------------------------------
    def _effective_tokens(self, req: Request) -> np.ndarray:
        """The token stream a (re)prefill must process: the prompt, plus —
        after a preemption — everything already generated (recompute: the
        resumed prefill rebuilds the evicted KV and its final logits yield
        the CONTINUATION token, exactly what the evicted decode step would
        have produced)."""
        if not req.generated:
            return req.prompt
        if self.cfg.n_codebooks > 1:
            gen = np.asarray(req.generated, np.int32).T          # [K, n]
            return np.concatenate([req.prompt, gen], axis=-1)
        return np.concatenate(
            [req.prompt, np.asarray(req.generated, np.int32)])

    def _effective_len(self, req: Request) -> int:
        return req.prompt_len + len(req.generated)

    def _preempt(self, req: Request) -> None:
        """Evict ``req`` from its slot: pages back to the pool, request
        back to WAITING (age-ordered).  With a host tier the victim's KV
        swaps out (exact page copies, resumed with ZERO recomputation);
        without one — or when the tier is full — it falls back to
        recompute-on-resume."""
        assert self.paged and req.slot >= 0
        if self.drafter is not None:
            self.drafter.release(req.slot)
        if not self._swap_out(req):
            # recompute-on-resume: the re-prefill rebuilds the evicted KV
            req.prefill_pos = 0
            req.cached_tokens = 0       # re-matched at re-admission
            self.recompute_preemptions += 1
        self.pool.release(req.slot)
        self.slot_req[req.slot] = None
        self.slot_pos[req.slot] = -1
        req.slot = -1
        req.state = RequestState.WAITING
        req.n_preempted += 1
        self.preemptions += 1
        req.t_requeue = time.monotonic()    # the next queued span starts here
        if self.tracer.enabled:
            self.tracer.instant("preempt", req.t_requeue, req_id=req.req_id,
                                swapped=req.swap is not None)
        # keep the queue age-ordered: older (smaller id) requests first,
        # so the re-queued victim outranks later submissions
        i = 0
        while i < len(self.queue) and self.queue[i].req_id < req.req_id:
            i += 1
        self.queue.insert(i, req)

    def _preemption_victim(self, needy: Request) -> Request:
        """Youngest slot-holding request whose eviction frees pages (or
        ``needy`` itself if nobody else holds any) — the oldest request is
        never chosen over an older needy one, so it always completes."""
        holders = sorted((r for r in self.slot_req if r is not None),
                         key=lambda r: r.req_id, reverse=True)
        for r in holders:
            if r is needy:
                continue
            if r.req_id > needy.req_id and self.pool.len_of(r.slot) > 0:
                return r
        return needy

    def _break_prefill_stall(self) -> None:
        """Deadlock breaker: PREFILLING requests exist but the tick planned
        NOTHING — mid-prefill requests hold every page between them and no
        decoder is running to trigger growth preemption.  Evict the
        youngest page holder (never the oldest: ``submit`` guarantees any
        single request fits the pool alone, so the oldest can always make
        progress once the young have yielded their pages)."""
        if not any(r is not None and r.state == RequestState.PREFILLING
                   for r in self.slot_req):
            return
        # cached pages yield before any live request does
        if self._reclaim_cache(1):
            return
        holders = [r for r in self.slot_req
                   if r is not None and self.pool.len_of(r.slot) > 0]
        if not holders:
            return
        victim = max(holders, key=lambda r: r.req_id)
        oldest = min((r for r in self.slot_req if r is not None),
                     key=lambda r: r.req_id)
        if victim is not oldest:
            self._preempt(victim)
            # the victim's prefix pages may now be held by the cache alone:
            # free one, or the next admission re-attaches them all and the
            # stall repeats every tick
            self._reclaim_cache(1)

    # -- tiered KV (host spill) --------------------------------------------------
    def _read_page(self, r: int, page: int) -> Dict[str, np.ndarray]:
        """Pull one device page across every layer to host numpy — pool
        leaves are [L, n_pages, P, ...], so each leaf yields [L, P, ...]."""
        return {k: np.asarray(leaf[:, page])
                for k, leaf in self.cache[r].items()}

    def _write_page(self, r: int, page: int,
                    data: Dict[str, np.ndarray]) -> None:
        """Upload one host-tier page into device page ``page``: a donated
        in-place program per run (mirrors ``_copy_pages``), so the arena
        is patched without a full-pool copy."""
        if r not in self._upload_programs:
            self._upload_programs[r] = jax.jit(
                lambda c, dst, vals: jax.tree.map(
                    lambda x, v: x.at[:, dst].set(v), c, vals),
                donate_argnums=(0,))
        self.cache[r] = self._upload_programs[r](
            self.cache[r], jnp.int32(page),
            {k: jnp.asarray(v) for k, v in data.items()})

    def _swap_out(self, req: Request) -> bool:
        """Copy a preemption victim's device pages into the host tier and
        hang a ``_SwapHandle`` off the request.  All-or-nothing: False (no
        state change) when the tier is absent, the slot holds nothing, or
        host pages run short — the caller then falls back to
        recompute-on-resume."""
        if self.host_tier is None:
            return False
        length = self.pool.len_of(req.slot)
        if length <= 0:
            return False
        pools = self.pool.pools
        need = [p.pages_of(length) for p in pools]
        if any(self.host_tier.free_pages(r) < n for r, n in enumerate(need)):
            return False
        t_sw0, b0 = self.tracer.now(), self.host_tier.swap_out_bytes
        pages: List[List[int]] = []
        for r, p in enumerate(pools):
            host = self.host_tier.alloc(r, need[r])
            assert host is not None     # free_pages checked per run above
            for i, hp in enumerate(host):
                self.host_tier.store(
                    r, hp, self._read_page(r, int(p.table[req.slot, i])))
            pages.append(host)
        req.swap = _SwapHandle(
            length=length, pages=pages, prefill_pos=req.prefill_pos,
            cached_tokens=req.cached_tokens,
            pos=int(self.slot_pos[req.slot]), state=req.state)
        self.swap_outs += 1
        if self.tracer.enabled:
            self.tracer.request_span(
                req.req_id, "swap_out", t_sw0, self.tracer.now(),
                bytes=self.host_tier.swap_out_bytes - b0, tokens=length)
        return True

    def _try_swap_in(self, req: Request, slot: int) -> bool:
        """Restore a swapped-out request into ``slot``: regrow its device
        pages (reclaiming cached pages on a shortfall), upload the host
        copies row for row, and resume EXACTLY where it left off — the
        swap path re-prefills zero tokens.  False leaves the request at
        the queue head with its handle intact."""
        h = req.swap
        if not self.pool.grow(slot, h.length):
            deficit = max(p.pages_of(h.length) - p.free_pages()
                          for p in self.pool.pools)
            self._reclaim_cache(deficit)
            if not self.pool.grow(slot, h.length):
                return False
        t_sw0, b0 = self.tracer.now(), self.host_tier.swap_in_bytes
        req.slot = slot
        self.slot_req[slot] = req
        for r, p in enumerate(self.pool.pools):
            for i, hp in enumerate(h.pages[r]):
                self._write_page(r, int(p.table[slot, i]),
                                 self.host_tier.load(r, hp))
            self.host_tier.release(r, h.pages[r])
        req.prefill_pos = h.prefill_pos
        req.cached_tokens = h.cached_tokens
        req.state = h.state
        self.slot_pos[slot] = h.pos     # -1 for a mid-prefill swap
        req.swap = None
        self.swap_resumes += 1
        if self.tracer.enabled:
            self.tracer.request_span(
                req.req_id, "swap_in", t_sw0, self.tracer.now(),
                bytes=self.host_tier.swap_in_bytes - b0, tokens=h.length)
        return True

    def _demote_pages(self, dev_pages: List[int]) -> Optional[List[int]]:
        """PrefixCache demote callback: copy one cached block (one device
        page PER RUN) into the host tier.  All-or-nothing; None makes the
        cache hard-drop the block instead."""
        host: List[int] = []
        for r, q in enumerate(dev_pages):
            got = self.host_tier.alloc(r, 1)
            if got is None:
                for rr, hp in enumerate(host):
                    self.host_tier.release(rr, [hp])
                return None
            host.append(got[0])
            self.host_tier.store(r, got[0], self._read_page(r, int(q)))
        return host

    def _promote_pages(self, host_pages: List[int]) -> Optional[List[int]]:
        """PrefixCache promote callback: re-materialise a demoted block on
        device — fresh externally-owned pages (``alloc_external``: ref=1,
        external=1, conservation holds from birth) — and free the host
        copies.  None when any run's free list is empty (partial hit)."""
        if any(not p.free for p in self.pool.pools):
            return None
        dev: List[int] = []
        for r, hp in enumerate(host_pages):
            q = self.pool.pools[r].alloc_external()
            assert q is not None        # free list checked above
            self._write_page(r, q, self.host_tier.load(r, hp))
            self.host_tier.release(r, [hp])
            dev.append(q)
        return dev

    def _discard_host_pages(self, host_pages: List[int]) -> None:
        """PrefixCache discard callback: a demoted block died (evicted
        subtree / re-published over) — drop its host copies."""
        for r, hp in enumerate(host_pages):
            self.host_tier.release(r, [hp])

    # -- prefill -> decode handoff (the 2.5D-link analogue) ----------------------
    def _record_handoff(self, req: Request) -> None:
        """Price the KV a prefill->decode handoff moves across HALO's
        2.5D interposer link (CiM prefill stack -> CiD decode stack).
        Only NEWLY-built state moves — prefix-cache hits are already
        decode-side resident.  Colocated executors have no link: no-op."""
        if not self.executor.migrates_kv:
            return
        eff = self._effective_len(req)
        if self.paged:
            pages = nbytes = 0
            for r, p in enumerate(self.pool.pools):
                n = max(p.pages_of(eff) - p.pages_of(req.cached_tokens), 0)
                pages += n
                nbytes += n * self.pool.page_bytes(r)
        else:
            pages = 0
            nbytes = eff * self._dense_token_bytes + self._dense_state_bytes
        self.executor.record_handoff(pages, nbytes)

    def _append_token(self, req: Request, tok_row) -> None:
        flat = np.asarray(tok_row).reshape(-1)
        if self.cfg.n_codebooks > 1:
            req.generated.append([int(t) for t in flat])
        else:
            req.generated.append(int(flat[0]))

    def _start_decoding(self, req: Request, tok_row) -> None:
        self._publish_prefix(req)       # prompt pages complete & unwrapped
        self.slot_pos[req.slot] = self._effective_len(req)
        self._record_handoff(req)       # KV crosses the phase boundary here
        if req.sampling.max_new_tokens == 0 and not req.generated:
            # prefill-only request: the seeding sample is discarded, no
            # token ever emits (ttft/tpot stay NaN), reason is "length"
            req.finish_reason = "length"
            self._retire(req)
            return
        self._append_token(req, tok_row)
        if req.t_first_token == 0.0:    # a resumed prefill keeps its TTFT
            req.t_first_token = time.monotonic()
            if self.tracer.enabled:
                self.tracer.instant("first_token", req.t_first_token,
                                    req_id=req.req_id)
        req.state = RequestState.DECODING
        if self._finished(req):
            self._retire(req)

    def _stream_reason(self, req: Request) -> Optional[str]:
        """Token-stream termination only (max_new / eos / stop) — what a
        verify window's emission loop may stop on.  The arena position
        bound is NOT checked here: a window commits its slot_pos jump
        before the tokens append, so mid-emission the position test would
        fire early and drop accepted tokens non-speculative decode would
        emit."""
        if len(req.generated) >= req.max_new_tokens:
            return "length"
        if req.generated:
            last = req.generated[-1]
            if isinstance(last, list):          # multi-codebook: codebook 0
                last = last[0] if last else None
            if req.eos_id is not None and last == req.eos_id:
                return "eos"
            if last is not None and last in req.sampling.stop:
                return "stop"
        return None

    def _stream_done(self, req: Request) -> bool:
        return self._stream_reason(req) is not None

    def _finished(self, req: Request) -> bool:
        reason = self._stream_reason(req)
        if reason is None:
            limit = self.pool.length_bound if self.paged else self.sc.max_len
            if self.slot_pos[req.slot] >= limit - 1:
                reason = "length"       # arena/pool position bound
        if reason is None:
            return False
        req.finish_reason = reason
        return True

    def _retire(self, req: Request) -> None:
        req.state = RequestState.DONE
        req.t_done = time.monotonic()
        self._account_latency(req)
        if self.tracer.enabled:
            self.tracer.end_request(req.req_id, req.t_done,
                                    reason=req.finish_reason,
                                    generated=len(req.generated),
                                    n_preempted=req.n_preempted)
        if self.drafter is not None:
            self.drafter.release(req.slot)
        if self.paged:
            self.pool.release(req.slot)
        self.slot_req[req.slot] = None
        self.slot_pos[req.slot] = -1
        self.done.append(req)

    def _account_latency(self, req: Request) -> None:
        """Retirement-time latency/SLO bookkeeping: TTFT/TPOT histogram
        samples (NaN = never emitted — skipped by ``observe``) and, for
        requests submitted with deadlines, the ``serving_slo_*``
        attainment counters behind ``goodput()``.  Aborted requests never
        reach here (see ``abort``): goodput measures served requests."""
        m = self.metrics
        m.observe("serving_ttft_seconds", req.ttft)
        m.observe("serving_tpot_seconds", req.tpot)
        if req.slo is None:
            return
        ok, ttft_ok, tpot_ok = slo_attainment(req.ttft, req.tpot, req.slo)
        m.inc("serving_slo_requests_total")
        if ok:
            m.inc("serving_slo_attained_total")
        if not ttft_ok:
            m.inc("serving_slo_ttft_violations_total")
        if not tpot_ok:
            m.inc("serving_slo_tpot_violations_total")

    def _grow_for_decode(self, r: Request) -> bool:
        """Secure this tick's one-token write for ``r``: grow the slot by
        one position and COW any shared page that position lands in
        (ring wrap over attached/published prefix pages).  Exhaustion
        order: reclaim cached pages first, preempt live requests only
        after the cache is dry.  Returns False iff ``r`` itself was
        evicted."""
        pos = int(self.slot_pos[r.slot])
        while True:
            if self.pool.grow(r.slot, pos + 1):
                if self._ensure_writable(r.slot, pos, pos + 1):
                    return True
                # grown but no COW target: roll back before freeing pages
                self.pool.shrink(r.slot, pos)
            elif self._reclaim_cache(1):
                continue
            victim = self._preemption_victim(r)
            self._preempt(victim)
            if victim is r:
                return False

    # -- phase execution --------------------------------------------------------
    # Each phase program runs in three profiler spans: ``prepare`` (page
    # claims, host arrays, their device copies), ``launch`` (the jitted
    # call, which returns before the device finishes) and ``sync`` (the
    # ``_to_host`` wait for its tokens).
    def _launch(self, group: str, call: _Call) -> Any:
        """Note ``call``'s compile key and launch its program; returns the
        sampled tokens, still on the device."""
        self._note_compile(group, call.kind, call.shape, call.all_greedy)
        out, self.cache = self._program(group, call.kind)(
            self.params, *call.head, self.cache, *call.tail, call.all_greedy)
        return out

    def _run_prefill_tick(self, plan: TickPlan) -> None:
        """Execute the plan's prefill chunks on the planned worker group."""
        reqs = self._by_id()
        chunks = [(reqs[rid], take) for rid, take in plan.prefill_chunks
                  if rid in reqs and take > 0]
        if not chunks:
            return
        if not self.chunked:
            self._run_whole_prefill(plan, chunks)
            return
        with self.tracer.span("serve.prefill.prepare"):
            if self.paged:
                chunks = self._claim_prefill_pages(chunks)
            call = None
            if chunks:
                call = (self._packed_prefill_call if self._packed
                        else self._padded_prefill_call)(chunks)
        if call is None:
            return
        self._prefill_progress = True

        tp0 = self.tracer.now()
        with self.tracer.span("serve.prefill.launch"):
            toks = self._launch(plan.prefill_group, call)
        if self.tracer.enabled:
            tp1 = self.tracer.now()     # one launch serves every chunk
            for req, take in chunks:
                self.tracer.request_span(req.req_id, "prefill_chunk",
                                         tp0, tp1, take=take,
                                         offset=req.prefill_pos)
        self.prefill_tokens_executed += sum(take for _, take in chunks)
        self.prefill_launches += 1
        sampled = None
        for i, (req, take) in enumerate(chunks):
            req.prefill_pos += take
            if req.prefill_pos >= self._effective_len(req):
                if sampled is None:
                    with self.tracer.span("serve.prefill.sync"):
                        sampled = self._to_host(toks)  # one transfer a tick
                self._start_decoding(req, sampled[i])

    def _run_whole_prefill(self, plan: TickPlan, chunks) -> None:
        """Atomic whole-prompt prefill (SSM / shared-attn state handoff):
        one call, and one transfer, per request."""
        self._prefill_progress = True
        for req, take in chunks:
            with self.tracer.span("serve.prefill.prepare"):
                pp, all_greedy = self._pack_params([(0, req)], 1)
                call = _Call("whole", (req.prompt_len,),
                             (jnp.asarray(req.prompt[None], jnp.int32),
                              jnp.int32(req.slot)), pp, all_greedy)
            tw0 = self.tracer.now()
            with self.tracer.span("serve.prefill.launch"):
                toks = self._launch(plan.prefill_group, call)
            if self.tracer.enabled:
                self.tracer.request_span(
                    req.req_id, "prefill_chunk", tw0, self.tracer.now(),
                    take=req.prompt_len, offset=0)
            req.prefill_pos = req.prompt_len
            self.prefill_tokens_executed += req.prompt_len
            self.prefill_launches += 1
            self.prefill_rows_executed += req.prompt_len
            with self.tracer.span("serve.prefill.sync"):
                tok = self._to_host(toks)[0]
            self._start_decoding(req, tok)

    def _claim_prefill_pages(self, chunks):
        """Claim the chunks' pages; the scheduler planned against the pool
        headroom, so this succeeds — trim defensively (one query, one
        grow) if a same-tick race says otherwise.  Any SHARED page the
        chunk would dirty (a ring wrap over attached prefix pages) is
        copied first; if no copy target exists even after reclaiming
        cached pages, the chunk rolls back and waits for a later tick.
        Returns the chunks that hold their pages."""
        claimed = []
        for req, take in chunks:
            take = min(take, self.pool.max_grow_tokens(req.slot))
            if take <= 0 or not self.pool.grow(req.slot,
                                               req.prefill_pos + take):
                continue
            if not self._ensure_writable(req.slot, req.prefill_pos,
                                         req.prefill_pos + take):
                self.pool.shrink(req.slot, req.prefill_pos)
                continue
            claimed.append((req, take))
        return claimed

    def _padded_prefill_call(self, chunks) -> _Call:
        """The tick's chunks as one padded [N, C] batch (pow2 buckets bound
        the number of compiled shapes).  Row i samples chunk i."""
        N = _bucket(len(chunks), self.sc.max_batch)
        C = _bucket(max(take for _, take in chunks), self.sc.phase.prefill_chunk)
        if self.cfg.n_codebooks > 1:
            tokens = np.zeros((N, self.cfg.n_codebooks, C), np.int32)
        else:
            tokens = np.zeros((N, C), np.int32)
        offs = np.zeros((N,), np.int32)
        lens = np.zeros((N,), np.int32)
        slots = np.full((N,), self.sc.max_batch, np.int32)  # OOB rows: drop
        for i, (req, take) in enumerate(chunks):
            sl = slice(req.prefill_pos, req.prefill_pos + take)
            tokens[i, ..., :take] = self._effective_tokens(req)[..., sl]
            offs[i] = req.prefill_pos
            lens[i] = take
            slots[i] = req.slot
        pp, all_greedy = self._pack_params(
            [(i, req) for i, (req, _) in enumerate(chunks)], N)
        self.prefill_rows_executed += N * C
        head = (jnp.asarray(tokens), jnp.asarray(offs), jnp.asarray(lens),
                jnp.asarray(slots))
        if self.paged:
            return _Call("chunk_paged", (N, C), head,
                         (self.pool.block_tables(), *pp), all_greedy)
        return _Call("chunk", (N, C), head, pp, all_greedy)

    def _packed_prefill_call(self, chunks) -> _Call:
        """The tick's chunks as ONE flat [T] token stream: chunk i occupies
        ``[starts[i], starts[i] + take)``, T is the pow2-bucketed packed
        length, and pad gaps carry no request (start sentinel T, slot
        sentinel max_batch).  Pad work is the alignment remainder instead
        of the padded batch's ``N*C - sum(take)``, and the compiled-shape
        key is (T,) alone — one ladder, not an (N, C) grid: the segment
        metadata is always max_batch wide (tiny arrays; pad segments are
        sentinel-masked), so only the stream length retraces.  Row i of
        the returned tokens samples chunk i, exactly like the padded
        batch."""
        packed = pack_chunks([(req.req_id, take) for req, take in chunks],
                             align=self._pack_align)
        T = packed.length
        Nb = self.sc.max_batch
        tokens = np.zeros((T,), np.int32)
        starts = np.full((Nb,), T, np.int32)    # pad segments: empty tail
        offs = np.zeros((Nb,), np.int32)
        lens = np.zeros((Nb,), np.int32)
        slots = np.full((Nb,), self.sc.max_batch, np.int32)  # OOB rows: drop
        for i, (req, take) in enumerate(chunks):
            s = packed.starts[i]
            sl = slice(req.prefill_pos, req.prefill_pos + take)
            tokens[s:s + take] = self._effective_tokens(req)[sl]
            starts[i] = s
            offs[i] = req.prefill_pos
            lens[i] = take
            slots[i] = req.slot
        pp, all_greedy = self._pack_params(
            [(i, req) for i, (req, _) in enumerate(chunks)], Nb)
        self.prefill_rows_executed += T
        head = (jnp.asarray(tokens), jnp.asarray(starts), jnp.asarray(offs),
                jnp.asarray(lens), jnp.asarray(slots))
        if self.paged:
            self.prefill_history_pages += sum(
                p.pages_of(req.prefill_pos)
                for req, _ in chunks for p in self.pool.pools)
            return _Call("packed_paged", (T, Nb), head,
                         (self.pool.block_tables(), *pp), all_greedy)
        return _Call("packed", (T, Nb), head, pp, all_greedy)

    # -- speculative draft/verify ------------------------------------------------
    def _spec_budget(self, r: Request) -> int:
        """Largest draft window this tick could commit AND roll back for
        ``r``, before any drafter runs: the ring rollback bound, the pool
        length bound, the remaining token budget (a window emits up to
        k+1 tokens), and the pages the pool can grant without preempting
        anyone (speculation is opportunistic — the one-token decode path
        owns the preemption machinery).  Computed drafter-free so
        permanently unspeculatable requests (a ring target past its
        rollback bound) never pay drafting cost at all."""
        pos = int(self.slot_pos[r.slot])
        return min(
            self.spec.k,
            self._rollback_bound - pos - 1,
            self.pool.length_bound - pos - 2,
            r.max_new_tokens - len(r.generated) - 1,
            self.pool.max_grow_tokens(r.slot) - 1,
        )

    def _run_verify_tick(self, plan: TickPlan,
                         rows: List[Tuple[Request, np.ndarray]]) -> None:
        """Execute the tick's verify windows as ONE packed batch on the
        verify (CiM-analogue) worker group and commit the results:
        accepted drafts + one correction/bonus token per row, with the
        rejected tail's KV rolled back via ``KVPool.truncate``."""
        kmax = max(int(d.shape[-1]) for _, d in rows)
        N = _bucket(len(rows), self.sc.max_batch)
        C = _bucket(kmax + 1, self.spec.k + 1)
        tokens = np.zeros((N, C), np.int32)
        draft = np.zeros((N, C - 1), np.int32)
        offs = np.zeros((N,), np.int32)
        lens = np.zeros((N,), np.int32)
        slots = np.full((N,), self.sc.max_batch, np.int32)  # OOB rows: drop
        for i, (r, d) in enumerate(rows):
            kd = int(d.shape[-1])
            tokens[i, 0] = r.generated[-1]
            tokens[i, 1:1 + kd] = d
            draft[i, :kd] = d
            offs[i] = self.slot_pos[r.slot]
            lens[i] = kd + 1
            slots[i] = r.slot
        pp, all_greedy = self._pack_params(
            [(i, r) for i, (r, _) in enumerate(rows)], N)
        call = _Call("verify", (N, C),
                     (jnp.asarray(tokens), jnp.asarray(offs),
                      jnp.asarray(lens), jnp.asarray(slots)),
                     (self.pool.block_tables(), jnp.asarray(draft), *pp),
                     all_greedy)
        tv0 = self.tracer.now()
        out = self._launch(plan.verify_group, call)
        packed = self._to_host(out)                 # [N, C+1], one transfer
        tv1 = self.tracer.now()
        for i, (r, d) in enumerate(rows):
            kd = int(d.shape[-1])
            n_emit = int(packed[i, -1])
            accepted = n_emit - 1
            self.decode_slot_ticks += 1
            self.spec_windows += 1
            self.spec_drafted += kd
            self.spec_accepted += accepted
            # the emitted tokens' KV: window inputs [gen[-1], d_1..d_acc]
            # are committed; the final emitted token is fed next tick; the
            # rejected tail (positions past pos + acc + 1) rolls back
            new_pos = int(self.slot_pos[r.slot]) + accepted + 1
            self.pool.truncate(r.slot, new_pos)
            self.slot_pos[r.slot] = new_pos
            appended = 0
            for t in packed[i, :n_emit]:
                self._append_token(r, t)
                self.decode_tokens_emitted += 1
                appended += 1
                if self._stream_done(r):        # eos / max_new clip only
                    break
            if self.tracer.enabled:
                self.tracer.request_span(r.req_id, "verify_window", tv0, tv1,
                                         drafted=kd, accepted=accepted,
                                         emitted=appended)
            if self.drafter is not None:
                self.drafter.observe(r.slot, r.req_id,
                                     self._effective_len(r))
            if self._finished(r):
                self._retire(r)

    def _plan_speculation(self, active: List[Request]
                          ) -> Tuple[List[Tuple[Request, np.ndarray]],
                                     List[Request]]:
        """Partition this tick's decode occupants into verify rows (the
        drafter proposed something usable, window pages secured, shared
        pages COW'd) and plain one-token decoders (everything else)."""
        budgets: Dict[int, int] = {}
        plain: List[Request] = []
        candidates: List[Request] = []
        for r in sorted(active, key=lambda r: r.req_id):
            budgets[r.req_id] = self._spec_budget(r)
            (candidates if budgets[r.req_id] >= 1 else plain).append(r)
        proposals = self.drafter.propose_batch(
            [(r.slot, r.req_id, self._effective_tokens(r))
             for r in candidates],
            self.spec.k) if candidates else {}
        rows: List[Tuple[Request, np.ndarray]] = []
        for r in candidates:
            d = proposals.get(r.slot)
            kd = min(budgets[r.req_id], int(d.shape[-1])) \
                if d is not None else 0
            if kd < 1:
                plain.append(r)
                continue
            d = np.asarray(d[:kd], np.int32)
            pos = int(self.slot_pos[r.slot])
            if not self.pool.grow(r.slot, pos + kd + 1):
                plain.append(r)                     # raced: fall back
                continue
            if not self._ensure_writable(r.slot, pos, pos + kd + 1):
                self.pool.shrink(r.slot, pos)       # roll the claim back
                plain.append(r)
                continue
            rows.append((r, d))
        return rows, plain

    def _run_decode_tick(self, plan: TickPlan) -> None:
        reqs = self._by_id()
        active = [reqs[rid] for rid in plan.decode_reqs
                  if rid in reqs and reqs[rid].state == RequestState.DECODING]
        if self.spec is not None and active:
            rows, active = self._plan_speculation(active)
            if rows:
                with self.tracer.span("serve.verify"):
                    self._run_verify_tick(plan, rows)
        if not active:
            return
        with self.tracer.span("serve.decode.prepare"):
            if self.paged:
                # each decode write may cross into a fresh page (or,
                # shared-prefix, into a page another request still reads
                # — COW).  Grow oldest-first; when the pool is out,
                # reclaim CACHED pages LRU-first, and only if the cache
                # cannot help PREEMPT the youngest page holder — its pages
                # come back, it re-queues for recompute
                survivors = []
                for r in sorted(active, key=lambda r: r.req_id):
                    if r.state != RequestState.DECODING or r.slot < 0:
                        continue                # evicted earlier this loop
                    if self._grow_for_decode(r):
                        survivors.append(r)
                active = survivors
            if active:
                call, emitted = self._decode_call(active)
        if not active:
            return
        td0 = self.tracer.now()
        with self.tracer.span("serve.decode.launch"):
            toks = self._launch(plan.decode_group, call)
        with self.tracer.span("serve.decode.sync"):
            sampled = self._to_host(toks)           # one transfer per tick
        td1 = self.tracer.now()
        for row, r in emitted:
            self._append_token(r, sampled[row])
            # occupancy is counted at emission, not at planning: a request
            # preempted by its own growth failure emitted nothing and must
            # not drag tokens_per_tick below the non-speculative 1.0 floor
            self.decode_tokens_emitted += 1
            self.decode_slot_ticks += 1
            self.slot_pos[r.slot] += 1
            if self.tracer.enabled:
                self.tracer.request_span(r.req_id, "decode", td0, td1,
                                         tokens=1)
            if self._finished(r):
                self._retire(r)

    def _decode_call(self, active: List[Request]
                     ) -> Tuple[_Call, List[Tuple[int, Request]]]:
        """The decode step's call, and which row of its tokens each
        request reads."""
        B = self.sc.max_batch
        if self.paged:
            # the page pool addresses KV through the CALL's block tables,
            # not the batch row, so the decode batch compacts: active
            # slots map to rows 0..len(active) and the row count rounds
            # up the pow2 bucket ladder — a lone straggler decodes at
            # batch 1, not max_batch, with at most log2(B)+1 shapes
            nb = _bucket(len(active), B)
            if self.cfg.n_codebooks > 1:
                tokens = np.zeros((nb, self.cfg.n_codebooks, 1), np.int32)
            else:
                tokens = np.zeros((nb, 1), np.int32)
            pos = np.zeros((nb,), np.int32)
            for i, r in enumerate(active):
                tokens[i, ..., 0] = r.generated[-1]
                pos[i] = self.slot_pos[r.slot]
            pp, all_greedy = self._pack_params(
                [(i, r) for i, r in enumerate(active)], nb)
            # pad rows carry all-sentinel block-table rows: their scatters
            # drop — the paged analogue of the dense slot_mask
            tables = self.pool.block_tables(rows=[r.slot for r in active],
                                            n=nb)
            return (_Call("decode_paged", (nb,), (jnp.asarray(tokens),),
                          (jnp.asarray(pos), tables, *pp), all_greedy),
                    list(enumerate(active)))
        # the dense arena is slot-indexed, so the batch stays [B]
        if self.cfg.n_codebooks > 1:
            tokens = np.zeros((B, self.cfg.n_codebooks, 1), np.int32)
        else:
            tokens = np.zeros((B, 1), np.int32)
        mask = np.zeros((B,), bool)
        for r in active:
            tokens[r.slot, ..., 0] = r.generated[-1]
            mask[r.slot] = True
        # ragged decode: per-slot positions (vector pos -> per-slot
        # rope, per-slot cache write index, per-slot validity mask)
        pos = np.where(self.slot_pos >= 0,
                       self.slot_pos, 0).astype(np.int32)
        pp, all_greedy = self._pack_params([(r.slot, r) for r in active], B)
        return (_Call("decode", (B,), (jnp.asarray(tokens),),
                      (jnp.asarray(pos), jnp.asarray(mask), *pp), all_greedy),
                [(r.slot, r) for r in active])

    # -- tick loop ---------------------------------------------------------------
    def step(self) -> List[RequestOutput]:
        """One engine tick: plan (scheduler) -> execute -> report.

        Returns one incremental ``RequestOutput`` per request that
        ADVANCED this tick — new tokens appended, and/or the request
        finished — ordered by req_id.  A preempted request whose tokens
        are unchanged emits nothing (preemption keeps its generated
        tokens; recompute-on-resume replays no draws), but one that
        GAINED tokens earlier in the same tick still reports them; an
        ``abort()`` between ticks returns its terminal output directly
        from ``abort``.

        The tick runs in the ``serve.step`` span; its phases in
        ``serve.admit``, ``serve.plan``, the prepare/launch/sync spans of
        each phase program, and ``serve.emit`` (``Tracer.span``)."""
        with self.tracer.span("serve.step") as span:
            t0 = time.monotonic()
            self.executor.begin_tick()
            self._prefill_progress = False
            with self.tracer.span("serve.admit"):
                # deferred submits re-enter as soon as the backlog has
                # room — BEFORE this tick's admission so they compete for
                # freed slots
                self._reconsider_deferred()
                # snapshot for incremental outputs: every request that can
                # gain tokens this tick is in the queue or a slot right now
                counts0 = {r.req_id: len(r.generated) for r in self.queue}
                counts0.update({r.req_id: len(r.generated)
                                for r in self.slot_req if r is not None})
                done0 = len(self.done)
                self._admit()
            with self.tracer.span("serve.plan"):
                plan = self._plan_tick()
            if plan.prefill_chunks:
                self._run_prefill_tick(plan)
            if plan.decode_reqs:
                self._run_decode_tick(plan)
            with self.tracer.span("serve.emit"):
                if (self.paged and not plan.decode_reqs
                        and not self._prefill_progress):
                    self._break_prefill_stall()
                rec = self._record_tick(plan, t0)
                outputs = self._tick_outputs(counts0, done0)
        if self.tracer.enabled:
            # the tick event spans serve.step exactly, so the two nest
            self._trace_tick(span.t0, span.t1, rec)
        return outputs

    def _plan_tick(self) -> TickPlan:
        # entries carry (priority, TTFT deadline) so plan_tick can order
        # the prefill budget SLO-aware: class first, then EDF, then age
        # (FIFO) — for deadline-free requests this is the original pure
        # age order, where under page contention the oldest request gets
        # the budget/pages first (slot order would starve older requests
        # behind a recycled low slot and thrash the pool)
        prefilling = sorted(
            ((r.req_id, self._effective_len(r) - r.prefill_pos,
              self.chunked, r.prefill_pos, r.priority, r.ttft_deadline_s)
             for r in self.slot_req
             if r is not None and r.state == RequestState.PREFILLING),
            key=lambda e: e[0])
        decoding = [r.req_id for r in self.slot_req
                    if r is not None and r.state == RequestState.DECODING]
        spec_k = self.spec.k if self.spec is not None else 0
        if not self.paged:
            return self.scheduler.plan_tick(prefilling, decoding,
                                            spec_k=spec_k)
        # token-level admission: prefill work is planned against the
        # pool's free pages, with this tick's decode growth reserved (a
        # speculative verify window grows by up to k+1 tokens — the
        # windows are charged like mini prefill chunks)
        headroom = self.pool.headroom_pages(
            [self.pool.len_of(r.slot) for r in self.slot_req
             if r is not None and r.state == RequestState.DECODING],
            growth=spec_k + 1)
        return self.scheduler.plan_tick(
            prefilling, decoding, free_pages=headroom,
            page_size=self.sc.page_size,
            capacity=self.pool.widest_capacity(), spec_k=spec_k)

    def _record_tick(self, plan: TickPlan, t0: float) -> TickRecord:
        """Append the tick's ``TickRecord`` and fold it into the counters
        and the tick-cost EMA."""
        resident = self.pool.resident_bytes() if self.paged else 0
        self.kv_resident_peak = max(self.kv_resident_peak, resident)
        # per-tick counters are registry DELTAS against the previous
        # record's baseline — preemption/spec/swap totals have exactly one
        # home (the registry), each TickRecord reports what accrued since
        # the last one, and Σ tick_log.<field> == the lifetime counter
        # even for movement BETWEEN ticks (e.g. a caller-driven preempt)
        cur = self.metrics.values(self._TICK_DELTA_KEYS)
        delta = {k: cur[k] - self._tick_delta_base[k] for k in cur}
        self._tick_delta_base = cur
        rec = TickRecord(
            index=self._n_ticks,
            prefill_reqs=list(plan.prefill_reqs),
            prefill_tokens=plan.prefill_tokens,
            decode_reqs=list(plan.decode_reqs),
            prefill_group=plan.prefill_group,
            decode_group=plan.decode_group,
            wall_s=time.monotonic() - t0,
            preemptions=int(delta["serving_preemptions_total"]),
            kv_resident_bytes=resident,
            spec_drafted=int(delta["serving_spec_drafted_total"]),
            spec_accepted=int(delta["serving_spec_accepted_total"]),
            new_compiles=self.executor.tick_new_compiles,
            migrated_pages=self.executor.tick_migrated_pages,
            migrated_bytes=self.executor.tick_migrated_bytes,
            swap_out_bytes=int(delta["serving_swap_out_bytes_total"]),
            swap_in_bytes=int(delta["serving_swap_in_bytes_total"]),
            host_resident_pages=(self.host_tier.used_pages()
                                 if self.host_tier is not None else 0))
        self.metrics.observe("serving_tick_wall_seconds", rec.wall_s)
        # tick-cost EMA for admission TTFT projections: alpha 0.2 tracks a
        # load shift within ~5 ticks.  Ticks that compiled a new phase
        # program are excluded outright — a compile stall is paid once per
        # shape, not per tick, and folding even one multi-second compile
        # into the EMA would have admission shedding everything on a
        # near-idle engine until the average decays
        if rec.new_compiles == 0:
            self._tick_wall_n += 1
            self._tick_wall_ema = (rec.wall_s if self._tick_wall_n == 1 else
                                   0.8 * self._tick_wall_ema
                                   + 0.2 * rec.wall_s)
        self.tick_log.append(rec)
        self._n_ticks += 1
        self._n_prefill_ticks += bool(rec.prefill_reqs)
        self._n_decode_ticks += bool(rec.decode_reqs)
        self._n_mixed_ticks += rec.mixed
        return rec

    def _tick_outputs(self, counts0: Dict[int, int],
                      done0: int) -> List[RequestOutput]:
        # incremental outputs: live slot holders + requests retired this
        # tick + requests preempted BACK TO THE QUEUE after gaining tokens
        # earlier in the same tick (a growth victim whose prefill had just
        # completed: its seeding token must not vanish from the stream),
        # diffed against the entry snapshot
        touched = [r for r in self.slot_req if r is not None]
        touched += self.done[done0:]
        touched += [r for r in self.queue
                    if len(r.generated) > counts0.get(r.req_id, 0)]
        outputs: List[RequestOutput] = []
        for r in sorted(touched, key=lambda r: r.req_id):
            n0 = counts0.get(r.req_id, 0)
            finished = r.state == RequestState.DONE
            if len(r.generated) > n0 or finished:
                outputs.append(RequestOutput(
                    req_id=r.req_id,
                    new_token_ids=list(r.generated[n0:]),
                    n_generated=len(r.generated),
                    finished=finished,
                    finish_reason=r.finish_reason if finished else None))
        return outputs

    def _trace_tick(self, t0: float, t1: float, rec: TickRecord) -> None:
        # the TickRecord twin: every rec counter appears as a tick-span
        # arg, so summing an arg across the tick track reproduces the
        # registry total (the conservation law the tests pin)
        self.tracer.tick_span(
            t0, t1, index=rec.index,
            prefill_reqs=list(rec.prefill_reqs),
            prefill_tokens=rec.prefill_tokens,
            decode_reqs=list(rec.decode_reqs),
            prefill_group=rec.prefill_group or "",
            decode_group=rec.decode_group or "",
            preemptions=rec.preemptions,
            kv_resident_bytes=rec.kv_resident_bytes,
            spec_drafted=rec.spec_drafted,
            spec_accepted=rec.spec_accepted,
            new_compiles=rec.new_compiles,
            migrated_pages=rec.migrated_pages,
            migrated_bytes=rec.migrated_bytes,
            swap_out_bytes=rec.swap_out_bytes,
            swap_in_bytes=rec.swap_in_bytes,
            host_resident_pages=rec.host_resident_pages)

    def counts(self) -> Dict[str, int]:
        """Queue/slot/done occupancy (the old ``step()`` return value),
        plus the lifetime migration / tiered-KV counters and SLO
        attainment — every value is a view over the metrics registry
        (or derived from one), never a second copy."""
        g = self.goodput()
        return {"queued": len(self.queue),
                "deferred": len(self.deferred),
                "active": sum(r is not None for r in self.slot_req),
                "done": len(self.done),
                "shed": self.admission_shed,
                "migrated_pages": self.executor.migrated_pages,
                "migrated_bytes": self.executor.migrated_bytes,
                "swap_out_bytes": (self.host_tier.swap_out_bytes
                                   if self.host_tier is not None else 0),
                "swap_in_bytes": (self.host_tier.swap_in_bytes
                                  if self.host_tier is not None else 0),
                "swap_resumes": self.swap_resumes,
                "recompute_preemptions": self.recompute_preemptions,
                "host_resident_pages": (self.host_tier.used_pages()
                                        if self.host_tier is not None else 0),
                "slo_total": g["slo_total"],
                "slo_attained": g["slo_attained"],
                "goodput": g["goodput"]}

    def goodput(self) -> Dict[str, float]:
        """SLO attainment over retired requests submitted with deadlines
        (``submit(..., slo=SLO(...))``); aborted requests are excluded.

        ``goodput`` is the attained fraction — 1.0 vacuously when no
        request carried an SLO, so SLO-free runs read as unconstrained
        rather than failing.  The per-axis violation counts say WHICH
        deadline was missed (a request can violate both)."""
        m = self.metrics
        total = int(m.counter("serving_slo_requests_total"))
        attained = int(m.counter("serving_slo_attained_total"))
        return {
            "slo_total": total,
            "slo_attained": attained,
            "ttft_violations":
                int(m.counter("serving_slo_ttft_violations_total")),
            "tpot_violations":
                int(m.counter("serving_slo_tpot_violations_total")),
            "goodput": attained / total if total else 1.0,
        }

    def metrics_snapshot(self) -> Dict[str, Dict]:
        """The full registry snapshot (counters / gauges / histograms)
        with the point-in-time occupancy gauges refreshed first — the
        machine-readable superset of ``counts()`` / ``spec_stats()`` /
        ``prefix_stats()``."""
        m = self.metrics
        m.set_gauge("serving_requests_queued", len(self.queue))
        m.set_gauge("serving_requests_active",
                    sum(r is not None for r in self.slot_req))
        m.set_gauge("serving_requests_done", len(self.done))
        m.set_gauge("serving_kv_resident_bytes",
                    self.pool.resident_bytes() if self.paged else 0)
        m.set_gauge("serving_host_resident_pages",
                    self.host_tier.used_pages()
                    if self.host_tier is not None else 0)
        return m.snapshot()

    def _check_drained(self, ticks: int, max_ticks: int) -> None:
        """Fail LOUDLY when the tick budget runs out with live requests —
        a silent partial drain poisons every downstream comparison.  The
        message carries the counts() snapshot, the per-state request
        breakdown, and the last TickRecord so a stuck engine is
        diagnosable from the exception alone.  Admission-control
        outcomes appear as their OWN buckets: ``deferred`` (parked
        outside the queue, still owed service) and the ``shed`` tally —
        an admission stall must read differently from a scheduling
        stall of live queued requests."""
        if ticks >= max_ticks and (
                self.queue or self.deferred
                or any(r is not None for r in self.slot_req)):
            c = self.counts()
            states: Dict[str, int] = {}
            for r in list(self.queue) + [r for r in self.slot_req
                                         if r is not None]:
                states[r.state.value] = states.get(r.state.value, 0) + 1
            if self.deferred:
                # WAITING but not in the queue — their own bucket, not
                # lumped into "waiting"
                states["deferred"] = len(self.deferred)
            last = self.tick_log[-1] if self.tick_log else None
            raise RuntimeError(
                f"max_ticks={max_ticks} exhausted with live requests "
                f"({c['queued']} queued, {c['deferred']} deferred, "
                f"{c['active']} active, {c['done']} done of which "
                f"{c['shed']} shed; states={states}, "
                f"preemptions={self.preemptions}) — the engine did not "
                f"drain; raise max_ticks or check for a scheduling stall. "
                f"counts={c} last_tick={last}")

    def _live(self) -> bool:
        """Requests still owed service: queued, deferred, or in a slot."""
        return bool(self.queue or self.deferred
                    or any(r is not None for r in self.slot_req))

    def run_until_drained(self, max_ticks: int = 10_000) -> List[Request]:
        ticks = 0
        while self._live() and ticks < max_ticks:
            self.step()
            ticks += 1
        self._check_drained(ticks, max_ticks)
        return self.done

    def stream(self, max_ticks: int = 10_000) -> Iterator[RequestOutput]:
        """Run the tick loop, yielding each ``RequestOutput`` as soon as
        its tick produced it — tokens are observable while OTHER requests
        are still prefilling or decoding.  ``submit()`` and ``abort()``
        may be called from the consuming loop (an abort's terminal output
        is returned by ``abort`` itself, not re-yielded here)."""
        ticks = 0
        while self._live() and ticks < max_ticks:
            yield from self.step()
            ticks += 1
        self._check_drained(ticks, max_ticks)

    def generate(self, prompts: Sequence[np.ndarray],
                 sampling: Union[SamplingParams, Sequence[SamplingParams],
                                 None] = None,
                 max_ticks: int = 10_000) -> List[Request]:
        """Batch facade: submit every prompt (one shared ``SamplingParams``
        or one per prompt), drain, and return the finished ``Request``s in
        submission order."""
        if sampling is None or isinstance(sampling, SamplingParams):
            sampling = [sampling] * len(prompts)
        if len(sampling) != len(prompts):
            raise ValueError(f"got {len(list(sampling))} SamplingParams for "
                             f"{len(prompts)} prompts")
        reqs = [self.submit(p, sampling=sp)
                for p, sp in zip(prompts, sampling)]
        self.run_until_drained(max_ticks)
        return reqs

    # -- metrics ------------------------------------------------------------------
    @property
    def n_ticks(self) -> int:
        """Lifetime tick count (``tick_log`` itself is bounded)."""
        return self._n_ticks

    def kv_bytes(self) -> Dict[str, int]:
        """KV memory accounting, dense-vs-paged comparable.

        ``reserved``: bytes the arena pins for its lifetime.  ``resident``:
        bytes actually backing live tokens right now (== reserved for the
        dense arena — that is the point); ``peak_resident``: high-water
        mark across ticks."""
        if self.paged:
            return {"reserved": self.pool.total_bytes(),
                    "resident": self.pool.resident_bytes(),
                    "peak_resident": self.kv_resident_peak}
        return {"reserved": self._dense_kv_bytes,
                "resident": self._dense_kv_bytes,
                "peak_resident": self._dense_kv_bytes}

    def prefix_stats(self) -> Dict[str, float]:
        """Prefix-cache effectiveness: hit rate, tokens served from cache
        vs prefill tokens actually computed, COW copies, evictions.
        Zeros when the cache is off (the comparison baseline)."""
        out = {
            "prefill_tokens_executed": float(self.prefill_tokens_executed),
            "cow_copies": float(self.cow_copies),
            "cache_evicted_pages": float(self.cache_evicted_pages),
            "hit_rate": 0.0,
            "hit_tokens": 0.0,
            "cached_pages": 0.0,
        }
        if self.prefix is not None:
            s = self.prefix.stats()
            out["hit_rate"] = float(s["hit_rate"])
            out["hit_tokens"] = float(s["hit_tokens"])
            out["cached_pages"] = float(s["cached_pages"])
        return out

    def spec_stats(self) -> Dict[str, float]:
        """Speculative-decoding effectiveness.

        ``acceptance_rate``: accepted / drafted (the drafter-quality
        number k should be tuned against); ``tokens_per_tick``: tokens
        emitted per (request, decode-tick) occupancy — 1.0 exactly for
        non-speculative decode, > 1 as soon as any draft survives
        verification.  Zeros/1.0 when speculation is off (the comparison
        baseline)."""
        return {
            "windows": float(self.spec_windows),
            "drafted": float(self.spec_drafted),
            "accepted": float(self.spec_accepted),
            "acceptance_rate": self.spec_accepted / max(self.spec_drafted, 1),
            "decode_tokens": float(self.decode_tokens_emitted),
            "tokens_per_tick": (self.decode_tokens_emitted
                                / max(self.decode_slot_ticks, 1)),
        }

    def phase_occupancy(self) -> Dict[str, float]:
        """Fractions of ticks running prefill / decode / both (interleave).

        Computed from running counters, so the numbers cover the engine's
        whole lifetime even after ``tick_log`` (bounded) has rotated."""
        n = max(self._n_ticks, 1)
        return {
            "prefill": self._n_prefill_ticks / n,
            "decode": self._n_decode_ticks / n,
            "mixed": self._n_mixed_ticks / n,
        }
