"""Executor layer: the jitted program table, compile counting, and device
placement for the serving engine.

The EngineCore (``serving/engine.py``) is host-only — it plans ticks,
packs batches, and keeps request/page accounting.  Everything that
touches a compiled executable lives HERE, keyed by (worker group, phase
kind).  Two placements:

* ``ColocatedExecutor`` — every program runs wherever jax would put it
  (one device group; the default, and exactly the pre-split behavior).
  The (group, kind) keying still simulates phase disaggregation — the
  strategy table routes each phase to a distinct jit instance — but
  no KV ownership ever moves.
* ``DisaggregatedExecutor`` — the HALO shape: prefill-side programs
  (chunk / whole / packed / speculative verify: the CiM-analogue GEMM
  phases) are pinned to the PREFILL device group and decode programs
  (the CiD-analogue GEMV phase) to the DECODE group
  (``launch/mesh.phase_device_groups``).  At each prefill -> decode
  handoff the engine reports the request's freshly-filled KV pages via
  ``record_handoff``; the executor accounts them as pages/bytes crossing
  the 2.5D interposer link — batched per tick (one link transaction per
  tick, however many requests finished prefilling in it).  Each phase
  program runs on the FIRST device of its group, with its inputs placed
  there explicitly: the parameters are copied to each phase device once,
  and any other input that lives elsewhere (the whole KV pool, after the
  other phase last wrote it) is moved and counted in ``moved_bytes``.
  The pool is one array, so a phase switch moves all of it, not only the
  handed-off pages.  On a single-device host both groups resolve to the
  same device and nothing moves, so greedy streams are bit-identical
  colocated vs disaggregated BY CONSTRUCTION — the programs, batches,
  and sampling are the same; only placement and ownership accounting
  differ.

Compile counting also lives here: every phase call notes its (group,
kind, bucketed shape, all_greedy) key and a first sighting counts as a
compile — the recompile-stall guarantee serving_bench asserts on (a
second wave of the same traffic adds ZERO keys) is an executor
property, not an engine one.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from repro.launch.mesh import phase_device_groups
from repro.serving.metrics import MetricsRegistry, counter_attr


class Executor:
    """Base executor: program table + compile accounting, no placement.

    ``impls`` maps phase kind -> the (unjitted) program body; the engine
    passes its bound ``_*_impl`` methods.  Each (group, kind) pair
    becomes a SEPARATE jit instance — the software analogue of phase
    disaggregation: on a cluster these are distinct executables resident
    on different worker pools, and the strategy table routes each phase
    to one of them.
    """

    # kind -> (donated cache argnum, static all_greedy argnum).  The cache
    # argument is donated: the engine rebinds ``self.cache`` to each
    # program's output, so XLA updates the KV arena in place instead of
    # copying it.  ``all_greedy`` is STATIC: an all-greedy tick compiles
    # to plain argmax with no sort/PRNG work, a mixed tick compiles the
    # per-row path — at most two specializations per program.
    KIND_ARGS: Dict[str, Tuple[int, int]] = {
        "chunk": (5, 11),           # packed chunked prefill (dense arena)
        "whole": (3, 9),            # whole-prompt prefill (SSM / hybrid)
        "decode": (2, 10),          # one-token batched step (dense)
        "chunk_paged": (5, 12),     # chunked prefill into the page pool
        "decode_paged": (2, 10),    # paged flash-decode step
        "packed": (6, 12),          # packed-stream prefill (dense)
        "packed_paged": (6, 13),    # packed-stream prefill (paged)
        "verify": (5, 13),          # speculative verify window
    }
    # phase classification: decode kinds run on the decode (CiD) side,
    # everything else — prefill chunks AND speculative verify windows
    # (k+1-token prefill-shaped GEMMs) — on the prefill (CiM) side
    DECODE_KINDS = frozenset({"decode", "decode_paged"})

    #: True iff KV ownership moves at the prefill -> decode handoff
    #: (the engine consults this before computing handoff footprints)
    migrates_kv: bool = False

    # lifetime counters live in the metrics registry (the engine shares
    # its own via make_executor(..., metrics=...), so counts()/snapshot()
    # and these attributes read the same cells — serving/metrics.py)
    compile_count = counter_attr("serving_compiles_total")
    migrated_pages = counter_attr("serving_migrated_pages_total")
    migrated_bytes = counter_attr("serving_migrated_bytes_total")
    migration_batches = counter_attr("serving_migration_batches_total")

    def __init__(self, impls: Dict[str, Callable], *, mesh=None,
                 metrics: Optional[MetricsRegistry] = None):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.impls = impls
        self.mesh = mesh
        # (group, kind) -> jitted program; built lazily so each strategy
        # only compiles the programs its groups actually execute
        self.programs: Dict[Tuple[str, str], Callable] = {}
        self._compile_keys: set = set()
        self.compile_count = 0           # distinct phase-program shapes
        self.tick_new_compiles = 0
        # migration counters (stay 0 forever on the colocated executor)
        self.migrated_pages = 0          # KV pages moved prefill -> decode
        self.migrated_bytes = 0          # the 2.5D-link byte analogue
        self.migration_batches = 0       # ticks with >= 1 handoff
        self.tick_migrated_pages = 0
        self.tick_migrated_bytes = 0

    # -- placement -------------------------------------------------------------
    def phase_of(self, kind: str) -> str:
        return "decode" if kind in self.DECODE_KINDS else "prefill"

    def device_for(self, kind: str):
        """Device the (jitted) programs of ``kind`` are pinned to, or None
        for jax's default placement (colocated)."""
        return None

    # -- program table ---------------------------------------------------------
    def program(self, group: str, kind: str) -> Callable:
        """Jitted program for (worker group, phase kind), built on first
        use and pinned to ``device_for(kind)`` when the executor places
        phases on separate device groups."""
        key = (group, kind)
        if key not in self.programs:
            cache_arg, static_arg = self.KIND_ARGS[kind]
            fn = jax.jit(self.impls[kind], donate_argnums=(cache_arg,),
                         static_argnums=(static_arg,))
            dev = self.device_for(kind)
            if dev is not None:
                fn = self._placed(fn, dev)
            self.programs[key] = fn
        return self.programs[key]

    # -- compile accounting ----------------------------------------------------
    def note_compile(self, group: str, kind: str, shape: Tuple[int, ...],
                     all_greedy: bool) -> None:
        """Record one phase-program call's compilation key.

        jit retraces on every new input-shape signature; with the pow2
        buckets each phase has a small closed key set, so after warmup
        every key is a cache hit.  The counter is what serving_bench and
        the tier-2 smoke assert on: a second pass of the same traffic mix
        must add ZERO new compiles — the recompile-stall guarantee the
        bucket ladder exists to provide."""
        key = (group, kind, shape, bool(all_greedy))
        if key not in self._compile_keys:
            self._compile_keys.add(key)
            self.compile_count += 1
            self.tick_new_compiles += 1

    # -- per-tick bookkeeping --------------------------------------------------
    def begin_tick(self) -> None:
        self.tick_new_compiles = 0
        self.tick_migrated_pages = 0
        self.tick_migrated_bytes = 0

    def record_handoff(self, pages: int, nbytes: int) -> None:
        """One request's prefill -> decode KV handoff (colocated: no
        ownership moves, nothing to record)."""

    def stats(self) -> Dict[str, int]:
        return {
            "compile_count": self.compile_count,
            "migrated_pages": self.migrated_pages,
            "migrated_bytes": self.migrated_bytes,
            "migration_batches": self.migration_batches,
        }


class ColocatedExecutor(Executor):
    """Default placement: one device group runs every program."""


class DisaggregatedExecutor(Executor):
    """Prefill programs pinned to the prefill device group, decode
    programs to the decode group, with KV page ownership migrating at the
    prefill -> decode handoff (batched per tick — HALO's 2.5D link).

    ``devices`` overrides the (prefill_group, decode_group) split; by
    default ``phase_device_groups()`` halves ``jax.devices()`` (a
    single-device host shares the one device between both groups, which
    keeps streams bit-identical while the ownership accounting — the
    quantity under study — still runs for real)."""

    migrates_kv = True
    # device-to-device bytes the placement moved (parameter copies + pool
    # moves + small per-call inputs made on the default device)
    moved_bytes = counter_attr("serving_placement_moved_bytes_total")

    def __init__(self, impls: Dict[str, Callable], *, mesh=None,
                 metrics: Optional[MetricsRegistry] = None,
                 devices: Optional[Tuple[List[Any], List[Any]]] = None):
        super().__init__(impls, mesh=mesh, metrics=metrics)
        groups = devices if devices is not None else phase_device_groups()
        self.prefill_devices, self.decode_devices = groups
        self.moved_bytes = 0
        self.tick_moved_bytes = 0
        self.max_tick_moved_bytes = 0
        # device -> (params object, its copy on that device)
        self._params_on: Dict[Any, Tuple[Any, Any]] = {}

    def device_for(self, kind: str):
        group = (self.decode_devices if self.phase_of(kind) == "decode"
                 else self.prefill_devices)
        return group[0] if group else None

    def _put(self, tree, dev):
        """``tree`` with every array leaf that is not on ``dev`` alone
        moved there (committed), its bytes counted."""
        def put(x):
            if isinstance(x, jax.Array) and x.devices() != {dev}:
                self.tick_moved_bytes += x.nbytes
                self.moved_bytes += x.nbytes
                self.max_tick_moved_bytes = max(self.max_tick_moved_bytes,
                                                self.tick_moved_bytes)
                return jax.device_put(x, dev)
            return x
        return jax.tree.map(put, tree)

    def _placed(self, fn: Callable, dev) -> Callable:
        """Run ``fn(params, *rest)`` on ``dev`` with its inputs placed
        there explicitly.  Left to ``jax.default_device``, uncommitted
        inputs would follow the default device silently; here the
        parameters are copied to ``dev`` once and reused, and every other
        input that lives elsewhere is moved and counted."""
        def run(params, *rest):
            held = self._params_on.get(dev)
            if held is None or held[0] is not params:
                held = (params, self._put(params, dev))
                self._params_on[dev] = held
            return fn(held[1], *self._put(rest, dev))
        return run

    def begin_tick(self) -> None:
        super().begin_tick()
        self.tick_moved_bytes = 0

    def record_handoff(self, pages: int, nbytes: int) -> None:
        if pages <= 0 and nbytes <= 0:
            return
        if not self.tick_migrated_pages and not self.tick_migrated_bytes:
            self.migration_batches += 1      # first handoff this tick
        self.tick_migrated_pages += pages
        self.tick_migrated_bytes += nbytes
        self.migrated_pages += pages
        self.migrated_bytes += nbytes

    def stats(self) -> Dict[str, int]:
        out = super().stats()
        out["prefill_devices"] = len(self.prefill_devices)
        out["decode_devices"] = len(self.decode_devices)
        out["moved_bytes"] = self.moved_bytes
        out["max_tick_moved_bytes"] = self.max_tick_moved_bytes
        return out


def make_executor(name: str, impls: Dict[str, Callable], *, mesh=None,
                  metrics: Optional[MetricsRegistry] = None) -> Executor:
    """ServeConfig.executor -> Executor instance."""
    if name == "colocated":
        return ColocatedExecutor(impls, mesh=mesh, metrics=metrics)
    if name == "disaggregated":
        return DisaggregatedExecutor(impls, mesh=mesh, metrics=metrics)
    raise ValueError(f"executor={name!r} (expected 'colocated' or "
                     "'disaggregated')")


__all__ = [
    "ColocatedExecutor",
    "DisaggregatedExecutor",
    "Executor",
    "make_executor",
]
