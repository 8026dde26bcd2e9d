"""Paged KV arena: a fixed block pool replaces the dense per-slot cache.

The dense arena allocates ``[L, max_batch, max_len, Hkv, Dh]`` per attention
run — memory scales with ``max_batch x max_len`` whatever the actual
lengths, and no prompt may exceed ``max_len``.  The paged arena instead
allocates a fixed pool of ``n_pages`` pages of ``page_size`` tokens per run
(``[L, n_pages, page_size, Hkv, Dh]``) and maps each slot's logical
positions onto physical pages through a per-slot block table.  Capacity is
then a POOL property, not a slot property: the same pool serves one
16k-token request or eight 2k-token requests, and the scheduler admits
prefill work token-by-token against the free-page count (see
``PhaseScheduler.plan_tick``) while the engine preempts the youngest
request when decode outgrows the pool.

HALO reading: a page is a contiguous CiD row burst — the block table is
the bank/row decoder, so the GEMV sweep still streams whole rows (bank
locality) while placement becomes fully dynamic.  See docs/serving.md.

Two layers:

* ``PagePool`` — pure host-side accounting for ONE pool: free list,
  per-slot block tables, per-page REFCOUNTS, grow/shrink/release plus
  attach (share another owner's pages), retain/release_ref (external —
  prefix-cache — references) and copy-on-write.  No jax; property-testable
  (refcounts are conserved, no page is freed while referenced, COW never
  leaves a writer aliasing a shared page).
* ``KVPool`` — one ``PagePool`` + device page arrays per attention run of
  the model plan, ring/MLA-aware via ``cache_len``: a sliding-window run
  pools only its ring of ``min(window, capacity)`` logical entries, an MLA
  run pools latent rows ``[L, n_pages, page_size, r+dr]``.  With
  ``kv_dtype="int8"`` GQA runs store int8 pages with per-token f32 scales
  riding in a parallel page array (same block table), and MLA runs store
  int8 latent pages with a per-token ``latent_scale`` page array.  With
  ``kv_dtype="int4"`` GQA pages pack two nibbles per byte (uint8
  ``[..., Dh//2]``, see quantized_cache.pack_int4) for a 4x resident-KV
  reduction vs f32; MLA latents are already rank-compressed, so int4
  falls back to the int8 latent layout there (halving is the floor the
  rmsnorm-sensitive latents tolerate).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.transformer import build_plan, cache_len
from repro.serving.metrics import MetricsRegistry, counter_attr
from repro.serving.scheduler import pages_for


class PagePool:
    """Host-side page accounting for one fixed pool of ``n_pages`` pages.

    Tracks, per slot: the logical length and the block table row mapping
    logical page ``i`` to a physical page (the sentinel ``n_pages`` means
    "never allocated" — device scatters through it drop, gathers clamp and
    mask).  Pages carry a REFCOUNT: a page may back the same logical range
    of several slots at once (shared-prefix reuse) and may additionally be
    pinned by an external holder (the radix prefix cache) via
    ``retain``/``release_ref``.  A page returns to the free list only when
    its last reference drops; a writer about to dirty a shared page must
    go through ``cow`` first.  Pure Python/numpy; every mutation preserves
    the pool invariants (refcount conservation: ref == table references +
    external references; no free-while-referenced; no double assignment)
    that tests/test_kv_pool.py property-checks under arbitrary
    interleavings.
    """

    def __init__(self, n_pages: int, page_size: int, n_slots: int,
                 capacity: int):
        if n_pages < 1 or page_size < 1:
            raise ValueError(f"need n_pages >= 1 and page_size >= 1, got "
                             f"{n_pages}/{page_size}")
        self.n_pages = n_pages
        self.page_size = page_size
        self.n_slots = n_slots
        # logical entries a slot can address (ring length R, or the full
        # pool span for position-indexed runs)
        self.capacity = capacity
        self.width = pages_for(capacity, page_size, capacity)
        self.free: List[int] = list(range(n_pages - 1, -1, -1))
        self.table = np.full((n_slots, self.width), n_pages, np.int32)
        self.lens = np.zeros((n_slots,), np.int64)
        # ref[p] = block-table rows pointing at p + external (cache) holds;
        # external is tracked separately so conservation is checkable
        self.ref = np.zeros((n_pages,), np.int32)
        self.external = np.zeros((n_pages,), np.int32)

    # -- queries ---------------------------------------------------------------
    def pages_of(self, length: int) -> int:
        return pages_for(length, self.page_size, self.capacity)

    def pages_needed(self, slot: int, new_len: int) -> int:
        return max(self.pages_of(new_len) - self.pages_of(int(self.lens[slot])),
                   0)

    def free_pages(self) -> int:
        return len(self.free)

    def used_pages(self) -> int:
        return self.n_pages - len(self.free)

    def is_shared(self, page: int) -> bool:
        """True iff ``page`` has more than one reference (another slot's
        table row, or the prefix cache) — a writer must COW it first."""
        return int(self.ref[page]) > 1

    def rows_touched(self, start: int, end: int) -> List[int]:
        """Block-table rows a write to logical positions [start, end)
        lands in (ring mapping: entry = pos % capacity).  A write range
        spanning the whole ring touches every row."""
        if end - start >= self.capacity:
            return list(range(self.width))
        rows, pos = [], start
        while pos < end:
            e = pos % self.capacity
            rows.append(e // self.page_size)
            # hop to the next page boundary OR the ring wrap, whichever
            # comes first (a ring span that is not a page multiple wraps
            # mid-page: positions on both sides land in different rows)
            pos += min(self.page_size - (e % self.page_size),
                       self.capacity - e)
        return sorted(set(rows))

    # -- mutations ---------------------------------------------------------------
    def _alloc(self) -> int:
        p = self.free.pop()
        assert self.ref[p] == 0, "free page had live references"
        self.ref[p] = 1
        return p

    def _decref(self, page: int) -> None:
        self.ref[page] -= 1
        assert self.ref[page] >= 0, "refcount underflow"
        if self.ref[page] == 0:
            self.free.append(int(page))

    def alloc_external(self) -> Optional[int]:
        """Allocate one free page owned by an EXTERNAL holder from birth
        (the prefix cache's host-tier PROMOTE path: a demoted block's KV
        is uploaded into a page no block table references yet).  The page
        starts at ref=1 external=1 — conservation (``ref == table_refs +
        external``) holds immediately — and frees through the usual
        ``release_ref``.  Returns None when the free list is empty."""
        if not self.free:
            return None
        p = self._alloc()
        self.external[p] += 1
        return p

    def grow(self, slot: int, new_len: int) -> bool:
        """Allocate the pages taking ``slot`` to ``new_len`` logical tokens.
        All-or-nothing: returns False (state unchanged) if the pool cannot
        cover it."""
        cur = int(self.lens[slot])
        if new_len < cur:
            raise ValueError(f"grow: new_len {new_len} < current {cur}")
        have = self.pages_of(cur)
        need = self.pages_of(new_len) - have
        if need > len(self.free):
            return False
        for j in range(need):
            self.table[slot, have + j] = self._alloc()
        self.lens[slot] = new_len
        return True

    def attach(self, slot: int, pages: Sequence[int], new_len: int) -> None:
        """Point an EMPTY slot's leading table rows at existing pages
        (shared-prefix reuse): each page gains a table reference, no page
        is allocated.  ``pages`` must exactly cover ``new_len`` tokens."""
        if int(self.lens[slot]) != 0:
            raise ValueError(f"attach: slot {slot} is not empty "
                             f"(len {int(self.lens[slot])})")
        if len(pages) != self.pages_of(new_len):
            raise ValueError(
                f"attach: {len(pages)} pages cannot back {new_len} tokens "
                f"(need {self.pages_of(new_len)})")
        for i, p in enumerate(pages):
            if not (0 <= p < self.n_pages) or self.ref[p] < 1:
                raise ValueError(f"attach: page {p} is not live")
            self.table[slot, i] = p
            self.ref[p] += 1
        self.lens[slot] = new_len

    def cow(self, slot: int, row: int) -> Optional[tuple]:
        """Copy-on-write the shared page behind ``table[slot, row]``: move
        the row to a freshly-allocated page and drop the old reference.
        Returns (old_page, new_page) for the caller's device copy, None if
        the page was exclusive (nothing to do).  Raises IndexError if the
        free list cannot supply the copy target — callers check
        ``free_pages()`` (or evict) first."""
        old = int(self.table[slot, row])
        if old >= self.n_pages or not self.is_shared(old):
            return None
        if not self.free:
            raise IndexError("cow: no free page for the copy target")
        new = self._alloc()
        self.table[slot, row] = new
        self.ref[old] -= 1              # > 0 by is_shared: never frees here
        return (old, new)

    def shrink(self, slot: int, new_len: int) -> None:
        """Drop the slot's references beyond ``new_len`` (rollback /
        partial free).  A page another slot or the prefix cache still
        references survives; exclusive pages return to the free list."""
        cur = int(self.lens[slot])
        if new_len > cur:
            raise ValueError(f"shrink: new_len {new_len} > current {cur}")
        keep = self.pages_of(new_len)
        for i in range(keep, self.pages_of(cur)):
            self._decref(int(self.table[slot, i]))
            self.table[slot, i] = self.n_pages
        self.lens[slot] = new_len

    def release(self, slot: int) -> None:
        """Drop every reference the slot holds (request done / preempted)."""
        self.shrink(slot, 0)

    # -- external (prefix cache) references ---------------------------------------
    def retain(self, page: int) -> None:
        """Pin a live page from outside the block tables (prefix cache)."""
        if not (0 <= page < self.n_pages) or self.ref[page] < 1:
            raise ValueError(f"retain: page {page} is not live")
        self.ref[page] += 1
        self.external[page] += 1

    def release_ref(self, page: int) -> None:
        """Drop one external reference; frees the page at refcount zero."""
        if self.external[page] < 1:
            raise ValueError(f"release_ref: page {page} has no external ref")
        self.external[page] -= 1
        self._decref(int(page))

    # -- invariants (asserted by the property tests) -----------------------------
    def check_invariants(self) -> None:
        table_refs = np.zeros((self.n_pages,), np.int64)
        for row in self.table:
            for p in row:
                if p < self.n_pages:
                    table_refs[p] += 1
        live = self.ref > 0
        assert (self.ref == table_refs + self.external).all(), \
            "refcount conservation violated (ref != table + external)"
        assert not (set(np.nonzero(live)[0].tolist()) & set(self.free)), \
            "page both referenced and free"
        assert len(self.free) == int((~live).sum()), \
            "free list does not match zero-ref pages"
        assert len(set(self.free)) == len(self.free), "free list duplicates"
        for s in range(self.n_slots):
            assert self.pages_of(int(self.lens[s])) == int(
                (self.table[s] < self.n_pages).sum()), "table/len mismatch"


class KVPool:
    """Device page arrays + per-run ``PagePool`` accounting for a model.

    ``caches`` is a list aligned with ``build_plan(cfg)`` — the paged
    analogue of ``init_cache`` — and is meant to be threaded through the
    engine's donated jitted programs exactly like the dense arena.  The
    block tables stay host-side (numpy) and are shipped per call.
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, n_pages: int,
                 page_size: int, kv_dtype: str = "f32"):
        plan = build_plan(cfg)
        if not all(run.kind == "attn" for run in plan):
            raise ValueError(
                "paged KV arena requires an all-attention plan (GQA / "
                "sliding-window / MLA); SSM and shared-attention runs carry "
                f"recurrent state — got kinds {[r.kind for r in plan]}")
        if kv_dtype not in ("f32", "int8", "int4"):
            raise ValueError(f"kv_dtype must be 'f32', 'int8' or 'int4', "
                             f"got {kv_dtype!r}")
        if (kv_dtype == "int4" and not cfg.mla.enabled
                and cfg.d_head % 2):
            raise ValueError(f"kv_dtype='int4' packs head-dim pairs; "
                             f"d_head={cfg.d_head} is odd")
        self.cfg = cfg
        self.n_slots = n_slots
        self.n_pages = n_pages
        self.page_size = page_size
        self.kv_dtype = kv_dtype
        # a position-indexed (full-attention / MLA) run can address the
        # whole pool from one slot: that IS the new length bound
        self.capacity = n_pages * page_size
        # ... but a plan whose every run is a ring (all-sliding-window)
        # bounds nothing: rings reuse their pages forever, so sequence
        # length is unlimited (the scheduler's ring-clamped page charge
        # and the decode kernels' pos % R addressing both already handle
        # arbitrary positions)
        self.length_bound = (self.capacity
                             if any(r.window == 0 for r in plan)
                             else (1 << 62))
        self.plan = plan
        self.pools: List[PagePool] = []
        self.caches: List[Any] = []
        dtype = jnp.dtype(cfg.dtype)
        for run in plan:
            R = cache_len(run, self.capacity)
            self.pools.append(PagePool(n_pages, page_size, n_slots, R))
            L, P = run.n_layers, page_size
            if cfg.mla.enabled:
                w = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
                if kv_dtype != "f32":
                    # int8 latent pages + one f32 scale per (layer, token);
                    # int4 deliberately maps here too (see module docstring)
                    self.caches.append({
                        "latent": jnp.zeros((L, n_pages, P, w), jnp.int8),
                        "latent_scale": jnp.zeros((L, n_pages, P),
                                                  jnp.float32),
                    })
                else:
                    self.caches.append(
                        {"latent": jnp.zeros((L, n_pages, P, w), dtype)})
            elif kv_dtype == "int4":
                # packed nibble pairs: uint8 pages at half the head width
                # (uint8 vs int8 is also the runtime marker q4-vs-q8)
                shape = (L, n_pages, P, cfg.n_kv_heads, cfg.d_head // 2)
                sshape = (L, n_pages, P, cfg.n_kv_heads)
                self.caches.append({
                    "k": jnp.zeros(shape, jnp.uint8),
                    "k_scale": jnp.zeros(sshape, jnp.float32),
                    "v": jnp.zeros(shape, jnp.uint8),
                    "v_scale": jnp.zeros(sshape, jnp.float32),
                })
            elif kv_dtype == "int8":
                shape = (L, n_pages, P, cfg.n_kv_heads, cfg.d_head)
                sshape = (L, n_pages, P, cfg.n_kv_heads)
                self.caches.append({
                    "k": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(sshape, jnp.float32),
                    "v": jnp.zeros(shape, jnp.int8),
                    "v_scale": jnp.zeros(sshape, jnp.float32),
                })
            else:
                shape = (L, n_pages, P, cfg.n_kv_heads, cfg.d_head)
                self.caches.append({"k": jnp.zeros(shape, dtype),
                                    "v": jnp.zeros(shape, dtype)})
        # the page dtype as allocated: kv_dtype "f32" means the model's
        # float dtype (bf16 at published widths), not float32
        self.page_dtype = next(iter(self.caches[0].values())).dtype
        # byte accounting is precomputed: the engine DONATES the cache
        # arrays to its jitted programs, so the initial leaves must never
        # be touched again after handing ``caches`` over
        self._page_bytes = [
            sum(leaf.nbytes // n_pages for leaf in c.values())
            for c in self.caches]

    # -- capacity queries ---------------------------------------------------------
    def fits(self, total_len: int) -> bool:
        """Can the pool EVER hold a request of ``total_len`` tokens (prompt +
        generation), assuming it runs alone?  Position-indexed runs bound
        length by pool span; an all-ring plan bounds nothing (pages_of is
        ring-clamped, so the per-run page check is what binds)."""
        if total_len > self.length_bound:
            return False
        return all(p.pages_of(total_len) <= p.n_pages for p in self.pools)

    def free_pages(self) -> int:
        """Binding free-page count (min across runs)."""
        return min(p.free_pages() for p in self.pools)

    def headroom_pages(self, decode_lens: Sequence[int],
                       growth: int = 1) -> int:
        """Free pages available to NEW prefill work after reserving the
        growth this tick's decode writes need (``growth`` tokens per
        listed slot length — one for a plain decode step, ``spec_k + 1``
        when speculative verify windows write a whole draft window).
        Min across runs; floored at 0."""
        room = None
        for p in self.pools:
            reserve = sum(p.pages_of(l + growth) - p.pages_of(l)
                          for l in decode_lens)
            r = p.free_pages() - reserve
            room = r if room is None else min(room, r)
        return max(room or 0, 0)

    def len_of(self, slot: int) -> int:
        return int(self.pools[0].lens[slot])

    def max_grow_tokens(self, slot: int) -> int:
        """Largest token growth ``grow(slot, len + t)`` can grant right now
        (min across runs).  A run whose current + free pages reach its full
        width is never binding: ring runs reuse their pages forever."""
        room = None
        for p in self.pools:
            cur = int(p.lens[slot])
            held = p.pages_of(cur)
            if held + p.free_pages() >= p.width:
                continue
            cov = (held + p.free_pages()) * p.page_size - cur
            room = cov if room is None else min(room, cov)
        return self.capacity if room is None else max(room, 0)

    # -- prefix-sharing queries ----------------------------------------------------
    def shareable_capacity(self) -> int:
        """Longest prefix (tokens) whose pages are position-pure in EVERY
        run: up to the narrowest ring span, logical page ``i`` is table row
        ``i`` for all runs, so one per-run page list describes the prefix.
        Beyond a run's ring span the ring has wrapped and its pages mix
        positions — those are never shared."""
        return min(p.capacity for p in self.pools)

    def widest_capacity(self) -> int:
        """Logical span of the widest run — the scheduler's conservative
        page-charge basis (see ``PhaseScheduler.plan_tick``)."""
        return max(p.capacity for p in self.pools)

    def prefix_pages(self, slot: int, n_tokens: int) -> List[List[int]]:
        """Per-run physical pages backing the slot's first ``n_tokens``
        tokens (``n_tokens`` page-aligned, within ``shareable_capacity``)."""
        if n_tokens % self.page_size or n_tokens > self.shareable_capacity():
            raise ValueError(f"prefix of {n_tokens} tokens is not "
                             "page-aligned/shareable")
        n = n_tokens // self.page_size
        return [[int(q) for q in p.table[slot, :n]] for p in self.pools]

    # -- mutations ---------------------------------------------------------------
    def grow(self, slot: int, new_len: int) -> bool:
        """Grow ``slot`` to ``new_len`` logical tokens in EVERY run's pool —
        all-or-nothing (partial successes roll back)."""
        done: List[PagePool] = []
        prev = [int(p.lens[slot]) for p in self.pools]
        for p, old in zip(self.pools, prev):
            if not p.grow(slot, new_len):
                for q, o in zip(done, prev):
                    q.shrink(slot, o)
                return False
            done.append(p)
        return True

    def attach(self, slot: int, pages: Sequence[Sequence[int]],
               new_len: int) -> None:
        """Point an empty slot at cached prefix pages (one page list per
        run) — shared, refcounted, no allocation.  ``new_len`` must be
        page-aligned and within ``shareable_capacity``."""
        if new_len % self.page_size or new_len > self.shareable_capacity():
            raise ValueError(f"attach of {new_len} tokens is not "
                             "page-aligned/shareable")
        for p, pp in zip(self.pools, pages):
            p.attach(slot, pp, new_len)

    def cow_deficit(self, slot: int, start: int, end: int) -> int:
        """Free pages still missing before ``ensure_writable(slot, start,
        end)`` could supply every COW copy target (max across runs; 0 when
        it would succeed right now)."""
        deficit = 0
        for p in self.pools:
            need = sum(1 for row in p.rows_touched(start, end)
                       if int(p.table[slot, row]) < p.n_pages
                       and p.is_shared(int(p.table[slot, row])))
            deficit = max(deficit, need - p.free_pages())
        return max(deficit, 0)

    def ensure_writable(self, slot: int, start: int, end: int
                        ) -> Optional[List[tuple]]:
        """Copy-on-write every SHARED page a write to logical positions
        [start, end) of ``slot`` would dirty, across all runs.  Returns
        [(run, old_page, new_page)] — the caller must mirror each entry
        with a device page copy BEFORE launching the write — or None,
        state unchanged, if some run's free list cannot supply its copy
        targets (the caller evicts/preempts and retries)."""
        planned: List[tuple] = []                 # (run, pool, row)
        for r, p in enumerate(self.pools):
            rows = [row for row in p.rows_touched(start, end)
                    if int(p.table[slot, row]) < p.n_pages
                    and p.is_shared(int(p.table[slot, row]))]
            if len(rows) > p.free_pages():
                return None                       # nothing mutated yet
            planned.extend((r, p, row) for row in rows)
        copies: List[tuple] = []
        for r, p, row in planned:
            moved = p.cow(slot, row)
            assert moved is not None
            copies.append((r, *moved))
        return copies

    def shrink(self, slot: int, new_len: int) -> None:
        """Drop every run's references beyond ``new_len`` (rollback)."""
        for p in self.pools:
            p.shrink(slot, new_len)

    def truncate(self, slot: int, new_len: int) -> int:
        """Roll ``slot`` back to ``new_len`` logical tokens — the
        speculative-decode rejection path (rejected draft tokens' KV must
        stop being addressable).  Per run: pages that backed ONLY the
        rejected tail return to the free list; pages still referenced —
        another slot sharing the prefix, a prefix-cache pin — survive with
        their references intact (a rejected token never frees a shared
        page out from under its sharers), and a page holding both kept and
        rejected tokens is kept whole (the stale entries past ``new_len``
        are masked by position validity on every read path and overwritten
        in place by the accepted continuation).  COW'd pages the verify
        write privatized stay private.  Returns pages freed across runs.
        """
        if new_len > self.len_of(slot):
            raise ValueError(f"truncate: new_len {new_len} > current "
                             f"{self.len_of(slot)}")
        before = sum(p.free_pages() for p in self.pools)
        self.shrink(slot, new_len)
        return sum(p.free_pages() for p in self.pools) - before

    def rollback_bound(self) -> int:
        """Highest arena position (exclusive) through which speculative
        writes can still be ROLLED BACK safely.  A ring (sliding-window)
        run's entry at index ``p % R`` holds live position ``p - R`` once
        ``p >= R`` — writing a draft token there destroys history a
        rollback cannot restore, so the draft/verify loop must stop
        speculating at the narrowest ring span and fall back to one-token
        decode.  Position-indexed runs (full attention, MLA) mask stale
        entries by length, so any position the pool can address is
        rollback-safe."""
        bounds = [p.capacity for p, run in zip(self.pools, self.plan)
                  if run.window > 0]
        return min(bounds) if bounds else self.length_bound

    def release(self, slot: int) -> None:
        for p in self.pools:
            p.release(slot)

    # -- external (prefix cache) references ---------------------------------------
    def retain(self, run: int, page: int) -> None:
        self.pools[run].retain(page)

    def release_ref(self, run: int, page: int) -> None:
        self.pools[run].release_ref(page)

    # -- device-facing views --------------------------------------------------------
    def block_tables(self, active: Optional[np.ndarray] = None, *,
                     rows: Optional[Sequence[int]] = None,
                     n: int = 0) -> List[Any]:
        """Per-run ``[n_slots, W_r]`` int32 block tables for a jitted call.
        Rows of slots not in ``active`` (bool [n_slots]) are forced to the
        sentinel so their scatters drop and their gathers mask out.

        ``rows`` selects a COMPACTED view instead: row i of the returned
        tables is slot ``rows[i]``'s table, padded with all-sentinel rows
        up to ``max(n, len(rows))`` — the engine's bucketed decode batch,
        where batch rows no longer coincide with slots."""
        out = []
        for p in self.pools:
            if rows is not None:
                nb = max(n, len(rows))
                t = np.full((nb, p.table.shape[1]), p.n_pages,
                            p.table.dtype)
                if rows:
                    t[:len(rows)] = p.table[list(rows)]
            else:
                t = p.table
                if active is not None:
                    t = t.copy()
                    t[~active] = p.n_pages
            out.append(jnp.asarray(t))
        return out

    # -- accounting ---------------------------------------------------------------
    def page_bytes(self, r: int) -> int:
        """Bytes of device memory one physical page of run ``r`` holds
        (across all layers and parallel leaves, scales included)."""
        return self._page_bytes[r]

    def resident_bytes(self) -> int:
        """KV bytes resident = allocated pages x page bytes (the number the
        dense arena pins at ``sum(leaf.nbytes)`` regardless of occupancy)."""
        return sum(self.pools[r].used_pages() * self._page_bytes[r]
                   for r in range(len(self.pools)))

    def total_bytes(self) -> int:
        return sum(b * self.n_pages for b in self._page_bytes)

    def utilization(self) -> float:
        total = sum(p.n_pages for p in self.pools)
        used = sum(p.used_pages() for p in self.pools)
        return used / max(total, 1)

    def stats(self) -> Dict[str, Any]:
        return {
            "n_pages": self.n_pages,
            "page_size": self.page_size,
            "capacity_tokens": self.capacity,
            "free_pages": self.free_pages(),
            "utilization": self.utilization(),
            "resident_bytes": self.resident_bytes(),
            "total_bytes": self.total_bytes(),
        }


class HostTier:
    """Host-memory spill pool behind the device page pool — the second
    level of the KV hierarchy (HALO reading: capacity memory behind the
    CiD banks, in the spirit of the High-Bandwidth-Flash argument).

    Holds ``n_pages`` host pages PER RUN, each mirroring the run's device
    page layout (every leaf — k/v, scales, MLA latents — at the same
    dtype, page axis dropped), plain numpy.  Two users:

    * **preemption swap**: the engine copies a victim's device pages here
      (``ServingEngine._swap_out``) and uploads them back at re-admission
      — resume costs two page copies instead of re-prefilling the whole
      prompt + generation (recompute stays the fallback when this tier is
      full or disabled);
    * **prefix demote/promote**: evicted prefix-cache blocks park their
      page contents here instead of dying, and re-promote to a fresh
      device page on the next hit (``PrefixCache`` callbacks).

    Pure host-side storage + free-list accounting; the DEVICE side of
    every copy lives in the engine (``_read_page``/``_write_page``), so
    tests can property-check this class without jax arrays.
    """

    # swap byte totals live in the metrics registry (the engine passes its
    # own, making these attributes views over the same cells counts() and
    # MetricsRegistry.snapshot() report — serving/metrics.py)
    swap_out_bytes = counter_attr("serving_swap_out_bytes_total")
    swap_in_bytes = counter_attr("serving_swap_in_bytes_total")

    def __init__(self, pool: KVPool, n_pages: int, *,
                 metrics: Optional[MetricsRegistry] = None):
        if n_pages < 1:
            raise ValueError(f"HostTier needs n_pages >= 1, got {n_pages}")
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.n_pages = n_pages
        self.n_runs = len(pool.caches)
        self._page_bytes = [pool.page_bytes(r) for r in range(self.n_runs)]
        # per-run page stores: leaf [L, n_dev_pages, P, ...] -> host
        # [L, n_host_pages, P, ...] (page axis 1, same as the device side)
        self._store: List[Dict[str, np.ndarray]] = [
            {k: np.zeros((leaf.shape[0], n_pages) + tuple(leaf.shape[2:]),
                         leaf.dtype)
             for k, leaf in cache.items()}
            for cache in pool.caches]
        self.free: List[List[int]] = [
            list(range(n_pages - 1, -1, -1)) for _ in range(self.n_runs)]
        # byte counters (device->host is "swap out", host->device "swap in")
        self.swap_out_bytes = 0
        self.swap_in_bytes = 0

    # -- queries ---------------------------------------------------------------
    def free_pages(self, run: Optional[int] = None) -> int:
        """Free host pages of one run, or the binding min across runs."""
        if run is not None:
            return len(self.free[run])
        return min(len(f) for f in self.free)

    def used_pages(self) -> int:
        """Host pages in use, summed across runs (the residency metric)."""
        return sum(self.n_pages - len(f) for f in self.free)

    def resident_bytes(self) -> int:
        return sum((self.n_pages - len(f)) * b
                   for f, b in zip(self.free, self._page_bytes))

    # -- allocation ------------------------------------------------------------
    def alloc(self, run: int, n: int) -> Optional[List[int]]:
        """Claim ``n`` host pages of ``run`` — all-or-nothing."""
        if n > len(self.free[run]):
            return None
        return [self.free[run].pop() for _ in range(n)]

    def release(self, run: int, pages: Sequence[int]) -> None:
        for p in pages:
            assert 0 <= p < self.n_pages and p not in self.free[run], \
                f"HostTier.release: page {p} of run {run} is not in use"
            self.free[run].append(int(p))

    # -- page contents ---------------------------------------------------------
    def store(self, run: int, page: int, data: Dict[str, np.ndarray]) -> None:
        """Copy one device page's host-fetched contents ({leaf: [L, P,
        ...]}) into host page ``page`` of ``run``."""
        for k, v in data.items():
            self._store[run][k][:, page] = v
        self.swap_out_bytes += self._page_bytes[run]

    def load(self, run: int, page: int) -> Dict[str, np.ndarray]:
        """Contents of host page ``page`` of ``run``, ready for a device
        upload (views — the caller uploads before the page is reused)."""
        self.swap_in_bytes += self._page_bytes[run]
        return {k: v[:, page] for k, v in self._store[run].items()}

    # -- invariants -------------------------------------------------------------
    def check_invariants(self) -> None:
        for r, f in enumerate(self.free):
            assert len(set(f)) == len(f), f"run {r}: free-list duplicates"
            assert all(0 <= p < self.n_pages for p in f), \
                f"run {r}: free list out of range"

    def stats(self) -> Dict[str, Any]:
        return {
            "n_pages": self.n_pages,
            "free_pages": self.free_pages(),
            "used_pages": self.used_pages(),
            "resident_bytes": self.resident_bytes(),
            "swap_out_bytes": self.swap_out_bytes,
            "swap_in_bytes": self.swap_in_bytes,
        }
