"""Paged KV arena: pool invariants (hypothesis), paged-vs-dense token
identity, token-level admission, preemption, pool-bounded capacity, and the
int8 page format."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models.transformer import init_params
from repro.serving.engine import RequestState, ServeConfig, ServingEngine
from repro.serving.kv_pool import KVPool, PagePool
from repro.serving.scheduler import PhaseAwareConfig, PhaseScheduler


def tiny_cfg(name="qwen3-1.7b"):
    return dataclasses.replace(get_config(name).reduced(), dtype="float32")


_PARAMS = {}


def cached_params(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = init_params(jax.random.PRNGKey(0), cfg)
    return _PARAMS[cfg.name]


def make_engine(cfg, max_batch=3, max_len=64, *, paged=False, page_size=8,
                n_pages=24, kv_dtype="f32", prefill_chunk=2048,
                max_prefill_tokens=8192):
    params = cached_params(cfg)
    sc = ServeConfig(max_batch=max_batch, max_len=max_len,
                     phase=PhaseAwareConfig(max_decode_batch=max_batch,
                                            prefill_chunk=prefill_chunk,
                                            max_prefill_tokens=max_prefill_tokens),
                     paged=paged, page_size=page_size, n_pages=n_pages,
                     kv_dtype=kv_dtype)
    return ServingEngine(cfg, params, sc)


def prompts(cfg, n, L, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (L,), dtype=np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# PagePool invariants (pure host logic)
# ---------------------------------------------------------------------------


def test_page_pool_basic_alloc_free():
    p = PagePool(n_pages=8, page_size=4, n_slots=2, capacity=32)
    assert p.width == 8 and p.free_pages() == 8
    assert p.grow(0, 10)                 # 3 pages
    assert p.used_pages() == 3 and int(p.lens[0]) == 10
    assert p.grow(0, 12)                 # still 3 pages (page tail)
    assert p.used_pages() == 3
    assert p.grow(1, 17)                 # 5 pages -> pool exactly full
    assert p.free_pages() == 0
    assert not p.grow(0, 13)             # needs a 4th page: refused
    assert int(p.lens[0]) == 12          # refusal left state untouched
    p.release(1)
    assert p.free_pages() == 5
    assert p.grow(0, 13)
    p.check_invariants()


def test_page_pool_ring_capacity_clamps():
    """A sliding-window pool never needs more than ceil(R / P) pages."""
    p = PagePool(n_pages=8, page_size=4, n_slots=1, capacity=10)  # ring R=10
    assert p.width == 3
    assert p.grow(0, 500)                # any length: ring reuses its pages
    assert p.used_pages() == 3
    p.check_invariants()


def test_kv_pool_grow_is_all_or_nothing():
    """A partial per-run success must roll back (no leaked pages)."""
    cfg = tiny_cfg("gemma3-1b")          # mixed window/full runs
    pool = KVPool(cfg, n_slots=2, n_pages=4, page_size=4)
    # capacity 16; ring runs clamp at min(window=16, 16)
    assert pool.grow(0, 12)
    free_before = [p.free_pages() for p in pool.pools]
    assert not pool.grow(1, 16)          # full runs out of pages
    assert [p.free_pages() for p in pool.pools] == free_before
    for p in pool.pools:
        p.check_invariants()


def test_kv_pool_accounting():
    cfg = tiny_cfg()
    pool = KVPool(cfg, n_slots=2, n_pages=8, page_size=4)
    assert pool.resident_bytes() == 0
    assert pool.grow(0, 9)
    r1 = pool.resident_bytes()
    assert r1 == 3 * pool.page_bytes(0)
    assert 0 < r1 < pool.total_bytes()
    assert pool.utilization() == pytest.approx(3 / 8)
    pool.release(0)
    assert pool.resident_bytes() == 0 and pool.free_pages() == 8


# ---------------------------------------------------------------------------
# refcounts, sharing, copy-on-write
# ---------------------------------------------------------------------------


def test_attach_shares_pages_and_refcounts():
    p = PagePool(n_pages=8, page_size=4, n_slots=3, capacity=32)
    assert p.grow(0, 8)                  # 2 pages
    pages = [int(x) for x in p.table[0, :2]]
    p.attach(1, pages, 8)                # no allocation, refcount++
    assert p.used_pages() == 2           # still 2 distinct pages
    assert all(p.is_shared(q) for q in pages)
    p.check_invariants()
    # releasing one sharer keeps the pages live for the other
    p.release(0)
    assert p.used_pages() == 2 and not any(p.is_shared(q) for q in pages)
    p.release(1)
    assert p.free_pages() == 8
    p.check_invariants()


def test_attach_validates():
    p = PagePool(n_pages=8, page_size=4, n_slots=2, capacity=32)
    assert p.grow(0, 8)
    pages = [int(x) for x in p.table[0, :2]]
    with pytest.raises(ValueError):      # wrong page count for the length
        p.attach(1, pages[:1], 8)
    with pytest.raises(ValueError):      # dead page
        p.attach(1, [7, 7], 8)
    assert p.grow(1, 2)
    with pytest.raises(ValueError):      # slot not empty
        p.attach(1, pages, 8)


def test_cow_moves_writer_never_frees_shared():
    p = PagePool(n_pages=8, page_size=4, n_slots=2, capacity=32)
    assert p.grow(0, 6)
    pages = [int(x) for x in p.table[0, :2]]
    p.attach(1, pages, 6)                # tail page shared mid-fill
    moved = p.cow(1, 1)                  # writer 1 extends the tail
    assert moved is not None
    old, new = moved
    assert old == pages[1] and new != old
    assert int(p.table[1, 1]) == new and int(p.table[0, 1]) == old
    assert not p.is_shared(old) and not p.is_shared(new)   # aliasing gone
    assert p.cow(1, 1) is None           # second write: already exclusive
    p.check_invariants()


def test_cow_requires_free_page():
    p = PagePool(n_pages=2, page_size=4, n_slots=2, capacity=8)
    assert p.grow(0, 8)                  # pool full
    pages = [int(x) for x in p.table[0, :2]]
    p.attach(1, pages, 8)                # shared, and no free copy target
    with pytest.raises(IndexError):
        p.cow(1, 0)
    p.check_invariants()


def test_external_refs_keep_pages_past_release():
    """A cache-retained page survives its publisher's release and frees
    only when the external ref drops too (no free-while-referenced)."""
    p = PagePool(n_pages=4, page_size=4, n_slots=1, capacity=16)
    assert p.grow(0, 8)
    pages = [int(x) for x in p.table[0, :2]]
    for q in pages:
        p.retain(q)
    p.release(0)
    assert p.free_pages() == 2           # retained pages did NOT free
    p.check_invariants()
    for q in pages:
        p.release_ref(q)
    assert p.free_pages() == 4
    p.check_invariants()
    with pytest.raises(ValueError):
        p.release_ref(pages[0])          # no external ref left


def test_kv_pool_ensure_writable_is_atomic():
    """If any run lacks COW copy targets, ensure_writable mutates NOTHING
    (the caller retries after evicting)."""
    cfg = tiny_cfg()
    pool = KVPool(cfg, n_slots=2, n_pages=4, page_size=4)
    assert pool.grow(0, 16)              # pool full
    pool.attach(1, pool.prefix_pages(0, 16), 16)   # all shared, none free
    before = [p.table.copy() for p in pool.pools]
    assert pool.ensure_writable(1, 0, 4) is None
    for p, t in zip(pool.pools, before):
        assert (p.table == t).all()
        p.check_invariants()


try:
    import hypothesis  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(
        n_pages=st.integers(2, 16),
        page_size=st.integers(1, 8),
        ops=st.lists(
            st.tuples(st.integers(0, 5),       # 0 grow, 1 release, 2 shrink,
                                               # 3 attach, 4 retain+release_ref,
                                               # 5 cow
                      st.integers(0, 3),       # slot
                      st.integers(0, 40)),     # length delta / target / row
            max_size=60),
    )
    def test_page_pool_interleavings_conserve_pages(n_pages, page_size, ops):
        """ANY interleaving of grow/release/shrink/attach/retain/cow
        (alloc, retire, preempt, prefix share, cache pin, write fault)
        conserves refcounts, never frees a referenced page, and never
        leaves a COW'd writer aliasing a shared page."""
        pool = PagePool(n_pages, page_size, n_slots=4,
                        capacity=n_pages * page_size)
        external: list = []              # pages we hold cache refs on
        for kind, slot, arg in ops:
            if kind == 0:
                pool.grow(slot, int(pool.lens[slot]) + arg)
            elif kind == 1:
                pool.release(slot)
            elif kind == 2:
                pool.shrink(slot, min(int(pool.lens[slot]), arg))
            elif kind == 3:
                src = arg % pool.n_slots
                n = int((pool.table[src] < pool.n_pages).sum())
                if src != slot and int(pool.lens[slot]) == 0 and n:
                    take_tok = min(int(pool.lens[src]), n * page_size)
                    take = pool.pages_of(take_tok)
                    pool.attach(slot, [int(q) for q in pool.table[src, :take]],
                                take_tok)
            elif kind == 4:
                if external and arg % 2:
                    pool.release_ref(external.pop())
                else:
                    live = np.nonzero(pool.ref > 0)[0]
                    if len(live):
                        q = int(live[arg % len(live)])
                        pool.retain(q)
                        external.append(q)
            else:
                rows = np.nonzero(pool.table[slot] < pool.n_pages)[0]
                if len(rows):
                    row = int(rows[arg % len(rows)])
                    try:
                        moved = pool.cow(slot, row)
                    except IndexError:   # no copy target: state unchanged
                        moved = None
                    if moved is not None:
                        old, new = moved
                        # the writer aliases nobody and owns the new page
                        assert int(pool.table[slot, row]) == new
                        assert pool.ref[new] == 1
            pool.check_invariants()
        assert pool.used_pages() + pool.free_pages() == n_pages

    @settings(max_examples=80, deadline=None)
    @given(
        n_pages=st.integers(1, 24),
        page_size=st.integers(1, 8),
        window=st.integers(0, 64),       # 0: full attention
        budget=st.integers(1, 256),
        chunk=st.integers(1, 128),
        waiting=st.lists(st.tuples(st.integers(0, 200),   # remaining
                                   st.integers(0, 200)),  # cur_len
                         min_size=1, max_size=4),
        decoding=st.integers(0, 3),
    )
    def test_plan_tick_never_exceeds_budget_or_pool(n_pages, page_size,
                                                    window, budget, chunk,
                                                    waiting, decoding):
        """Under ARBITRARY (waiting, decoding, pool, window) the planned
        chunks respect the token budget AND every chunk's pages can
        actually be granted by a real PagePool seeded with the same
        state — the scheduler's ring-clamped charge (pages_for) and the
        pool's are the same rule."""
        from repro.serving.scheduler import pages_for
        capacity = min(window, n_pages * page_size) if window > 0 \
            else n_pages * page_size
        pool = PagePool(n_pages, page_size, n_slots=len(waiting),
                        capacity=capacity)
        entries = []
        for i, (remaining, cur_len) in enumerate(waiting):
            if not pool.grow(i, cur_len):
                return                   # seed state not realizable
            entries.append((i, remaining, True, cur_len))
        s = PhaseScheduler(PhaseAwareConfig(
            "halo", max_decode_batch=4, max_prefill_tokens=budget,
            prefill_chunk=chunk))
        plan = s.plan_tick(entries, list(range(decoding)),
                           free_pages=pool.free_pages(),
                           page_size=page_size, capacity=capacity)
        assert plan.prefill_tokens <= budget
        by_id = {e[0]: e for e in entries}
        for rid, take in plan.prefill_chunks:
            _, remaining, _, cur_len = by_id[rid]
            assert 0 < take <= remaining
            assert take <= chunk
            # the real pool grants EVERY planned chunk, in plan order
            assert pool.grow(rid, cur_len + take), (
                f"planned chunk ({rid}, {take}) exceeds what the pool "
                f"can grant (cur_len={cur_len}, "
                f"free={pool.free_pages()})")
            pool.check_invariants()
        # cross-check the scheduler's page arithmetic directly
        for rid, take in plan.prefill_chunks:
            cur = by_id[rid][3]
            assert (pages_for(cur + take, page_size, capacity)
                    - pages_for(cur, page_size, capacity)) >= 0


# ---------------------------------------------------------------------------
# scheduler: token-level (page-aware) admission
# ---------------------------------------------------------------------------


def test_scheduler_admits_only_what_free_pages_cover():
    s = PhaseScheduler(PhaseAwareConfig(
        "halo", max_decode_batch=4, max_prefill_tokens=1000,
        prefill_chunk=600))
    # 2 free pages of 8 tokens; request 10 already holds 4 tokens of a page
    plan = s.plan_tick(waiting=[(10, 600, True, 4), (11, 600, True, 0)],
                       decoding=[], free_pages=2, page_size=8)
    # req 10: page tail (4) + 2 fresh pages = 20 coverable tokens
    assert plan.prefill_chunks == [(10, 20)]
    # no pages at all: nothing admitted even though the token budget is open
    plan = s.plan_tick(waiting=[(11, 600, True, 0)], decoding=[],
                       free_pages=0, page_size=8)
    assert plan.prefill_chunks == []
    # page-tail tokens are admitted without consuming a page
    plan = s.plan_tick(waiting=[(12, 3, True, 5)], decoding=[],
                       free_pages=0, page_size=8)
    assert plan.prefill_chunks == [(12, 3)]


def test_scheduler_page_accounting_across_requests():
    """Pages consumed by an earlier chunk shrink what later ones may take
    (two fresh requests cannot share one free page)."""
    s = PhaseScheduler(PhaseAwareConfig(
        "halo", max_decode_batch=4, max_prefill_tokens=1000,
        prefill_chunk=600))
    plan = s.plan_tick(waiting=[(1, 5, True, 0), (2, 5, True, 0)],
                       decoding=[], free_pages=1, page_size=8)
    assert plan.prefill_chunks == [(1, 5)]   # req 2 has no page left


def test_scheduler_ring_clamp_matches_pool():
    """Regression (sliding-window admission): a request whose arena length
    exceeds the ring span holds ceil(R / P) pages FOREVER — growth costs
    zero fresh pages.  The unclamped ``ceil(cur_len / page_size)`` charge
    used to diverge from ``PagePool.pages_of``'s ring clamp and refuse
    (or page-charge) work the pool grants for free."""
    s = PhaseScheduler(PhaseAwareConfig(
        "halo", max_decode_batch=4, max_prefill_tokens=1000,
        prefill_chunk=600))
    # ring R = 16, P = 8: a slot at cur_len 40 has long since wrapped
    plan = s.plan_tick(waiting=[(1, 100, True, 40)], decoding=[],
                       free_pages=0, page_size=8, capacity=16)
    assert plan.prefill_chunks == [(1, 100)]   # ring reuse: zero pages
    # the real pool agrees: grow costs nothing once wrapped
    pool = PagePool(n_pages=2, page_size=8, n_slots=1, capacity=16)
    assert pool.grow(0, 40) and pool.free_pages() == 0
    assert pool.grow(0, 140)
    pool.check_invariants()
    # unclamped (capacity omitted = legacy behavior): mis-charges 5 pages
    # and admits nothing — exactly the bug the clamp fixes
    legacy = s.plan_tick(waiting=[(1, 100, True, 40)], decoding=[],
                         free_pages=0, page_size=8)
    assert legacy.prefill_chunks == []


def test_scheduler_ring_clamp_engine_end_to_end():
    """A sliding-window config whose prompt exceeds the window serves
    through an exactly-ring-sized pool: without the clamp the planner
    starves (it charges pages the ring never needs)."""
    cfg = tiny_cfg("gemma3-1b")          # window 16
    window = cfg.attn.sliding_window
    # force an ALL-sliding-window plan so the ring is the binding run
    # (local_global_ratio=0 + sliding_window>0 -> every layer local);
    # rename: cached_params keys on cfg.name
    cfg = dataclasses.replace(
        cfg, name="gemma3-1b-all-local",
        attn=dataclasses.replace(cfg.attn, local_global_ratio=0))
    from repro.models.transformer import build_plan
    assert all(r.window > 0 for r in build_plan(cfg))
    eng = make_engine(cfg, max_batch=1, paged=True, page_size=8,
                      n_pages=window // 8, prefill_chunk=8,
                      max_prefill_tokens=8)
    r = eng.submit(prompts(cfg, 1, 3 * window, seed=3)[0],
                   max_new_tokens=4)     # prompt far beyond the ring
    eng.run_until_drained(max_ticks=200)
    assert r.state == RequestState.DONE
    assert len(r.generated) == 4


# ---------------------------------------------------------------------------
# paged-vs-dense engine identity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-1.7b",       # GQA
                                  "gemma3-1b",        # sliding-window ring
                                  "deepseek-v2-236b"  # MLA latent pages
                                  ])
def test_paged_engine_token_identical_to_dense(arch):
    """Paged and dense engines produce identical greedy token streams —
    the pool + block tables + paged kernel are a pure relayout."""
    cfg = tiny_cfg(arch)
    ps = prompts(cfg, 4, 14, seed=2)
    dense = make_engine(cfg)
    rd = [dense.submit(p.copy(), max_new_tokens=5) for p in ps]
    dense.run_until_drained()
    paged = make_engine(cfg, paged=True, page_size=8, n_pages=24)
    rp = [paged.submit(p.copy(), max_new_tokens=5) for p in ps]
    paged.run_until_drained()
    assert [r.generated for r in rd] == [r.generated for r in rp]
    assert paged.preemptions == 0        # pool was big enough
    # paged residency stayed below the pool reservation
    kv = paged.kv_bytes()
    assert 0 < kv["peak_resident"] <= kv["reserved"]


def test_paged_engine_chunked_prefill_identical():
    """Chunked prefill through the block tables == one-shot prefill."""
    cfg = tiny_cfg()
    p = prompts(cfg, 1, 40, seed=5)[0]
    outs = []
    for chunk in (64, 7):
        eng = make_engine(cfg, max_batch=2, paged=True, page_size=8,
                          n_pages=24, prefill_chunk=chunk,
                          max_prefill_tokens=chunk)
        r = eng.submit(p.copy(), max_new_tokens=6)
        eng.run_until_drained()
        outs.append(r.generated)
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# capacity beyond max_len & preemption
# ---------------------------------------------------------------------------


def test_paged_capacity_is_pool_not_max_len():
    """A request with prompt_len + max_new_tokens > max_len completes under
    the paged engine when the pool covers it."""
    cfg = tiny_cfg()
    eng = make_engine(cfg, max_batch=2, max_len=48, paged=True,
                      page_size=8, n_pages=12)     # 96-token pool
    long_req = eng.submit(prompts(cfg, 1, 40, seed=5)[0], max_new_tokens=30)
    assert 40 + 30 > 48                  # would not even submit densely
    eng.run_until_drained()
    assert long_req.state == RequestState.DONE
    assert len(long_req.generated) == 30


def test_dense_engine_rejects_what_paged_accepts():
    cfg = tiny_cfg()
    dense = make_engine(cfg, max_len=32)
    with pytest.raises(ValueError):
        dense.submit(prompts(cfg, 1, 40, seed=5)[0])
    paged = make_engine(cfg, max_len=32, paged=True, page_size=8, n_pages=12)
    paged.submit(prompts(cfg, 1, 40, seed=5)[0])   # fits the 96-token pool
    with pytest.raises(ValueError):
        paged.submit(prompts(cfg, 1, 96, seed=5)[0])   # pool-bounded still


def test_pool_exhaustion_preempts_and_preempted_request_finishes():
    """Forced exhaustion: 3 requests of 26 total tokens vs a 48-token pool.
    The youngest is evicted mid-decode (pages released, WAITING), resumes
    by recompute, and every request finishes with the tokens it would have
    produced running alone (greedy recompute identity)."""
    cfg = tiny_cfg()
    solo = []
    for p in prompts(cfg, 3, 14, seed=7):
        eng = make_engine(cfg, max_batch=1, paged=True, page_size=8,
                          n_pages=6)
        r = eng.submit(p.copy(), max_new_tokens=12)
        eng.run_until_drained()
        solo.append(r.generated)
    eng = make_engine(cfg, max_batch=3, paged=True, page_size=8, n_pages=6)
    rs = [eng.submit(p.copy(), max_new_tokens=12)
          for p in prompts(cfg, 3, 14, seed=7)]
    done = eng.run_until_drained()
    assert len(done) == 3
    assert eng.preemptions > 0
    assert max(r.n_preempted for r in rs) > 0
    assert all(r.state == RequestState.DONE for r in rs)
    assert [r.generated for r in rs] == solo
    # preempted pages really went back: pool fully free at drain
    assert eng.pool.free_pages() == 6
    assert sum(t.preemptions for t in eng.tick_log) == eng.preemptions


def test_prefill_stall_breaks_via_preemption():
    """Regression: two mid-prefill requests holding every page between
    them (no decoder running) used to spin forever — the stall breaker
    must evict the youngest holder and still drain everything with
    dense-identical tokens."""
    cfg = tiny_cfg()
    ps = prompts(cfg, 6, 48, seed=7)
    dense = make_engine(cfg, max_batch=4, max_len=64)
    rd = [dense.submit(p.copy(), max_new_tokens=8) for p in ps]
    dense.run_until_drained()
    # 10 pages x 8 = 80 tokens for 6 x 56-token requests: heavy contention
    paged = make_engine(cfg, max_batch=4, max_len=64, paged=True,
                        page_size=8, n_pages=10)
    rp = [paged.submit(p.copy(), max_new_tokens=8) for p in ps]
    done = paged.run_until_drained(max_ticks=500)
    assert len(done) == 6                # no deadlock
    assert paged.preemptions > 0
    assert [r.generated for r in rd] == [r.generated for r in rp]


def test_preemption_never_evicts_the_oldest():
    """The oldest admitted request must always run to completion (progress
    guarantee: no preemption livelock)."""
    cfg = tiny_cfg()
    eng = make_engine(cfg, max_batch=3, paged=True, page_size=8, n_pages=6)
    rs = [eng.submit(p, max_new_tokens=12)
          for p in prompts(cfg, 3, 14, seed=7)]
    eng.run_until_drained()
    assert rs[0].n_preempted == 0


# ---------------------------------------------------------------------------
# int8 paged pool (HALO's CiD memory format on pages)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "gemma3-1b"])
def test_int8_paged_greedy_token_identity(arch):
    """GQA int8 pages (scales in a parallel page array): greedy tokens
    match the f32 pool within tolerance — int8 KV rounding may flip a
    near-tie, so we require >= 90% agreement and identical first tokens."""
    cfg = tiny_cfg(arch)
    ps = prompts(cfg, 3, 12, seed=11)
    outs = {}
    for dt in ("f32", "int8"):
        eng = make_engine(cfg, paged=True, page_size=8, n_pages=24,
                          kv_dtype=dt)
        rs = [eng.submit(p.copy(), max_new_tokens=6) for p in ps]
        eng.run_until_drained()
        outs[dt] = [r.generated for r in rs]
    total = sum(len(g) for g in outs["f32"])
    agree = sum(a == b for ga, gb in zip(outs["f32"], outs["int8"])
                for a, b in zip(ga, gb))
    assert agree / total >= 0.9
    # the first generated token comes straight off the f32 prefill logits:
    # it must match exactly
    assert [g[0] for g in outs["f32"]] == [g[0] for g in outs["int8"]]


def test_int8_requires_paged():
    cfg = tiny_cfg()
    with pytest.raises(ValueError):
        make_engine(cfg, kv_dtype="int8")


def test_paged_rejects_recurrent_plans():
    cfg = tiny_cfg("mamba2-2.7b")
    with pytest.raises(ValueError):
        make_engine(cfg, paged=True)


@pytest.mark.parametrize("dtype,kv,want", [
    ("bfloat16", "f32", "bfloat16"),     # "f32" means the model's dtype
    ("float32", "f32", "float32"),
    ("bfloat16", "int8", "int8"),
    ("bfloat16", "int4", "uint8"),
])
def test_pool_page_dtype_is_as_allocated(dtype, kv, want):
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(), dtype=dtype)
    pool = KVPool(cfg, n_slots=2, n_pages=8, page_size=4, kv_dtype=kv)
    assert str(pool.page_dtype) == want
    assert pool.caches[0]["k"].dtype == pool.page_dtype
