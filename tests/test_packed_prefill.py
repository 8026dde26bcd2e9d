"""Packed multi-request prefill: the flat-stream chunk path vs the padded
[N, C] batch — greedy identity across architectures and engine modes,
prefix-skip on partial chunks, pack-plan token conservation, the Pallas
kernel vs its pure-JAX reference, and the bucket ladder's zero-recompile
guarantee on repeated traffic."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models.transformer import (
    forward_chunk,
    forward_chunk_packed,
    init_cache,
    init_params,
)
from repro.serving.engine import ServeConfig, ServingEngine
from repro.serving.kv_pool import KVPool
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import (
    PackedPrefill,
    PhaseAwareConfig,
    align_up,
    bucket_pow2,
    bucket_tokens,
    pack_chunks,
)


def tiny_cfg(name="qwen3-1.7b"):
    return dataclasses.replace(get_config(name).reduced(), dtype="float32")


# -- pack plans -------------------------------------------------------------------

def test_pack_chunks_layout():
    pk = pack_chunks([(3, 5), (7, 8), (9, 2)], align=4)
    assert isinstance(pk, PackedPrefill)
    assert pk.req_ids == (3, 7, 9)
    assert pk.takes == (5, 8, 2)
    assert pk.starts == (0, 8, 16)           # 5 -> 8, 8 -> 16 (aligned)
    assert pk.total_tokens == 15
    # packed end 18 aligns to 20, then rounds up the half-octave ladder
    # (..., 16, 24, 32, ...) rather than all the way to the next pow2
    assert pk.length == 24
    assert pk.padded_tokens == 24 - 15
    assert bucket_pow2(20) == 32 and bucket_tokens(20, 4) == 24


def test_pack_chunks_conserves_tokens():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(
        takes=st.lists(st.integers(min_value=0, max_value=700),
                       min_size=0, max_size=12),
        align=st.sampled_from([1, 2, 4, 8, 16, 128]))
    @hyp.settings(deadline=None, max_examples=200)
    def check(takes, align):
        pk = pack_chunks(list(enumerate(takes)), align=align)
        live = [(i, t) for i, t in enumerate(takes) if t > 0]
        # every planned token survives packing, none are invented
        assert pk.total_tokens == sum(t for _, t in live)
        assert pk.takes == tuple(t for _, t in live)
        assert pk.req_ids == tuple(i for i, _ in live)
        # segments are disjoint, ordered, and tile-aligned
        for j, (s, t) in enumerate(zip(pk.starts, pk.takes)):
            assert s % align == 0
            if j + 1 < len(pk.starts):
                assert s + t <= pk.starts[j + 1]
        # the stream bounds every segment and buckets to the pow2 ladder
        if pk.takes:
            assert pk.starts[-1] + pk.takes[-1] <= pk.length
            end = align_up(pk.starts[-1] + pk.takes[-1], align)
            assert pk.length == max(bucket_tokens(end, align), align)
        assert pk.padded_tokens == pk.length - pk.total_tokens

    check()


# -- model-level identity ---------------------------------------------------------

def _run_ticks_padded(cfg, params, prompts, slots, ticks, cache, pool):
    outs = {}
    for tick in ticks:
        C = max(t for _, _, t in tick)
        N = len(tick)
        toks = np.zeros((N, C), np.int32)
        offs = np.zeros((N,), np.int32)
        lens = np.zeros((N,), np.int32)
        slts = np.full((N,), 4, np.int32)
        for i, (ri, off, take) in enumerate(tick):
            toks[i, :take] = prompts[ri][off:off + take]
            offs[i], lens[i], slts[i] = off, take, slots[ri]
        kw = {"block_tables": pool.block_tables()} if pool else {}
        lg, cache = forward_chunk(params, cfg, toks, offs, lens, slts,
                                  cache, **kw)
        if pool:
            pool.caches = cache
        for i, (ri, off, take) in enumerate(tick):
            outs[(ri, off)] = np.asarray(lg[i, 0])
    return outs, cache


def _run_ticks_packed(cfg, params, prompts, slots, ticks, cache, pool,
                      align):
    outs = {}
    for tick in ticks:
        pk = pack_chunks([(ri, take) for ri, _, take in tick], align=align)
        T, N = pk.length, len(tick)
        toks = np.zeros((T,), np.int32)
        starts = np.full((N,), T, np.int32)
        offs = np.zeros((N,), np.int32)
        lens = np.zeros((N,), np.int32)
        slts = np.full((N,), 4, np.int32)
        for i, (ri, off, take) in enumerate(tick):
            s = pk.starts[i]
            toks[s:s + take] = prompts[ri][off:off + take]
            starts[i], offs[i], lens[i], slts[i] = s, off, take, slots[ri]
        kw = {"block_tables": pool.block_tables()} if pool else {}
        lg, cache = forward_chunk_packed(params, cfg, toks, starts, offs,
                                         lens, slts, cache,
                                         pack_align=align, **kw)
        if pool:
            pool.caches = cache
        for i, (ri, off, take) in enumerate(tick):
            outs[(ri, off)] = np.asarray(lg[i, 0])
    return outs, cache


# llama2-7b / qwen3-8b are the paper's two models (MHA / GQA); gemma3-1b
# adds the sliding-window ring, deepseek-v2-236b the MLA latent cache
@pytest.mark.parametrize("name", ["llama2-7b", "qwen3-8b", "gemma3-1b",
                                  "deepseek-v2-236b"])
@pytest.mark.parametrize("paged", [False, True])
def test_packed_matches_padded_chunks(name, paged):
    """Two mixed-length chunk ticks: packed logits pick the same greedy
    token as the padded batch for every chunk, and the KV written to the
    arena matches."""
    cfg = tiny_cfg(name)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (13, 7)]
    slots = [0, 2]
    ticks = [[(0, 0, 8), (1, 0, 7)], [(0, 8, 5)]]

    def fresh():
        if paged:
            pool = KVPool(cfg, n_slots=4, page_size=8, n_pages=32)
            for i, p in enumerate(prompts):
                assert pool.grow(slots[i], len(p))
            return pool.caches, pool
        return init_cache(cfg, 4, 48), None

    cache, pool = fresh()
    ref, ref_cache = _run_ticks_padded(cfg, params, prompts, slots, ticks,
                                       cache, pool)
    cache, pool = fresh()
    got, got_cache = _run_ticks_packed(cfg, params, prompts, slots, ticks,
                                       cache, pool, align=8)
    for k in ref:
        assert np.argmax(ref[k]) == np.argmax(got[k]), k
        np.testing.assert_allclose(got[k], ref[k], atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ref_cache),
                    jax.tree_util.tree_leaves(got_cache)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-5, rtol=1e-5)


# -- engine-level identity --------------------------------------------------------

def _engine(cfg, params, packed, **kw):
    sc = ServeConfig(max_batch=4, max_len=128,
                     phase=PhaseAwareConfig(prefill_chunk=8, pack_align=8),
                     page_size=8, n_pages=96, packed_prefill=packed, **kw)
    return ServingEngine(cfg, params, sc)


@pytest.mark.parametrize("mode", ["dense", "paged", "prefix", "spec"])
def test_engine_packed_identity(mode):
    """Greedy token streams are identical with packed prefill on or off,
    in every engine mode the padded path serves."""
    from repro.serving.speculative import SpecConfig

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    kw = {"dense": {},
          "paged": {"paged": True},
          "prefix": {"paged": True, "prefix_cache": True},
          "spec": {"paged": True, "speculative": SpecConfig(k=3)}}[mode]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (13, 29, 7, 22)]
    streams = {}
    for packed in (False, True):
        eng = _engine(cfg, params, packed, **kw)
        # guard the gate itself: with packed_prefill=True on a chunkable
        # single-codebook model the flat-stream path MUST engage — a
        # silently-off gate would make this test vacuously green
        assert eng._packed is packed
        for p in prompts:
            eng.submit(p, max_new_tokens=5)
        done = eng.run_until_drained(max_ticks=400)
        assert len(done) == len(prompts)
        streams[packed] = {r.req_id: list(r.generated) for r in done}
        assert eng.prefill_launches > 0
    assert streams[False] == streams[True]


def test_partial_chunk_prefix_skip():
    """A cached prefix ending mid-chunk: the resumed request's packed
    stream starts exactly at the first uncached token — the skipped
    tokens never enter the stream (prefill_tokens_executed counts only
    the remainder) and the continuation is greedy-identical."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    head = rng.integers(0, cfg.vocab_size, size=20).astype(np.int32)
    tail = rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
    long = np.concatenate([head, tail])

    # reference stream for the long prompt, no cache
    ref = _engine(cfg, params, True, paged=True)
    r0 = ref.submit(long, max_new_tokens=4)
    ref.run_until_drained(max_ticks=200)

    eng = _engine(cfg, params, True, paged=True, prefix_cache=True)
    eng.submit(head, max_new_tokens=2)
    eng.run_until_drained(max_ticks=200)
    before = eng.prefill_tokens_executed
    r1 = eng.submit(long, max_new_tokens=4)
    eng.run_until_drained(max_ticks=200)
    executed = eng.prefill_tokens_executed - before
    # the 20-token head published 2 FULL pages (page_size 8, 16 tokens);
    # the resume enters the packed stream at token 16 and prefills only
    # the 9 uncached tokens — one full chunk plus a 1-token partial
    assert executed == len(long) - 16
    assert r1.generated == r0.generated


def test_compile_counter_stability():
    """Second pass of the same mixed-length traffic compiles nothing new:
    the pow2 ladder over pack lengths and decode batches closes the
    compiled-shape set after one wave (tick_log carries the per-tick
    delta)."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = _engine(cfg, params, True, paged=True)
    assert eng._packed
    rng = np.random.default_rng(3)
    lens = (13, 29, 7, 22, 40, 3)
    for wave in range(2):
        for n in lens:
            eng.submit(rng.integers(0, cfg.vocab_size, size=n)
                       .astype(np.int32), max_new_tokens=4)
        eng.run_until_drained(max_ticks=400)
        if wave == 0:
            first = eng.compile_count
            assert first > 0
    assert eng.compile_count == first, "second wave recompiled"
    assert sum(t.new_compiles for t in eng.tick_log) == first


# -- kernel vs reference ----------------------------------------------------------

def test_packed_kernel_matches_reference():
    """The Pallas packed-prefill kernel (interpret mode) reproduces the
    pure-JAX packed reference on a multi-segment stream with arena
    history, a wrapped SWA ring, and an all-sentinel pad segment."""
    from repro.kernels.flash_attention import packed_prefill_attention
    from repro.models.attention import _packed_attention_jax, \
        make_packed_segs

    rng = np.random.default_rng(1)
    Hkv, G, D, P, W, n_pages = 2, 2, 16, 8, 4, 16
    ring, window, bq = 16, 16, 8
    H = Hkv * G
    segs = [(6, 21, [2, 3, 4, 5]), (11, 18, [7, 8, 9, 10]), (0, 0, [])]
    takes = [t for t, _, _ in segs]
    starts, cur = [], 0
    for t in takes:
        starts.append(cur)
        cur = align_up(cur + t, bq)
    T = max(cur, bq)
    offs = np.array([o for _, o, _ in segs], np.int32)
    lens = np.array(takes, np.int32)
    starts = np.array(starts, np.int32)
    starts[-1] = T                              # pad segment: empty tail
    bt = np.full((len(segs), W), n_pages, np.int32)
    for i, (_, _, pgs) in enumerate(segs):
        bt[i, :len(pgs)] = pgs
    q = rng.standard_normal((T, H, D)).astype(np.float32)
    kn = rng.standard_normal((T, Hkv, D)).astype(np.float32)
    vn = rng.standard_normal((T, Hkv, D)).astype(np.float32)
    kp = rng.standard_normal((n_pages, P, Hkv, D)).astype(np.float32)
    vp = rng.standard_normal((n_pages, P, Hkv, D)).astype(np.float32)

    out = packed_prefill_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn), jnp.asarray(kp),
        jnp.asarray(vp), jnp.asarray(bt), jnp.asarray(starts),
        jnp.asarray(offs), jnp.asarray(lens), ring=ring, window=window,
        bq=bq, interpret=True)

    seg = make_packed_segs(starts, offs, lens,
                           np.arange(len(segs), dtype=np.int32), T)
    S = W * P
    pages = np.clip(bt, 0, n_pages - 1)
    prev_k = jnp.asarray(kp)[pages].reshape(len(segs), S, Hkv, D)
    prev_v = jnp.asarray(vp)[pages].reshape(len(segs), S, Hkv, D)
    s_idx = np.arange(S, dtype=np.int32)
    prev_pos = offs[:, None] - 1 - ((offs[:, None] - 1 - s_idx) % ring)
    prev_pos = np.where(s_idx[None, :] < ring, prev_pos, -1)
    prev_pos = np.where(np.repeat(bt >= n_pages, P, axis=1), -1, prev_pos)
    ref = _packed_attention_jax(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn), prev_k, prev_v,
        jnp.asarray(prev_pos), seg, n_heads=H, n_kv_heads=Hkv, d_head=D,
        window=jnp.int32(window), softcap=0.0).reshape(T, H, D)

    valid = np.asarray(seg.valid)
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(ref)[valid], atol=2e-5)


# -- kernel routes and the kernel's segment alignment ------------------------------

def test_attention_route_counters_name_the_path_taken():
    """Trace-time counters: on the CPU packed prefill takes its jnp path
    and paged decode the (interpreted) kernel; a softcapped plan gives
    decode way to jnp, and the counter shows it."""
    from repro.models.attention import (attention_route_counts,
                                        reset_attention_route_counts)
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    sp = SamplingParams(max_new_tokens=3)
    reset_attention_route_counts()
    _engine(cfg, params, True, paged=True).generate([[1, 2, 3, 4, 5]], sp)
    routes = attention_route_counts()
    assert routes.get("packed_prefill.jnp", 0) > 0
    assert routes.get("paged_decode.kernel", 0) > 0
    assert "packed_prefill.kernel" not in routes
    capped = dataclasses.replace(
        cfg, attn=dataclasses.replace(cfg.attn, logit_softcap=30.0))
    reset_attention_route_counts()
    _engine(capped, params, True, paged=True).generate([[1, 2, 3]], sp)
    assert attention_route_counts().get("paged_decode.jnp", 0) > 0
    reset_attention_route_counts()
    assert attention_route_counts() == {}


@pytest.mark.parametrize("backend,softcap,kv,paged,want", [
    ("tpu", 0.0, "f32", True, 128),    # the kernel runs: its query tile
    ("tpu", 0.0, "int8", True, 8),     # quantized pool: jnp path
    ("tpu", 30.0, "f32", True, 8),     # softcap: jnp path
    ("cpu", 0.0, "f32", True, 8),      # no TPU: jnp path
    ("tpu", 0.0, "f32", False, 8),     # dense arena: jnp path
])
def test_pack_alignment_follows_the_kernel(monkeypatch, backend, softcap,
                                           kv, paged, want):
    """Where the Pallas packed-prefill kernel runs, the engine aligns its
    packed segments to the kernel's 128-token tile whatever
    PhaseAwareConfig.pack_align (default 8) says, so the default paged
    path reaches the kernel; elsewhere the user's alignment stands."""
    from repro.models import attention
    monkeypatch.setattr(attention.jax, "default_backend", lambda: backend)
    cfg = tiny_cfg()
    cfg = dataclasses.replace(
        cfg, attn=dataclasses.replace(cfg.attn, logit_softcap=softcap))
    assert attention.packed_kernel_align(softcap, kv != "f32") == (
        128 if (backend, softcap, kv) == ("tpu", 0.0, "f32") else 0)
    sc = ServeConfig(max_batch=2, max_len=64, paged=paged, page_size=8,
                     n_pages=16, kv_dtype=kv,
                     phase=PhaseAwareConfig(prefill_chunk=8))
    eng = ServingEngine(cfg, init_params(jax.random.PRNGKey(0), cfg), sc)
    assert eng._pack_align == want
