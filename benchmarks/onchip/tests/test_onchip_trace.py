"""The trace reduction: on a synthetic trace with known answers, and on
a slice of a trace recorded on a TPU v5e (``tests/data``)."""

import json
import os
from types import SimpleNamespace

import pytest

import _path
import peaks
import spec
import tracefile

MS = 1_000_000          # ns


def synthetic():
    return {
        "chips": 1,
        "host": [["bench.step", 0, 10 * MS], ["bench.client", 11 * MS, MS],
                 ["bench.step", 20 * MS, 10 * MS]],
        "device": {
            "XLA Modules": [["jit__decode_paged_impl(1)", 1 * MS, 6 * MS],
                            ["jit__packed_paged_impl(2)", 21 * MS, 8 * MS]],
            "XLA Ops": [["while.2", 1 * MS, 5 * MS],      # holds the next two
                        ["fusion.1", 1 * MS, 2 * MS],
                        ["_paged_decode_kernel", 3 * MS, 2 * MS],
                        ["_packed_prefill_kernel", 21 * MS, 4 * MS],
                        ["fusion.7", 25 * MS, 4 * MS],
                        ["fusion.3", 40 * MS, 1 * MS]],    # past the window
        },
    }


def test_window_busy_and_times():
    t = tracefile.Trace.from_raw(synthetic())
    assert (t.w0, t.w1) == (0, 30 * MS)
    assert t.window_s == pytest.approx(0.030)
    # ops cover [1, 6) and [21, 29) ms: 13 ms busy
    assert t.busy_s == pytest.approx(0.013)
    assert t.time_of("XLA Modules", "decode_paged_impl") == \
        (pytest.approx(0.006), 1)
    assert t.time_of("XLA Ops", "packed_prefill") == (pytest.approx(0.004), 1)
    assert t.top_ops() == [["fusion", pytest.approx(0.006)],
                           ["_packed_prefill_kernel", pytest.approx(0.004)],
                           ["_paged_decode_kernel", pytest.approx(0.002)],
                           ["while", pytest.approx(0.001)]]


def test_idle_gaps_are_named_by_the_host_span():
    gaps = tracefile.Trace.from_raw(synthetic()).idle_gaps()
    # [6, 21) ms: its middle (13.5 ms) lies in no span; [0, 1) and
    # [29, 30) lie in bench.step spans
    assert gaps[0] == ["idle", pytest.approx(0.015)]
    assert sorted(g[0] for g in gaps[1:]) == ["bench.step", "bench.step"]
    assert all(g[1] == pytest.approx(0.001) for g in gaps[1:])


def test_no_chip_or_no_tick_gives_no_trace():
    raw = synthetic()
    assert tracefile.Trace.from_raw(dict(raw, chips=0)) is None
    assert tracefile.Trace.from_raw(dict(raw, host=[])) is None


def test_self_times_and_names():
    ev = [["while", 0, 10], ["a", 1, 2], ["b", 4, 3], ["c", 12, 1]]
    assert tracefile.self_times(ev) == [("while", 5), ("a", 2), ("b", 3),
                                        ("c", 1)]
    assert tracefile.op_name("%fusion.12 = bf16[4]{0} fusion(%x)") == \
        "fusion.12"


def test_op_kind():
    assert tracefile.op_kind("fusion.12") == "fusion"
    assert tracefile.op_kind("copy_3") == "copy"
    assert tracefile.op_kind("_paged_decode_kernel") == "_paged_decode_kernel"


def readers_ctx(trace, ticks):
    cell = spec.load_cell("qwen3-8b-l12.halo-longctx")
    return cell, SimpleNamespace(
        model=cell.config, peaks=peaks.peaks_for("TPU v5 lite"), itemsize=2,
        recs=[], w0=0.0, w1=1.0, ticks=ticks, traced=ticks, trace=trace)


def test_readers_on_the_synthetic_trace():
    decode = SimpleNamespace(segments=[], ctx=[4000, 4000, 4000, 4000])
    prefill = SimpleNamespace(segments=[(0, 2048)], ctx=[])
    cell, ctx = readers_ctx(tracefile.Trace.from_raw(synthetic()),
                            [decode, prefill])
    got = {m["name"]: cell.reader(m["name"])(ctx) for m in cell.per_layer}
    assert got["decode_tick_ms"] == pytest.approx(6.0)
    assert got["prefill_ms_per_ktok"] == pytest.approx(8.0 / 2.048)
    assert got["device_idle_frac"] == pytest.approx(1 - 13 / 30)
    L = 12
    kv = 16000 * 2 * 8 * 128 * 2
    want = L * max(4 * 32 * 128 * 16000 / 197e12, (kv + 4 * 2 * 32 * 128 * 2)
                   / 819e9) / 0.002
    assert got["paged_decode_roofline"] == pytest.approx(100 * want)
    for name in ("step_mfu", "decode_step_hbm_frac",
                 "packed_prefill_roofline"):
        assert got[name] > 0
    assert got["ttft_p50_ms.halo"] is None          # no request: nothing read
    assert got["decode_batch_mean.halo"] == 4


FIXTURE = os.path.join(_path.BENCH, "tests", "data", "trace_v5e.json")


def test_recorded_chip_trace():
    """A slice of six engine ticks of ``qwen3-8b-l12.halo-longctx``
    recorded on one TPU v5e (``record_trace.py``)."""
    raw = json.load(open(FIXTURE))
    t = tracefile.Trace.from_raw(raw)
    assert t is not None and raw["chips"] == 1
    assert 0 < t.busy_s <= t.window_s
    dec_s, n_dec = t.time_of("XLA Modules", "decode_paged_impl")
    pre_s, n_pre = t.time_of("XLA Modules", "packed_paged_impl")
    assert n_dec >= 1 and n_pre >= 1
    # one kernel event per layer per program call
    layers = 12
    assert t.time_of("XLA Ops", "paged_decode")[1] == layers * n_dec
    assert t.time_of("XLA Ops", "packed_prefill")[1] == layers * n_pre
    assert t.time_of("XLA Ops", "paged_decode")[0] < dec_s
    assert t.time_of("XLA Ops", "packed_prefill")[0] < pre_s
    top = t.top_ops()
    assert len(top) == 10 and len(t.idle_gaps()) <= 10
    # self times: the layer loop's while op does not double its body
    assert sum(d for _, d in top) <= t.busy_s * 1.001
    assert top[0][0] == "packed_prefill_attention"


def test_chat_front_end_readers():
    import measure
    from measure import Rec
    cell = spec.load_cell("qwen3-1.7b.chat-shared")
    r = Rec(prompt_len=600, max_new=3, due=0.1, sent=0.2,
            t_tok=[0.6, 0.65, 0.75], finish="length")
    r.request = object()
    r.cached_tokens = 512
    ctx = SimpleNamespace(recs=[r], w0=0.0, w1=1.0, trace=None, traced=[],
                          ticks=[])
    e2e = measure.end_to_end(ctx.recs, ctx.w0, ctx.w1)
    assert {"ttft_p50_ms", "itl_p95_ms"} <= {m["name"]
                                             for m in cell.end_to_end}
    assert e2e["ttft_p50_ms"] == pytest.approx(500)
    assert e2e["itl_p95_ms"] == pytest.approx(100)
    assert cell.reader("prefix_hit_frac.chat")(ctx) == pytest.approx(512 / 600)
    assert cell.reader("device_idle_frac")(ctx) is None
