"""Device-idle time put down to the program's ``serve.*`` spans
(``spans.py``), the request-lifecycle readers, and the names by which the
readers find the program in a trace."""

import gzip
import json
import os
import re
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

import _path
import generator
import readings
import run
import spans
import spec
import tracefile
from measure import Rec

MS = 1_000_000          # ns


def synthetic():
    """Two engine ticks: a decode tick [0, 20) ms and a prefill tick
    [24, 34) ms, with the owner loop's fan-out and a client task between
    them; the device is busy [6, 15) and [27, 30) ms."""
    host = [["bench.step", 0, 20], ["serve.step", 1, 18],
            ["serve.admit", 1, 1], ["serve.plan", 2, 1],
            ["serve.decode.prepare", 3, 2], ["serve.decode.launch", 5, 1],
            ["serve.decode.sync", 6, 10], ["serve.emit", 16, 2],
            ["serve.frontend.fanout", 20, 1], ["bench.client", 22, 1],
            ["bench.step", 24, 10], ["serve.step", 25, 8],
            ["serve.prefill.prepare", 25, 1], ["serve.prefill.launch", 26, 1],
            ["serve.prefill.sync", 27, 4], ["serve.emit", 31, 2],
            ["serve.frontend.drain", 40, 1]]          # past the window
    return {
        "chips": 1,
        "host": [[n, s * MS, d * MS] for n, s, d in host],
        "device": {
            "XLA Modules": [["jit__decode_paged_impl(1)", 6 * MS, 9 * MS],
                            ["jit__packed_paged_impl(2)", 27 * MS, 3 * MS]],
            "XLA Ops": [["_paged_decode_kernel", 6 * MS, 9 * MS],
                        ["_packed_prefill_kernel", 27 * MS, 3 * MS]]},
    }


def test_idle_is_put_down_to_the_innermost_span():
    t = tracefile.Trace.from_raw(synthetic())
    assert (t.w0, t.w1) == (0, 34 * MS)
    by = spans.idle_by_span(t)
    want = {"bench.step": 4, "serve.admit": 1, "serve.plan": 1,
            "serve.decode.prepare": 2, "serve.decode.launch": 1,
            "serve.decode.sync": 1, "serve.emit": 4, "serve.step": 1,
            "serve.frontend.fanout": 1, "bench.client": 1, "idle": 2,
            "serve.prefill.prepare": 1, "serve.prefill.launch": 1,
            "serve.prefill.sync": 1}
    assert by == {k: pytest.approx(v * 1e-3) for k, v in want.items()}
    # the gaps the run's breakdown lists now carry the program's names
    # (named by the span over each gap's middle)
    assert t.idle_gaps()[:2] == [["idle", pytest.approx(0.012)],
                                 ["serve.decode.prepare",
                                  pytest.approx(0.006)]]


def test_layer_idle_partitions_the_window():
    t = tracefile.Trace.from_raw(synthetic())
    assert spans.ticks(t) == 2
    got = spans.layer_idle_ms_per_tick(t)
    # scheduler: admit, plan, two emits; executor: decode and prefill
    # phases and serve.step's own time; front end: the rest
    assert got == {"scheduler": pytest.approx(3.0),
                   "executor": pytest.approx(4.0),
                   "frontend": pytest.approx(4.0)}
    assert sum(got.values()) * spans.ticks(t) * 1e-3 == \
        pytest.approx(t.window_s - t.busy_s, rel=1e-9)


def test_without_program_spans_nothing_is_read():
    raw = synthetic()
    raw["host"] = [e for e in raw["host"] if e[0].startswith("bench.")]
    assert spans.layer_idle_ms_per_tick(tracefile.Trace.from_raw(raw)) \
        is None
    assert spans.layer_idle_ms_per_tick(None) is None


@pytest.mark.parametrize("span,layer", [
    ("serve.admit", "scheduler"), ("serve.plan", "scheduler"),
    ("serve.emit", "scheduler"), ("serve.prefill.sync", "executor"),
    ("serve.decode.launch", "executor"), ("serve.verify", "executor"),
    ("serve.step", "executor"), ("serve.frontend.drain", "frontend"),
    ("bench.step", "frontend"), ("idle", "frontend")])
def test_layer_of(span, layer):
    assert spans.layer_of(span) == layer


def lifecycle_recs(**stamps):
    """Three window requests: admitted after 50 and 200 ms, first tokens
    400 ms after admission; the third never admitted."""
    def req(t_submit, t_admit, t_first_token):
        return SimpleNamespace(t_submit=t_submit, t_admit=t_admit,
                               t_first_token=t_first_token, **stamps)
    recs = []
    for due, q in ((0.10, req(0.15, 0.20, 0.60)), (0.30, req(0.30, 0.50, 0.90)),
                   (0.40, req(0.41, 0.0, 0.0))):
        r = Rec(prompt_len=8, max_new=4, due=due, sent=due)
        r.request = q
        recs.append(r)
    return recs


def test_request_lifecycle_readers():
    cell = spec.load_cell("qwen3-1.7b.chat-shared")
    ctx = SimpleNamespace(recs=lifecycle_recs(), w0=0.0, w1=1.0)
    # nearest-rank medians of {50, 200, inf} and {400, 400, inf}
    assert cell.reader("queue_wait_ms_p50.chat")(ctx) == pytest.approx(200)
    assert cell.reader("first_token_ms_p50.chat")(ctx) == pytest.approx(400)
    # a request refused at submit counts as never admitted
    extra = Rec(prompt_len=8, max_new=4, due=0.5, sent=0.5)
    ctx.recs.append(extra)
    ctx.recs.append(Rec(prompt_len=8, max_new=4, due=0.6, sent=0.6))
    assert cell.reader("queue_wait_ms_p50.chat")(ctx) is None   # inf


def test_request_readers_without_the_admission_stamp():
    """A program that stamps no ``t_admit`` reads nothing, and raises
    nothing."""
    cell = spec.load_cell("qwen3-1.7b.chat-shared")
    recs = lifecycle_recs()
    for r in recs:
        r.request = SimpleNamespace(t_submit=r.request.t_submit,
                                    t_first_token=r.request.t_first_token)
    ctx = SimpleNamespace(recs=recs, w0=0.0, w1=1.0)
    assert cell.reader("queue_wait_ms_p50.chat")(ctx) is None
    assert cell.reader("first_token_ms_p50.chat")(ctx) is None
    assert cell.reader("queue_wait_ms_p50.chat")(
        SimpleNamespace(recs=[], w0=0.0, w1=1.0)) is None


def test_phase_programs_carry_the_names_the_readers_match():
    """The device trace names each program execution by its XLA module;
    the readers find the served programs by ``readings.DECODE_PROGRAM``
    and ``readings.PREFILL_PROGRAM``. A rename fails here, not as a
    silent null on the chip."""
    cell = spec.load_cell("qwen3-1.7b.chat-shared")
    adapter = cell.adapter()
    m = adapter.rehearsal_sizes(cell.config)
    mix = run.merged_mix(cell.traffic, True)
    serving = mix["serving"]
    engine = run.build_engine(adapter.program_config(m),
                              adapter.make_params(m, 1), serving,
                              generator.max_context(mix))
    nb, T = serving["max_batch"], engine._pack_align
    pg, dg = engine.scheduler.groups_for()
    pp, _ = engine._pack_params([], nb)
    zeros = jnp.zeros((nb,), jnp.int32)
    decode = engine._program(dg, "decode_paged").lower(
        engine.params, jnp.zeros((nb, 1), jnp.int32), engine.cache, zeros,
        engine.pool.block_tables(rows=[], n=nb), *pp, True)
    starts = np.full((nb,), T, np.int32)
    prefill = engine._program(pg, "packed_paged").lower(
        engine.params, jnp.zeros((T,), jnp.int32), jnp.asarray(starts),
        zeros, zeros, jnp.full((nb,), nb, jnp.int32), engine.cache,
        engine.pool.block_tables(), *pp, True)

    def module(lowered):
        return re.match(r"module @(\S+)", lowered.as_text()).group(1)
    assert re.search(readings.DECODE_PROGRAM, module(decode))
    assert re.search(readings.PREFILL_PROGRAM, module(prefill))
    assert not re.search(readings.DECODE_PROGRAM, module(prefill))
    assert not re.search(readings.PREFILL_PROGRAM, module(decode))


def test_traced_rehearsal_keeps_the_program_spans(tmp_path, capsys):
    """A traced CPU rehearsal through ``spans.py``: the profiler's host
    events hold the program's spans, one ``serve.step`` in each
    ``bench.step``, and the owner loop's drain and fan-out; the run reads
    both request-lifecycle metrics. (The traced ticks hold a prefill and
    a decode, but not always a prefill that completes: no prefill sync
    is required here.)"""
    rc = spans.main(["--out", str(tmp_path), "--", "--workload",
                     "qwen3-1.7b.chat-shared", "--seed", str(2**31 + 7),
                     "--seconds", "2", "--trace", "1", "--cpu-rehearsal"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"]
    assert line["numbers"]["queue_wait_ms_p50.chat"] >= 0
    assert line["numbers"]["first_token_ms_p50.chat"] > 0
    with gzip.open(tmp_path / "trace_full.json.gz", "rt") as f:
        host = json.load(f)["host"]
    names = {n for n, _, _ in host}
    assert {"serve.step", "serve.admit", "serve.plan", "serve.emit",
            "serve.prefill.prepare", "serve.prefill.launch",
            "serve.decode.prepare", "serve.decode.launch",
            "serve.decode.sync", "serve.frontend.drain",
            "serve.frontend.fanout"} <= names
    benches = [(s, s + d) for n, s, d in host if n == "bench.step"]
    steps = [(s, s + d) for n, s, d in host if n == "serve.step"]
    assert benches and len(steps) == len(benches)
    for a, b in benches:
        assert sum(a <= s and e <= b for s, e in steps) == 1
    assert os.path.exists(tmp_path / "trace_trimmed.json")


SPANS_FIXTURE = os.path.join(_path.BENCH, "tests", "data",
                             "trace_v5e_spans.json")


def test_recorded_chip_trace_with_program_spans():
    """A slice of six engine ticks of ``qwen3-8b-l12.halo-longctx``
    recorded on one TPU v5e through ``spans.py``: one ``serve.step`` in
    each ``bench.step``, the three layers' idle time partitions the
    window's, and the idle gaps inside ticks carry the program's span
    names."""
    raw = json.load(open(SPANS_FIXTURE))
    t = tracefile.Trace.from_raw(raw)
    assert t is not None and raw["chips"] == 1
    benches = [(s, s + d) for n, s, d in raw["host"] if n == "bench.step"]
    steps = [(s, s + d) for n, s, d in raw["host"] if n == "serve.step"]
    assert len(benches) == len(steps) == spans.ticks(t) == 6
    for a, b in benches:
        assert sum(a <= s and e <= b for s, e in steps) == 1
    got = spans.layer_idle_ms_per_tick(t)
    assert all(v >= 0 for v in got.values())
    assert sum(got.values()) * spans.ticks(t) * 1e-3 == \
        pytest.approx(t.window_s - t.busy_s, rel=1e-9)
    assert got["executor"] > got["scheduler"] + got["frontend"]
    gaps = t.idle_gaps()
    assert gaps and all(n.startswith("serve.") for n, _ in gaps)
    # the existing readers read the same programs here
    assert t.time_of("XLA Modules", readings.DECODE_PROGRAM)[1] == 6
    assert t.time_of("XLA Modules", readings.PREFILL_PROGRAM)[1] == 1
