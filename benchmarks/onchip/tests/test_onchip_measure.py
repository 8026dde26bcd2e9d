"""End-to-end metric arithmetic on synthetic client timestamps."""

import math

import pytest

import _path  # noqa: F401
import measure
from measure import Rec


def rec(start, toks, finish="length", due=True, cut=False):
    r = Rec(prompt_len=10, max_new=len(toks))
    if due:
        r.due = start
    r.sent = start
    r.t_tok, r.finish, r.cut = list(toks), finish, cut
    return r


def test_window_edges():
    recs = [rec(-1.0, [0.5, 1.0]),           # started before: not attempted
            rec(0.0, [0.2, 0.4, 10.0]),      # at w0: in; token at w1: out
            rec(9.99, [10.5]),               # in; its token is past w1
            rec(10.0, [10.1])]               # at w1: out
    e = measure.end_to_end(recs, 0.0, 10.0)
    assert e["attempted"] == 2
    assert e["failed"] == 0
    # tokens received in [0, 10): 0.5, 1.0, 0.2, 0.4
    assert e["output_tok_s"] == pytest.approx(4 / 10)
    # gaps whose later token lies in the window: 0.5, 0.2
    assert sorted(measure.gaps_in_window(recs, 0.0, 10.0)) == \
        pytest.approx([0.2, 0.5])
    assert measure.ttfts(recs, 0.0, 10.0) == pytest.approx([0.2, 0.51])


def test_failures_count_as_misses():
    recs = [rec(1.0, [1.1, 1.2]), rec(2.0, [], finish="shed"),
            rec(3.0, [], finish=None, cut=True),      # no first token
            rec(4.0, [4.5], finish="abort"),          # engine abort: failed
            rec(5.0, [5.5], finish="abort", cut=True)]  # ended by the run
    e = measure.end_to_end(recs, 0.0, 10.0)
    assert e["attempted"] == 5
    assert e["failed"] == 3
    tt = measure.ttfts(recs, 0.0, 10.0)
    assert sorted(tt)[:2] == pytest.approx([0.1, 0.5])
    assert sum(math.isinf(t) for t in tt) == 3
    assert e["ttft_p50_ms"] == math.inf
    gaps = measure.gaps_in_window(recs, 0.0, 10.0)
    assert sum(math.isinf(g) for g in gaps) == 3
    assert e["itl_p95_ms"] == math.inf


def test_quantiles_over_all_gaps():
    # one request with 99 gaps of 10 ms and one with a single 1 s gap:
    # the tail is over all gaps, not a per-request mean
    fast = rec(0.0, [0.01 * i for i in range(1, 101)])
    slow = rec(0.0, [0.5, 1.5])
    gaps = measure.gaps_in_window([fast, slow], 0.0, 10.0)
    assert len(gaps) == 100
    assert measure.quantile(gaps, 0.95) == pytest.approx(0.01)
    assert measure.quantile(gaps, 1.0) == pytest.approx(1.0)
    assert measure.quantile([3, 1, 2], 0.5) == 2
    assert measure.quantile([1, 2, 3, 4], 0.5) == 2
    assert math.isnan(measure.quantile([], 0.5))


def test_closed_loop_clock_starts_when_sent():
    r = rec(2.0, [3.5], due=False)
    assert r.start == 2.0 and r.ttft == pytest.approx(1.5)
    late = rec(1.0, [2.0])
    late.sent = 1.4
    assert measure.lateness([late, r]) == pytest.approx([0.4])
    assert late.ttft == pytest.approx(1.0)     # from when it was due
