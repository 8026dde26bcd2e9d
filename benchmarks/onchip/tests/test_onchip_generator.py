"""Traffic generation: determinism per seed, the same work under every
seed, and the mixes' length distributions."""

import json
import math
import os
from collections import Counter

import numpy as np
import pytest

import _path
import generator

MIXES = {name: json.load(open(os.path.join(_path.BENCH, "traffic",
                                           f"{name}.json")))
         for name in ("halo-longctx", "chat-shared")}
VOCAB = 151936


def lengths(plan):
    reqs = plan.arrivals or [r for s in plan.sessions for r in s]
    return sorted(len(r.prompt) for r in reqs), sorted(r.max_new for r in reqs)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_plan(mix):
    a = generator.build(MIXES[mix], VOCAB, 2**31 + 17, 50)
    b = generator.build(MIXES[mix], VOCAB, 2**31 + 17, 50)
    ra = a.arrivals or [r for s in a.sessions for r in s]
    rb = b.arrivals or [r for s in b.sessions for r in s]
    assert len(ra) == len(rb)
    for x, y in zip(ra, rb):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due_s, x.session) == (y.max_new, y.due_s,
                                                   y.session)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_seeds_change_order_not_work(mix):
    a = generator.build(MIXES[mix], VOCAB, 1, 50)
    b = generator.build(MIXES[mix], VOCAB, 2, 50)
    assert lengths(a) == lengths(b)
    ra = a.arrivals or [r for s in a.sessions for r in s]
    rb = b.arrivals or [r for s in b.sessions for r in s]
    # a closed loop's sessions walk the sizes in a seeded order; an open
    # loop sends one schedule of sizes and gaps under every seed
    same_order = [len(r.prompt) for r in ra] == [len(r.prompt) for r in rb]
    assert same_order == (MIXES[mix]["loop"] == "open")
    if same_order:
        assert [(r.max_new, r.due_s) for r in ra] == \
            [(r.max_new, r.due_s) for r in rb]
    assert not np.array_equal(ra[0].prompt[-8:], rb[0].prompt[-8:])
    assert all(0 <= r.prompt.min() and r.prompt.max() < VOCAB for r in ra)


def test_halo_sessions_walk_balanced_strata():
    mix = MIXES["halo-longctx"]
    strata = generator.quantile_lens(mix["prompt"], 8)
    assert strata[0] > 1024 and strata[-1] < 8192
    # log-uniform: equal ratios between neighbouring strata
    ratios = [b / a for a, b in zip(strata, strata[1:])]
    assert max(ratios) / min(ratios) < 1.01
    for seed in (5, 6, 2**31 + 1):
        plan = generator.build(mix, VOCAB, seed, 50)
        assert len(plan.sessions) == 4
        for s in plan.sessions:
            assert len(s) == mix["requests_per_session"]
            # each session walks every stratum
            assert sorted(len(r.prompt) for r in s[:8]) == strata
            assert all(256 <= r.max_new <= 512 for r in s)
        # at each step the four sessions hold half the strata, of the same
        # total length whatever the seed (within 2%), and half the outputs
        for i in range(8):
            total = sum(len(s[i].prompt) for s in plan.sessions)
            assert abs(total / (sum(strata) / 2) - 1) < 0.02
            assert sum(s[i].max_new for s in plan.sessions) == 4 * 384


def test_balanced_order_is_a_permutation_of_equal_halves():
    rng = np.random.default_rng(0)
    lens = [1, 2, 3, 4, 5, 6, 7, 8]
    half = generator.equal_halves(lens)
    assert len(half) == 4 and sum(lens[i] for i in half) == 18
    for _ in range(20):
        order = generator.balanced_order(lens, rng)
        assert sorted(order) == list(range(8))
        assert sum(lens[k] for k in order[0::2]) == 18


def test_chat_poisson_arrivals_and_shared_prompts():
    mix = MIXES["chat-shared"]
    window, tail = 50.0, 20.0
    rate, lead = mix["rate_rps"], mix["lead_in_s"]
    plans = [generator.build(mix, VOCAB, seed, window, tail)
             for seed in (11, 12, 2**31 + 3)]
    for plan in plans:
        due = [r.due_s for r in plan.arrivals]
        assert due == sorted(due)
        assert due[0] == -lead
        assert len(due) == sum(round(rate * s) for s in (lead, window, tail))
        inside = [r for r in plan.arrivals if 0 <= r.due_s < window]
        # the window holds the same number of arrivals under every seed,
        # one at its open, their gaps filling it with mean 1/rate
        assert len(inside) == round(rate * window)
        assert inside[0].due_s == 0.0
        gaps = np.diff([r.due_s for r in inside] + [window])
        assert gaps.sum() == pytest.approx(window)
        assert abs(np.mean(gaps) * rate - 1) < 0.05
        assert max(gaps) > 3 * min(gaps)               # exponential spread
        sp = mix["system_prompts"]
        heads = Counter(r.prompt[:sp["len"]].tobytes() for r in inside)
        assert len(heads) == sp["count"]
        assert max(heads.values()) - min(heads.values()) <= 1
        turns = [len(r.prompt) - sp["len"] for r in plan.arrivals]
        assert all(mix["prompt"]["lo"] <= t <= mix["prompt"]["hi"]
                   for t in turns)
        assert abs(np.median(turns) - mix["prompt"]["median"]) < 15
        outs = [r.max_new for r in plan.arrivals]
        assert abs(np.median(outs) - mix["output"]["median"]) < 10
    # ... and the same sizes and gaps, in the same order, other tokens
    windows = [[r for r in p.arrivals if 0 <= r.due_s < window]
               for p in plans]
    assert len({tuple((len(r.prompt), r.max_new, r.due_s) for r in w)
                for w in windows}) == 1
    assert len({w[0].prompt.tobytes() for w in windows}) == 3


@pytest.mark.parametrize("dist,q,want", [
    ({"dist": "uniform", "lo": 256, "hi": 512}, 0.5, 384),
    ({"dist": "loguniform", "lo": 1024, "hi": 8192}, 0.5, 2896),
    ({"dist": "lognormal", "median": 128, "sigma": 0.7, "lo": 16,
      "hi": 512}, 0.5, 128),
    ({"dist": "lognormal", "median": 128, "sigma": 0.7, "lo": 16,
      "hi": 512}, 0.9999, 512),
])
def test_quantile_len(dist, q, want):
    assert generator.quantile_len(dist, q) == want


def test_max_context():
    assert generator.max_context(MIXES["halo-longctx"]) == 8192 + 512
    assert generator.max_context(MIXES["chat-shared"]) == 512 + 1536 + 512
