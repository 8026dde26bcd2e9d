"""Seeded traffic from a mix's parameters.

It follows the design of ``serving/traffic.py``'s ``synthesize``: seeded
draws, never the wall clock, merged into one plan. It adds closed-loop
sessions, log-uniform, uniform and lognormal length distributions, and
shared system prompts. Every length and every gap is a quantile of its
distribution at ``(i + 0.5) / n``: each seed carries the same multiset of
sizes and gaps. A closed loop's seed also orders the sessions' requests
(balanced, below); an open loop's seed draws only the token ids.

A mix file (``traffic/<name>.json``) holds:

* ``loop``: ``"closed"`` (``sessions`` clients, each sending its next
  request as soon as the last one finished) or ``"open"`` (Poisson
  arrivals at ``rate_rps``, starting ``lead_in_s`` before the window).
  An open loop's lead-in, window and follow-up each get
  ``round(rate_rps * seconds)`` arrivals whose gaps are the exponential's
  quantiles, scaled to fill that span: Poisson arrivals given their
  count. Their order, with the sizes and system prompts they carry, is
  one fixed draw for every seed (the seed draws the token ids): where in
  the window a long answer starts decides how much of it the window
  receives, so a seeded order changed the work from seed to seed;
* ``prompt`` / ``output``: a length distribution (``loguniform`` with
  ``lo``/``hi`` and ``strata``; ``uniform`` with ``lo``/``hi``;
  ``lognormal`` with ``median``/``sigma`` clipped to ``lo``/``hi``);
* ``system_prompts`` (optional): ``count`` shared prefixes of ``len``
  tokens, each prompt starting with one of them in equal shares;
* ``serving``: the pool and batch the traffic needs;
* ``rehearsal``: the same keys, at the size of the CPU rehearsal.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclass(frozen=True)
class Req:
    prompt: np.ndarray          # [T] int32
    max_new: int
    due_s: Optional[float]      # open loop: seconds after the window opens
    session: int = -1           # closed loop: owning session


@dataclass
class Plan:
    loop: str
    arrivals: List[Req] = field(default_factory=list)         # open loop
    sessions: List[List[Req]] = field(default_factory=list)   # closed loop


def quantile_len(dist: Dict, q: float) -> int:
    """The ``q`` quantile of a length distribution, as a whole number of
    tokens inside its bounds."""
    kind, lo, hi = dist["dist"], dist["lo"], dist["hi"]
    if kind == "uniform":
        x = lo + q * (hi - lo)
    elif kind == "loguniform":
        x = lo * (hi / lo) ** q
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(q))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(x), lo), hi))


def quantile_lens(dist: Dict, n: int) -> List[int]:
    return [quantile_len(dist, (i + 0.5) / n) for i in range(n)]


def equal_halves(lens: List[int]) -> List[int]:
    """Indices of the half of ``lens`` whose sum is nearest half the total
    (the other half is the rest)."""
    n, total = len(lens), sum(lens)
    best = min((abs(2 * sum(lens[i] for i in c) - total), c)
               for c in itertools.combinations(range(n), n // 2)
               if 0 in c)
    return list(best[1])


def balanced_order(lens: List[int], rng: np.random.Generator) -> List[int]:
    """A seeded order of the strata ``lens`` whose even positions hold one
    half of them and odd positions the other, the halves of (nearly)
    equal total length. Sessions that start two positions apart then
    hold, at each step, one half or the other: about the same work
    whatever the seed."""
    half = equal_halves(lens)
    rest = [i for i in range(len(lens)) if i not in half]
    a, b = [list(rng.permutation(h)) for h in (half, rest)]
    if rng.integers(2):
        a, b = b, a
    return [int(x) for pair in zip(a, b) for x in pair]


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng((seed, stream))


def build(mix: Dict, vocab: int, seed: int, window_s: float,
          tail_s: float = 0.0) -> Plan:
    """The plan of one run: every request the run may send, in order. An
    open loop also sends arrivals for ``tail_s`` past the window."""
    rng = seed_rng(seed, 0)
    tok_rng = seed_rng(seed, 1)
    prefixes = []
    sp = mix.get("system_prompts")
    if sp:
        prefixes = [tok_rng.integers(0, vocab, sp["len"], dtype=np.int32)
                    for _ in range(sp["count"])]

    def prompt(n_tokens: int, prefix: Optional[int]) -> np.ndarray:
        head = prefixes[prefix] if prefix is not None else \
            np.zeros(0, np.int32)
        tail = tok_rng.integers(0, vocab, n_tokens, dtype=np.int32)
        return np.concatenate([head, tail])

    if mix["loop"] == "closed":
        n_strata = mix["prompt"]["strata"]
        p_lens = quantile_lens(mix["prompt"], n_strata)
        o_lens = quantile_lens(mix["output"], n_strata)
        p_order = balanced_order(p_lens, rng)
        o_order = balanced_order(o_lens, rng)
        per_session = mix["requests_per_session"]
        plan = Plan(loop="closed")
        for s in range(mix["sessions"]):
            reqs = []
            for i in range(per_session):
                k = (2 * s + i) % n_strata
                reqs.append(Req(prompt=prompt(p_lens[p_order[k]], None),
                                max_new=o_lens[o_order[k]], due_s=None,
                                session=s))
            plan.sessions.append(reqs)
        return plan

    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rate, lead = mix["rate_rps"], mix["lead_in_s"]
    order = seed_rng(0, 3)                     # the same schedule every seed
    plan = Plan(loop="open")
    for start, span in ((-lead, lead), (0.0, window_s),
                        (window_s, tail_s)):
        n = int(round(rate * span))
        if n == 0:
            continue
        gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                         for i in range(n)])[order.permutation(n)]
        due = start + span * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
            / gaps.sum()
        u_lens = [quantile_lens(mix["prompt"], n)[i]
                  for i in order.permutation(n)]
        o_lens = [quantile_lens(mix["output"], n)[i]
                  for i in order.permutation(n)]
        owner = [None] * n
        if prefixes:
            owner = [int(i % len(prefixes)) for i in order.permutation(n)]
        for i in range(n):
            plan.arrivals.append(Req(prompt=prompt(u_lens[i], owner[i]),
                                     max_new=o_lens[i], due_s=float(due[i])))
    return plan


def max_context(mix: Dict) -> int:
    """Longest prompt plus output the mix can send."""
    pre = mix["system_prompts"]["len"] if mix.get("system_prompts") else 0
    return pre + mix["prompt"]["hi"] + mix["output"]["hi"]
