"""End-to-end metric arithmetic on client-side timestamps.

A request's clock starts when it was due (open loop) or sent (closed
loop). The window is ``[w0, w1)``. A request belongs to the window when
its clock starts inside it; a token belongs to the window when it was
received inside it, whichever request it came from. A request that
failed (shed, aborted by the engine, an error, or no first token by the
end of the follow-up) counts as missing every latency: an infinite TTFT
and one infinite inter-token gap."""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

FAILED_REASONS = ("shed", "abort", "error")


@dataclass
class Rec:
    """One request as its client saw it (monotonic seconds)."""
    prompt_len: int
    max_new: int
    due: Optional[float] = None        # open loop: when it was due
    sent: Optional[float] = None       # when the client called submit
    t_tok: List[float] = field(default_factory=list)   # per token received
    tokens: List[int] = field(default_factory=list)
    finish: Optional[str] = None       # engine's finish reason, if finished
    cut: bool = False                  # still running when the run ended
    cached_tokens: int = 0             # prompt tokens the prefix cache served
    prompt: object = None
    request: object = None             # the engine's Request, once accepted

    @property
    def start(self) -> float:
        return self.due if self.due is not None else self.sent

    @property
    def failed(self) -> bool:
        if self.finish in FAILED_REASONS and not self.cut:
            return True
        return not self.t_tok

    @property
    def ttft(self) -> float:
        return math.inf if self.failed else self.t_tok[0] - self.start


def quantile(values: Sequence[float], q: float) -> float:
    """Nearest-rank quantile (inf-safe): the smallest value with at least
    a share ``q`` of the sample at or below it."""
    if not values:
        return math.nan
    s = sorted(values)
    return s[max(math.ceil(q * len(s)) - 1, 0)]


def in_window(t: float, w0: float, w1: float) -> bool:
    return w0 <= t < w1


def window_requests(recs: Sequence[Rec], w0: float, w1: float) -> List[Rec]:
    return [r for r in recs if r.start is not None
            and in_window(r.start, w0, w1)]


def tokens_in_window(recs: Sequence[Rec], w0: float, w1: float) -> int:
    return sum(1 for r in recs for t in r.t_tok if in_window(t, w0, w1))


def gaps_in_window(recs: Sequence[Rec], w0: float, w1: float) -> List[float]:
    """Every inter-token gap whose later token arrived inside the window,
    over all requests, plus one infinite gap per failed window request."""
    gaps = [b - a for r in recs for a, b in zip(r.t_tok, r.t_tok[1:])
            if in_window(b, w0, w1)]
    gaps += [math.inf for r in window_requests(recs, w0, w1) if r.failed]
    return gaps


def ttfts(recs: Sequence[Rec], w0: float, w1: float) -> List[float]:
    return [r.ttft for r in window_requests(recs, w0, w1)]


def end_to_end(recs: Sequence[Rec], w0: float, w1: float) -> dict:
    """Every end-to-end number a cell may report, plus the counts."""
    win = window_requests(recs, w0, w1)
    tt = ttfts(recs, w0, w1)
    return {
        "output_tok_s": tokens_in_window(recs, w0, w1) / (w1 - w0),
        "itl_p95_ms": 1e3 * quantile(gaps_in_window(recs, w0, w1), 0.95),
        "ttft_p50_ms": 1e3 * quantile(tt, 0.50),
        "ttft_p90_ms": 1e3 * quantile(tt, 0.90),
        "attempted": len(win),
        "failed": sum(r.failed for r in win),
        "n_gaps": len(gaps_in_window(recs, w0, w1)),
    }


def lateness(recs: Sequence[Rec]) -> List[float]:
    """Open loop: how late the generator submitted each request."""
    return [r.sent - r.due for r in recs
            if r.due is not None and r.sent is not None]
