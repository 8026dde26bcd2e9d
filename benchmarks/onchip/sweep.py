#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, once, on the chip.

    python3 benchmarks/onchip/sweep.py --workload <cell> --seconds <s> \\
        --seeds 11 12 13 --rates 0.3 0.45 0.6

Runs of the cell in this one process, each with the mix's ``rate_rps``
replaced, rate by rate from the lowest, every seed at each rate. A run
sustains its rate when no request of the window failed and the queue
(requests due and still waiting for their first token), averaged over
the window's last ``EDGE_S`` seconds, holds at most one request more than
over its first ``EDGE_S`` seconds. A rate is sustained only when every
seed's run sustains it; the sweep stops at the first rate that is not.
Prints one JSON line per run and one per rate. The benchmark's own runs
never run this: the rate chosen is written into the mix file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402

EDGE_S = 10.0


def queue(recs, t: float) -> int:
    """Requests due by ``t`` that had no token by ``t``."""
    return sum(1 for r in recs if r.start is not None and r.start <= t
               and not (r.t_tok and r.t_tok[0] <= t))


def mean_queue(recs, t0: float, t1: float, step: float = 0.1) -> float:
    return float(np.mean([queue(recs, t) for t in np.arange(t0, t1, step)]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    for rate in sorted(args.rates):
        ok = True
        for seed in args.seeds:
            out = run.run(SimpleNamespace(
                workload=args.workload, seed=seed, seconds=args.seconds,
                trace=0, cpu_rehearsal=args.cpu_rehearsal),
                mix_overrides={"rate_rps": rate})
            q0 = mean_queue(out.recs, out.w0, out.w0 + EDGE_S)
            q1 = mean_queue(out.recs, out.w1 - EDGE_S, out.w1)
            e = out.e2e
            held = e["failed"] == 0 and q1 <= q0 + 1
            print(json.dumps({
                "rate_rps": rate, "seed": seed, "attempted": e["attempted"],
                "failed": e["failed"], "queue_open": q0, "queue_close": q1,
                "sustained": held, "output_tok_s": e["output_tok_s"],
                "ttft_p50_ms": e["ttft_p50_ms"],
                "ttft_p90_ms": e["ttft_p90_ms"],
                "itl_p95_ms": e["itl_p95_ms"],
                "correct": run.verdict(out.check, out.check["limit"])}),
                flush=True)
            if not held:
                ok = False
                break
        print(json.dumps({"rate_rps": rate, "sustained": ok}), flush=True)
        if not ok:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
