#!/usr/bin/env python3
"""One traced run of a cell that also keeps its trace, reduced to the
neutral form of ``tracefile.load_xplane``: whole, gzipped, and a trimmed
slice of a few engine ticks for ``tests/data`` (the fixture the trace
reduction's test reads).

    python3 benchmarks/onchip/record_trace.py --out <dir> --ticks 6 \\
        -- --workload <cell> --seed <n> --seconds <s> --trace 1

Prints the trace's device planes and lines with their event counts, and
the most frequent operation names, to standard error.
"""

from __future__ import annotations

import argparse
import collections
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def trim(raw, ticks: int):
    """The first ``ticks`` engine-tick host spans that hold at least one
    prefill and one decode program, and every event inside them."""
    steps = sorted((s, d) for n, s, d in raw["host"] if n == "bench.step")
    mods = raw["device"].get("XLA Modules", [])

    def kinds(s, d):
        return {k for k in ("packed_paged", "decode_paged")
                for n, a, _ in mods if k in n and s <= a < s + d}
    for i in range(len(steps)):
        sel = steps[i:i + ticks]
        got = set().union(*(kinds(s, d) for s, d in sel)) if sel else set()
        if len(got) == 2:
            break
    a, b = sel[0][0], sel[-1][0] + sel[-1][1]

    def keep(ev):
        return [e for e in ev if a <= e[1] < b]
    return {"device": {k: keep(v) for k, v in raw["device"].items()},
            "host": keep(raw["host"]), "chips": raw["chips"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--ticks", type=int, default=6)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    import run
    import tracefile
    load = tracefile.load_xplane

    def keep_and_load(log_dir):
        import glob
        from jax.profiler import ProfileData
        for path in glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                              recursive=True):
            for plane in ProfileData.from_file(path).planes:
                print(f"plane {plane.name!r}: lines "
                      f"{[(ln.name, len(list(ln.events))) for ln in plane.lines]}",
                      file=sys.stderr)
        raw = load(log_dir)
        with gzip.open(os.path.join(args.out, "trace_full.json.gz"),
                       "wt") as f:
            json.dump(raw, f)
        with open(os.path.join(args.out, "trace_trimmed.json"), "w") as f:
            json.dump(trim(raw, args.ticks), f)
        for line, ev in raw["device"].items():
            names = collections.Counter(tracefile.op_kind(e[0]) for e in ev)
            print(f"device line {line!r}: {len(ev)} events; most frequent "
                  f"{names.most_common(12)}", file=sys.stderr)
        print(f"chips {raw['chips']}; host spans "
              f"{collections.Counter(e[0] for e in raw['host'])}",
              file=sys.stderr)
        return raw
    tracefile.load_xplane = keep_and_load
    rest = args.run_args[1:] if args.run_args[:1] == ["--"] \
        else args.run_args
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
