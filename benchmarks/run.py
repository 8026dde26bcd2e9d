"""Benchmark aggregator: one section per paper table/figure + kernels +
roofline + serving.  Prints ``name,value,unit,paper`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--only fig5,kernels]
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filters on suite names")
    args = ap.parse_args(argv)

    from benchmarks import kernel_micro, paper_figs, roofline_table, serving_bench
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    suites = []
    for mod in (paper_figs, kernel_micro, roofline_table, serving_bench):
        for fn in mod.ALL:
            suites.append((f"{mod.__name__.split('.')[-1]}.{fn.__name__}", fn))

    if args.only:
        keys = [k.strip() for k in args.only.split(",")]
        suites = [(n, f) for n, f in suites
                  if any(k in n for k in keys)]

    print("name,value,unit,paper")
    n_rows = 0
    for name, fn in suites:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # a failing suite must not hide the others
            print(f"{name}.ERROR,nan,,{type(e).__name__}")
            continue
        for rname, value, unit, paper in rows:
            if isinstance(value, float):
                print(f"{rname},{value:.6g},{unit},{paper}")
            else:
                print(f"{rname},{value},{unit},{paper}")
            n_rows += 1
        print(f"# {name}: {len(rows)} rows in {time.time()-t0:.1f}s",
              file=sys.stderr)
    print(f"# total rows: {n_rows}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
