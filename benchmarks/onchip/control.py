#!/usr/bin/env python3
"""The readings a cell's correctness limit is set from, on the chip.

    python3 benchmarks/onchip/control.py --workload <cell> \\
        --seconds <s> --seeds 11 12 13 [--program-int8] [--cpu-rehearsal]

For each seed, in this one process, a run of the cell as the benchmark
runs it (a window of ``--seconds`` at the cell's own load), printing
each reading with ``correct`` as the run's own verdict (``run.verdict``)
gives it at the cell's limit:

* ``sound``: the program's mean and widest gap, served bf16 tokens
  against the float32 reference (the limit's lower reading is the
  largest mean over a dozen seeds or more); ``correct`` must be true;
* ``control_ref_int8``: the control, the reference computed with int8
  weights put in the program's place: at the same positions, the gap of
  the token it puts first; ``correct`` must be false;
* ``served_configuration``: the served tokens against the reference
  computed as the program runs the model (its own q/k-norm eps), to show
  that the sound readings do not hang on that departure;
* with ``--program-int8``, ``control_program_int8``: a second run with
  the program's own int8 weight path switched on, its served tokens
  against the float32 reference; ``correct`` must be false.

The limit lies above every sound reading and below the smallest control
reading (``PERF.md`` gives the readings). The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def readings(workload: str, seed: int, seconds: float, program_int8: bool,
             rehearsal: bool) -> dict:
    args = SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=0, cpu_rehearsal=rehearsal)
    out = run.run(args, control=True)
    c, limit = out.check, out.check["limit"]

    def reading(r: dict) -> dict:
        return {"mean_gap_logits": r["mean_gap_logits"],
                "max_gap_logits": r["max_gap_logits"],
                "tokens": r["tokens"], "correct": run.verdict(r, limit)}

    row = {"seed": seed, "limit": limit,
           "sound": reading(c),
           "control_ref_int8": reading(c["control_int8_reference"]),
           "served_configuration": reading(c["served_configuration"]),
           "longest_context": c["longest_context"]}
    if program_int8:
        q = run.run(args, weights_dtype="int8")
        row["control_program_int8"] = reading(q.check)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-int8", action="store_true")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.seconds,
                                  args.program_int8, args.cpu_rehearsal)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
