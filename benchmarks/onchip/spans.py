#!/usr/bin/env python3
"""Device-idle time of a traced run put down to the serving program's own
spans: ``serve.*``, which ``Tracer.span`` writes as profiler annotations
on the device's clock (``docs/serving.md``, "Profiler spans").

    python3 benchmarks/onchip/spans.py --out <dir> [--ticks 6] \\
        -- --workload <cell> --seed <n> --seconds <s> --trace 1

makes one traced run of ``run.py`` with a trace loader that keeps the
program's spans beside the benchmark's ``bench.*`` ones (the window is
still that of the ``bench.step`` spans), and writes the reduced trace
whole (``trace_full.json.gz``) and as a trimmed slice of ``--ticks``
engine ticks (``trace_trimmed.json``, the form of ``tests/data``). To
standard error it prints the window's device-idle seconds by innermost
span and the idle milliseconds per engine tick under each layer's spans
(``layer_idle``). The run's own result line follows as usual; its
``breakdown.idle_gaps`` then names the program's spans.

    python3 benchmarks/onchip/spans.py --cost 200000

prints the host microseconds one ``Tracer.span`` enter and exit costs,
with the profiler off and on.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

import tracefile  # noqa: E402

PROGRAM = "serve."
PROGRAM_STEP = "serve.step"
NO_SPAN = "idle"
SCHEDULER = ("serve.admit", "serve.plan", "serve.emit")
EXECUTOR = ("serve.prefill.", "serve.decode.", "serve.verify", PROGRAM_STEP)
_load_xplane = tracefile.load_xplane


def program_spans(log_dir: str) -> List[List]:
    """The host events of the newest ``.xplane.pb`` under ``log_dir``
    whose names start with ``serve.``, as ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(sorted(paths)[-1])
    return [[e.name, int(e.start_ns), int(e.duration_ns)]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM)]


def load(log_dir: str) -> Dict:
    """``tracefile.load_xplane``'s reduction, with the program's spans
    among the host events."""
    raw = _load_xplane(log_dir)
    raw["host"] = raw["host"] + program_spans(log_dir)
    return raw


def idle_intervals(trace: tracefile.Trace) -> List[Tuple[int, int]]:
    """The window's device-idle intervals, in order."""
    edges = [trace.w0] + [x for iv in trace.busy_intervals() for x in iv] \
        + [trace.w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(trace: tracefile.Trace) -> Dict[str, float]:
    """Device-idle seconds of the window by the innermost host span over
    them (the shortest covering one; ``"idle"`` where the host was in
    none), cut exactly at every span edge: each idle nanosecond goes to
    one name, so the values sum to the window's idle time."""
    edges = []                       # (ns, +1 opens / -1 closes, span)
    for i, (n, s, d) in enumerate(trace.raw["host"]):
        a, b = max(s, trace.w0), min(s + d, trace.w1)
        if a < b:
            edges += [(a, 1, (d, i, n)), (b, -1, (d, i, n))]
    for a, b in idle_intervals(trace):
        edges += [(a, 1, None), (b, -1, None)]
    edges.sort(key=lambda e: e[0])
    out: Dict[str, int] = {}
    open_spans: set = set()
    idle, x0 = 0, trace.w0
    for x, step, span in edges:
        if idle and x > x0:
            name = min(open_spans)[2] if open_spans else NO_SPAN
            out[name] = out.get(name, 0) + x - x0
        x0 = x
        if span is None:
            idle += step
        elif step > 0:
            open_spans.add(span)
        else:
            open_spans.discard(span)
    return {n: v * 1e-9 for n, v in out.items()}


def layer_of(span: str) -> str:
    """The layer whose time a span is: the scheduler's admit, plan and
    emit; the executor's phase programs and the rest of the engine tick;
    the front end for everything outside ``serve.step`` (the owner loop's
    drain and fan-out, client tasks, the benchmark's recorder, none)."""
    if span in SCHEDULER:
        return "scheduler"
    if span.startswith(EXECUTOR):
        return "executor"
    return "frontend"


def ticks(trace: tracefile.Trace) -> int:
    return sum(1 for n, s, _ in trace.raw["host"]
               if n == PROGRAM_STEP and trace.w0 <= s < trace.w1)


def layer_idle_ms_per_tick(trace: Optional[tracefile.Trace]
                           ) -> Optional[Dict[str, float]]:
    """Device-idle milliseconds per engine tick under each layer's spans
    (``layer_of``); they sum to the window's idle time over its
    ``serve.step`` count. None where the trace holds no program span."""
    if trace is None or not ticks(trace):
        return None
    out = dict.fromkeys(("scheduler", "executor", "frontend"), 0.0)
    for name, s in idle_by_span(trace).items():
        out[layer_of(name)] += s
    return {k: 1e3 * v / ticks(trace) for k, v in out.items()}


def span_cost(n: int) -> Dict[str, float]:
    """Host microseconds per ``Tracer.span`` enter and exit of a disabled
    tracer (the served path's), with the profiler off and recording."""
    import jax

    from repro.serving.tracing import Tracer
    span = Tracer(enabled=False).span

    def per_span() -> float:
        t = time.perf_counter()
        for _ in range(n):
            with span("serve.plan"):
                pass
        return 1e6 * (time.perf_counter() - t) / n
    off = per_span()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        on = per_span()
        jax.profiler.stop_trace()
    return {"profiler_off_us": off, "profiler_on_us": on, "spans": n}


def report(raw: Dict, out_dir: str, n_ticks: int) -> None:
    import record_trace
    with gzip.open(os.path.join(out_dir, "trace_full.json.gz"), "wt") as f:
        json.dump(raw, f)
    if any(n == tracefile.STEP_SPAN for n, _, _ in raw["host"]):
        with open(os.path.join(out_dir, "trace_trimmed.json"), "w") as f:
            json.dump(record_trace.trim(raw, n_ticks), f)
    trace = tracefile.Trace.from_raw(raw)
    if trace is None:
        return
    by = sorted(idle_by_span(trace).items(), key=lambda kv: -kv[1])
    idle = trace.window_s - trace.busy_s
    print(f"window {trace.window_s:.6f}s, busy {trace.busy_s:.6f}s, idle "
          f"{idle:.6f}s, {ticks(trace)} serve.step spans", file=sys.stderr)
    print("device-idle s by innermost span: " + json.dumps(dict(by)),
          file=sys.stderr)
    print("layer_idle " + json.dumps(layer_idle_ms_per_tick(trace)),
          file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--ticks", type=int, default=6)
    ap.add_argument("--cost", type=int, default=0,
                    help="time this many spans instead of a run")
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.cost:
        print(json.dumps(span_cost(args.cost)))
        return 0
    os.makedirs(args.out, exist_ok=True)
    import run

    def load_and_report(log_dir: str) -> Dict:
        raw = load(log_dir)
        report(raw, args.out, args.ticks)
        return raw
    tracefile.load_xplane = load_and_report
    rest = args.run_args[1:] if args.run_args[:1] == ["--"] \
        else args.run_args
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
