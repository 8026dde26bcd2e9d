"""Reduction of a ``jax.profiler`` trace to the numbers the per-layer
metrics read.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a small neutral
form (also what the test fixture under ``tests/data`` holds):

    {"device": {"<line>": [[name, start_ns, dur_ns], ...]},   # chip 0
     "host":   [[name, start_ns, dur_ns], ...],               # bench.* spans
     "chips":  n}

Device lines are those of ``/device:TPU:<i>`` planes ("XLA Modules": one
event per program execution; "XLA Ops": one per operation, a Pallas
kernel among them). Host spans are the benchmark's own
``TraceAnnotation`` spans, on the same clock. The traced window runs from
the first host ``bench.step`` span's start to the last one's end: the
profiler starts and stops between engine ticks, so every tick in it is
whole.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINES = ("XLA Modules", "XLA Ops")
STEP_SPAN = "bench.step"


def load_xplane(log_dir: str) -> Dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(sorted(paths)[-1])
    device: Dict[str, List] = {}
    host: List = []
    chips = 0
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chips += 1
            if int(m.group(1)) != 0:
                continue
            for line in plane.lines:
                if line.name in LINES:
                    device.setdefault(line.name, []).extend(
                        [op_name(e.name), int(e.start_ns),
                         int(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"device": device, "host": host, "chips": chips}


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def op_name(text: str) -> str:
    """An operation's name without its HLO text: the TPU trace names each
    op by its whole instruction (``%fusion.12 = bf16[...] fusion(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """An operation's name without its instance number (``fusion.12`` ->
    ``fusion``), so one kind of operation is summed under one name."""
    return re.sub(r"[._]\d+$", "", name)


def self_times(events) -> List[Tuple[str, int]]:
    """(name, duration less that of the events nested directly in it)."""
    out: List[List] = []
    stack: List[Tuple[int, int]] = []          # (end, index into out)
    for n, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(d, stack[-1][0] - s)
        out.append([n, d])
        stack.append((s + d, len(out) - 1))
    return [(n, d) for n, d in out]


@dataclass
class Trace:
    raw: Dict
    w0: int
    w1: int

    @classmethod
    def from_raw(cls, raw: Dict) -> Optional["Trace"]:
        """None where the trace holds no engine tick or no chip."""
        steps = [(s, s + d) for n, s, d in raw["host"] if n == STEP_SPAN]
        if not steps or not raw["chips"]:
            return None
        return cls(raw, min(a for a, _ in steps), max(b for _, b in steps))

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def events(self, line: str) -> List[Tuple[str, int, int]]:
        """Events of a device line that start inside the window."""
        return [(n, s, d) for n, s, d in self.raw["device"].get(line, [])
                if self.w0 <= s < self.w1]

    def busy_intervals(self) -> List[Tuple[int, int]]:
        line = "XLA Ops" if self.raw["device"].get("XLA Ops") \
            else "XLA Modules"
        return _merge([(max(s, self.w0), min(s + d, self.w1))
                       for n, s, d in self.raw["device"].get(line, [])
                       if s < self.w1 and s + d > self.w0])

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy_intervals()) * 1e-9

    def time_of(self, line: str, pattern: str) -> Tuple[float, int]:
        """Seconds and count of the window's events on ``line`` whose
        name matches the regular expression ``pattern``."""
        rx = re.compile(pattern)
        hits = [d for n, s, d in self.events(line) if rx.search(n)]
        return sum(hits) * 1e-9, len(hits)

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` kinds of operation with the most self time: an op's
        time less that of the ops nested in it (a layer loop's ``while``
        holds its body's ops)."""
        tot: Dict[str, int] = {}
        for n, d in self_times(self.events("XLA Ops")
                               or self.events("XLA Modules")):
            tot[op_kind(n)] = tot.get(op_kind(n), 0) + d
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, d * 1e-9] for n, d in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest device-idle gaps in the window, each named by
        the innermost benchmark host span covering its middle ("idle" if
        the host was in none: waiting for work)."""
        busy = self.busy_intervals()
        edges = [self.w0] + [x for iv in busy for x in iv] + [self.w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        host = [(n, s, s + d) for n, s, d in self.raw["host"]]
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            mid = (a + b) // 2
            cover = [(e - s, n) for n, s, e in host if s <= mid < e]
            out.append([min(cover)[1] if cover else "idle", (b - a) * 1e-9])
        return out
