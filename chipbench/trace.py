"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Reads only ``jax.profiler.ProfileData``. A device plane is one whose name
starts with ``/device:TPU``; its ``XLA Ops`` line holds the operations and
its ``XLA Modules`` line one event per program run. Host spans are the
benchmark's own ``TraceAnnotation`` events (names starting ``chipbench.``)
on the host plane; two of them are marks, of the traced window's start and
end. All times are in the trace's nanoseconds.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

DEVICE_PREFIX = "/device:TPU"
SPAN_PREFIX = "chipbench."
WINDOW_START = "chipbench.window_start"  # marks: the traced window is from the one
TRACE_END = "chipbench.trace_end"  # to the other


@dataclass
class Trace:
    window: Optional[Interval] = None  # the traced window, from mark to mark
    ops: List[List[Tuple[float, float, str]]] = field(default_factory=list)  # per device
    modules: List[Tuple[float, float, str]] = field(default_factory=list)  # all devices
    spans: List[Tuple[float, float, str]] = field(default_factory=list)  # host

    # -- reductions -------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9 if self.window else 0.0

    def busy_intervals(self, device: int) -> List[Interval]:
        """Union of the device's op intervals, clipped to the window."""
        lo, hi = self.window if self.window else (-float("inf"), float("inf"))
        ivs = sorted((max(s, lo), min(e, hi)) for s, e, _ in self.ops[device] if e > lo and s < hi)
        out: List[Interval] = []
        for s, e in ivs:
            if out and s <= out[-1][1]:
                out[-1] = (out[-1][0], max(out[-1][1], e))
            else:
                out.append((s, e))
        return out

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices."""
        if not self.ops:
            return 0.0
        tot = sum(e - s for d in range(len(self.ops)) for s, e in self.busy_intervals(d))
        return tot / len(self.ops) / 1e9

    def coverage(self) -> str:
        """Where the device's recorded operations start and end against the
        window, and how many there are: a trace that stops recording early
        shows as a last operation well before the window's end."""
        if not self.window or not self.ops:
            return "no window or no device operations"
        lo, hi = self.window
        parts = []
        for d, dev in enumerate(self.ops):
            if dev:
                first, last = min(s for s, _, _ in dev), max(e for _, e, _ in dev)
                parts.append(f"device {d}: {len(dev)} ops, first {(first - lo) / 1e9:+.3f} s, "
                             f"last {(last - hi) / 1e9:+.3f} s from the window's end")
        return "; ".join(parts)

    def top_ops(self, n: int = 10) -> List[List]:
        per: Dict[str, float] = defaultdict(float)
        lo, hi = self.window if self.window else (-float("inf"), float("inf"))
        for dev in self.ops:
            for s, e, name in dev:
                if e > lo and s < hi:
                    per[name] += (min(e, hi) - max(s, lo)) / 1e9
        return [[k, v] for k, v in sorted(per.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10, device: int = 0) -> List[List]:
        """The longest gaps between device operations inside the window,
        each named by the innermost host span open at its midpoint."""
        if not self.ops or not self.window:
            return []
        busy = self.busy_intervals(device)
        edges = [self.window[0]] + [x for iv in busy for x in iv] + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            out.append([self.span_at((s + e) / 2), (e - s) / 1e9])
        return out

    def span_at(self, t: float) -> str:
        best = None
        for s, e, name in self.spans:
            if s <= t <= e and e > s and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "no span (waiting for a request)"

    def module_time(self, needle: str) -> Tuple[float, int]:
        """(device seconds, runs) of the programs whose name holds ``needle``."""
        lo, hi = self.window if self.window else (-float("inf"), float("inf"))
        runs = [(s, e) for s, e, name in self.modules if needle in name and s >= lo and e <= hi]
        return sum(e - s for s, e in runs) / 1e9, len(runs)


def op_name(name: str) -> str:
    """An op event's name up to `` = `` (TPU traces carry the whole HLO
    instruction in it)."""
    return name.split(" = ", 1)[0].strip()


def load(path: str) -> Trace:
    """``path``: an ``.xplane.pb`` file, or a directory to search for one."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    tr = Trace()
    marks = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops: List[Tuple[float, float, str]] = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name))
                            for ev in line.events]
                elif line.name == "XLA Modules":
                    tr.modules += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                                   for ev in line.events]
            tr.ops.append(ops)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (WINDOW_START, TRACE_END):
                        marks[ev.name] = ev.start_ns
                    elif ev.name.startswith(SPAN_PREFIX):
                        tr.spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    if len(marks) == 2:
        tr.window = (marks[WINDOW_START], marks[TRACE_END])
    return tr
