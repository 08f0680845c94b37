"""The program's own spans for the per-layer readers, and their mapping onto
a profiler trace's clock.

The program keeps a span log (``repro.core.spans``) on its executor,
``run.executor.spans``: each span's name, job, index, and start and end on
``time.perf_counter_ns()``. A program that has none gives the readers
nothing, and each returns None.

The trace's host events are on another clock, at a constant offset from
``perf_counter_ns``. The offset is read from the benchmark's own
``run_iteration`` spans, which are both in ``run.spans`` (perf_counter
seconds) and, annotated, in ``run.trace.spans`` (trace ns): the median of
their start differences, its interquartile spread logged. In a traced run
the readers take only the spans that start in the traced part, put on the
program's clock by that offset.
"""
from __future__ import annotations

import re
import statistics
import sys
from typing import Any, List, Optional, Tuple

import numpy as np

from chipbench import trace as tracemod


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def span_log(run: Any) -> Any:
    return getattr(run.executor, "spans", None)


def in_window(run: Any, *names: str, job: Optional[str] = None) -> List[Any]:
    """The program's spans named any of ``names`` that start in the window;
    in a traced run, in its traced part only. The profiler records the
    window's first ``run.TRACE_SECONDS``, and the thread that stops it
    slows the host for the rest of the window, so a traced run is read
    where its trace is. A traced run whose spans cannot be put on the
    trace's clock gives nothing."""
    spans = span_log(run)
    if spans is None:
        return []
    lo, hi = run.t_w0 * 1e9, run.window_end() * 1e9
    if run.trace is not None:
        off = trace_offset_ns(run)
        if off is None or run.trace.window is None:
            return []
        lo, hi = (t - off for t in run.trace.window)
    return [s for s in spans.of(*names, job=job) if lo <= s.t0 < hi]


def mean_ms(ns: List[int]) -> Optional[float]:
    return sum(ns) / len(ns) / 1e6 if ns else None


def trace_offset_ns(run: Any) -> Optional[float]:
    """Trace ns less ``perf_counter_ns`` for the same instant: the median
    over the benchmark's ``run_iteration`` spans in the trace, paired in
    order with its own, job by job. Read once a run, kept in ``run.prog``."""
    if run.trace is None:
        return None
    if "trace_offset_ns" in run.prog:
        return run.prog["trace_offset_ns"]
    in_trace: dict = {}
    for s, _, name in sorted(run.trace.spans):
        kind, _, job = name[len(tracemod.SPAN_PREFIX):].partition(":")
        if kind == "run_iteration":
            in_trace.setdefault(job, []).append(s)
    offsets = [t - mine.t0 * 1e9 for job, starts in in_trace.items()
               for t, mine in zip(starts, run.spans.of("run_iteration", job))]
    q2 = None
    if len(offsets) >= 2:
        q1, q2, q3 = statistics.quantiles(offsets, n=4)
        log(f"program spans on the trace's clock: offset {q2:.0f} ns, interquartile spread "
            f"{(q3 - q1) / 1e3:.3f} us over {len(offsets)} spans")
    run.prog["trace_offset_ns"] = q2
    return q2


def switch_gaps(run: Any) -> Optional[List[Tuple[str, float]]]:
    """``(incoming job, ns)`` for each switch whose incoming iteration starts
    in the traced window: the device's idle from the end of the last
    operation before that iteration's span to its first operation. Logs
    the same gap at the traced boundaries between two iterations of one
    job, for comparison; the switches counted three ways (the program's
    counter, its spans, the device's programs in the trace); and where
    the traced idle time lies among the program's spans."""
    spans = span_log(run)
    if spans is None or run.trace is None or not run.trace.ops or run.trace.window is None:
        return None
    off = trace_offset_ns(run)
    if off is None:
        return None
    lo, hi = run.trace.window
    ops = run.trace.ops[0]
    starts = np.array([s for s, _, _ in ops], np.float64)
    order = np.argsort(starts)
    starts = starts[order]
    ends_before = np.maximum.accumulate(np.array([e for _, e, _ in ops], np.float64)[order])
    its = spans.of("iteration")
    gaps, same, traced = [], [], 0
    for a, b in zip(its, its[1:]):
        t0, t1 = b.t0 + off, b.t1 + off
        if not lo <= t0 < hi:
            continue
        traced += a.job != b.job
        k = int(np.searchsorted(starts, t0))
        if 0 < k < len(starts) and starts[k] <= t1:
            gap = float(starts[k] - ends_before[k - 1])
            (gaps if a.job != b.job else same).append((b.job, gap))
    programs = [(s, re.sub(r"\(\d+\)$", "", name)) for s, _, name in sorted(run.trace.modules)]
    on_device = sum(x[1] != y[1] and lo <= y[0] < hi for x, y in zip(programs, programs[1:]))
    log(f"switches: {spans.counters['switches']} by the program's counter over the run "
        f"({sum(x.job != y.job for x, y in zip(its, its[1:]))} in its span log); in the traced "
        f"part {traced} by the program's spans, {on_device} by the device's programs in the trace; "
        f"{len(gaps)} with device operations on both sides")
    for job in sorted({j for j, _ in gaps}):
        log(f"switch gaps to {job} (ms): {[round(g / 1e6, 4) for j, g in gaps if j == job]}")
    if same:
        log(f"same-job boundary gaps, for comparison: median "
            f"{statistics.median(g for _, g in same) / 1e6:.4f} ms over {len(same)}")
    idle_in_spans(run, spans, off)
    return gaps


def idle_in_spans(run: Any, spans: Any, off: float, n: int = 10) -> None:
    """Logs the share of the traced window's device idle time that lies
    inside some program span, and the ``n`` longest idle gaps, each split
    by the innermost program span open over each part of it."""
    lo, hi = run.trace.window
    busy = run.trace.busy_intervals(0)
    b0 = np.array([s for s, _ in busy], np.float64)
    b1 = np.array([e for _, e in busy], np.float64)
    done = np.concatenate([[0.0], np.cumsum(b1 - b0)])

    def busy_until(t: float) -> float:
        k = int(np.searchsorted(b0, t, side="right"))
        return float(done[k] - (max(0.0, b1[k - 1] - t) if k else 0.0))

    mapped = sorted(((max(s.t0 + off, lo), min(s.t1 + off, hi), s) for s in spans.spans
                     if s.t1 + off > lo and s.t0 + off < hi), key=lambda m: m[:2])
    merged: List[List[float]] = []
    for s, e, _ in mapped:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    idle = (hi - lo) - busy_until(hi)
    covered = sum((e - s) - (busy_until(e) - busy_until(s)) for s, e in merged)
    log(f"device idle in the traced window: {idle / 1e6:.3f} ms, {100 * covered / idle if idle else 0:.2f}% "
        "of it inside a program span")
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    holes = sorted(((edges[i + 1] - edges[i], edges[i]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]), reverse=True)[:n]
    named = []
    for length, g0 in holes:
        parts = ", ".join(f"{name} {ns / 1e6:.3f}" for name, ns in split(g0, g0 + length, mapped))
        named.append(f"{length / 1e6:.3f} ms ({parts})")
    log("longest idle gaps, split by the innermost program span (ms): " + "; ".join(named))


def split(g0: float, g1: float, mapped: List[Tuple[float, float, Any]]) -> List[Tuple[str, float]]:
    """The ns of ``[g0, g1]`` under each innermost span (the open span that
    began last), most first; ``no program span`` where none is open."""
    inside = [(max(s, g0), min(e, g1), sp) for s, e, sp in mapped if e > g0 and s < g1]
    cuts = sorted({g0, g1, *(x for s, e, _ in inside for x in (s, e))})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [sp for s, e, sp in inside if s <= a and b <= e]
        sp = max(open_, key=lambda x: x.t0) if open_ else None
        name = "no program span" if sp is None else f"{sp.name}:{sp.job}"
        out[name] = out.get(name, 0.0) + (b - a)
    return sorted(out.items(), key=lambda kv: -kv[1])
