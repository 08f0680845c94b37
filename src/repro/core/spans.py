"""SpanLog: the program's own record of where the host's time goes.

A span is one interval of host work: its name, the job it serves (the
job's name), the iteration or request index, its start and end on
``time.perf_counter_ns()``, and its parent, the innermost span open when
it began. The spans of one request share ``(job, index)``. Counters are
plain ints beside the spans.

The log is always on and bounded: it keeps the last ``BOUND`` spans,
oldest dropped first. The executor and its sessions write about eight
spans per iteration, so the bound holds some 8,000 iterations (about nine
minutes of 70 ms iterations, and a 30 s benchmark window's 4,000 spans
many times over).

Counters are also kept while a job's step is traced: code that the step
runs calls ``count(name)`` as it is traced, and ``counting(counters)``
routes those calls into a log's counters (the adaptor does so around the
trace of each step it compiles). Such a count is per trace, not per
execution: it says which path the compiled program took, for instance
``ssm_scan.kernel`` or ``ssm_scan.xla`` (``models/ssm.py``).

Each span is mirrored into the profiler as a
``jax.profiler.TraceAnnotation`` named ``salus.<name>:<job>`` (``salus.<name>``
for a span of no job), which costs about a microsecond and records nothing
while no profiler runs: a profile shows the program's spans beside the
device's operations, on the trace's clock, at a constant offset from
``perf_counter_ns``.

A log is written by one thread at a time, as its executor is driven.
Span times are measurements only: no scheduling decision reads them.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict, deque
from typing import Any, Deque, Dict, Iterator, List, Optional

import jax

BOUND = 1 << 16  # spans kept

_tracing = threading.local()  # .counters: where this thread's count() calls go


class Span:
    """One interval; a context manager that records itself on exit."""

    __slots__ = ("name", "job", "index", "t0", "t1", "parent", "_log", "_mark")

    def __init__(self, log: "SpanLog", name: str, job: Optional[str], index: Optional[int]) -> None:
        self.name = name
        self.job = job
        self.index = index
        self.t0 = self.t1 = 0
        self.parent: Optional[Span] = None
        self._log: Optional[SpanLog] = log
        self._mark: Any = None

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def __enter__(self) -> "Span":
        log = self._log
        if log._open:
            self.parent = log._open[-1]
        log._open.append(self)
        label = f"salus.{self.name}" if self.job is None else f"salus.{self.name}:{self.job}"
        self._mark = jax.profiler.TraceAnnotation(label)
        self._mark.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.t1 = time.perf_counter_ns()
        self._mark.__exit__(*exc)
        log = self._log
        log._open.pop()
        log.spans.append(self)
        self._log = self._mark = None


class SpanLog:
    """The last ``bound`` closed spans, in the order they closed (a child
    before its parent), and the counters."""

    def __init__(self, bound: int = BOUND) -> None:
        self.spans: Deque[Span] = deque(maxlen=bound)
        self.counters: Dict[str, int] = defaultdict(int)
        self._open: List[Span] = []

    def span(self, name: str, job: Optional[str] = None, index: Optional[int] = None) -> Span:
        """``with log.span(name, job, index):`` records the block's interval."""
        return Span(self, name, job, index)

    def of(self, *names: str, job: Optional[str] = None) -> List[Span]:
        """The spans named any of ``names`` (of ``job``, if given), by start."""
        out = [s for s in self.spans if s.name in names and (job is None or s.job == job)]
        out.sort(key=lambda s: s.t0)
        return out

    def self_ns(self, spans: List[Span]) -> List[int]:
        """Each span's duration less its children's (its self time)."""
        inner = {id(s): 0 for s in spans}
        for s in self.spans:
            if s.parent is not None and id(s.parent) in inner:
                inner[id(s.parent)] += s.t1 - s.t0
        return [s.t1 - s.t0 - inner[id(s)] for s in spans]


@contextlib.contextmanager
def counting(counters: Dict[str, int]) -> Iterator[Dict[str, int]]:
    """Routes the ``count`` calls this thread makes inside the block into
    ``counters``."""
    prev = getattr(_tracing, "counters", None)
    _tracing.counters = counters
    try:
        yield counters
    finally:
        _tracing.counters = prev


def count(name: str) -> None:
    """Adds one to ``name`` in the counters ``counting`` routes to, if any."""
    counters = getattr(_tracing, "counters", None)
    if counters is not None:
        counters[name] = counters.get(name, 0) + 1
