"""Session: the executor-side handle of one DL job (paper §3.1).

A session owns the job's *persistent* state (live param/optimizer device
arrays — they stay resident across switches: that IS fast job switching on
XLA) and yields iterations to the executor. The adaptor creates sessions
from user-level step functions without the user script changing.

The state lives on the host while the job holds no lane: before admission,
while paged out, and after the job ends. The executor calls :meth:`place`
when the memory manager admits the job and :meth:`release` when it lets go
of it, so the device holds only what admission control has counted.

Each of those calls, and each iteration's input, dispatch and wait for the
device, is a span in :attr:`Session.spans`: the executor's log once the
session is submitted (``core/spans.py``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, Optional

import jax

from repro.core.spans import SpanLog
from repro.core.types import JobSpec, MemoryProfile


class Session:
    """Wraps (step_fn, state, data source) into an iteration supplier."""

    def __init__(
        self,
        name: str,
        step_fn: Callable,  # (state, batch) -> (state, metrics)
        init_state: Any,
        data_fn: Callable[[int], Any],  # step index -> batch
        n_iters: int,
        profile: MemoryProfile,
        iter_time: float = 0.01,
        utilization: float = 1.0,
        arrival_time: float = 0.0,
        kind: str = "train",
        priority: Optional[int] = None,
        request_times: Optional[tuple] = None,  # open-loop request stream
        executable: Optional[Any] = None,  # step_fn compiled ahead of time
    ) -> None:
        self.name = name
        self.step_fn = step_fn
        self.executable = executable
        self.state = init_state
        self.data_fn = data_fn
        self.n_iters = n_iters
        self.iterations_run = 0
        self.metrics_log = []
        self.spans = SpanLog()  # the executor hands its own at submit
        self.job = JobSpec(
            name=name,
            profile=profile,
            n_iters=n_iters,
            iter_time=iter_time,
            utilization=utilization,
            arrival_time=arrival_time,
            kind=kind,
            priority=priority,
            request_times=request_times,
            run_iteration=self.run_iteration,
        )

    def place(self, device: Any) -> None:
        """Put host-side state on ``device`` and block until it is there.
        Leaves that are already device arrays stay where they are (a
        migration may have landed them with a mesh-aware put). An
        ahead-of-time executable compiled for another device (a fleet
        places jobs after profiling them) is compiled again here, for
        where the state now lives, so the first iteration does not."""
        with self.spans.span("session.place", self.name):
            leaves = jax.tree_util.tree_leaves(self.state)
            if any(not isinstance(x, jax.Array) for x in leaves):
                self.state = jax.device_put(self.state, device)
                jax.block_until_ready(self.state)
            if self.executable is not None and not _compiled_for(self.executable, device):
                with self.spans.span("session.compile", self.name):
                    self.executable = self.step_fn.lower(self.state, self.data_fn(0)).compile()

    def release(self) -> None:
        """Move the state to host (numpy) buffers, freeing its device memory."""
        with self.spans.span("session.release", self.name):
            self.state = jax.device_get(self.state)

    def run_iteration(self, index: int) -> float:
        """Execute one iteration on-device; returns wall seconds. Blocks
        until the computation is done (the executor serializes within a
        lane, matching iteration-granularity scheduling)."""
        t0 = time.perf_counter()
        with self.spans.span("session.data_fn", self.name, index):
            batch = self.data_fn(index)
        fn = self.executable if self.executable is not None else self.step_fn
        with self.spans.span("session.dispatch", self.name, index):
            out = fn(self.state, batch)
        if isinstance(out, tuple):
            self.state, metrics = out
        else:
            self.state, metrics = out, None
        with self.spans.span("session.block", self.name, index):
            jax.block_until_ready(self.state)
        self.iterations_run += 1
        if metrics is not None:
            self.metrics_log.append(metrics)
        return time.perf_counter() - t0

    @property
    def finished(self) -> bool:
        return self.iterations_run >= self.n_iters


def _compiled_for(executable: Any, device: Any) -> bool:
    """Whether every input of a compiled executable lives on ``device``."""
    shardings = jax.tree_util.tree_leaves(executable.input_shardings)
    return all(sh.device_set == {device} for sh in shardings)
