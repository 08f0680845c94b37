"""Framework adaptor (paper Fig. 3): users keep their training scripts; the
adaptor presents Salus as a virtual device.

    vdev = VirtualDevice(executor)
    sess = vdev.create_session(step_fn, state, data_fn, n_iters)   # (1a,1b)
    vdev.run()                                                     # (2a,2b)

Memory profiles are measured automatically by compiling one step
(``profiles.profile_executable``) when not supplied — the adaptor is the
only component that touches jit/compile, keeping user code unchanged. The
step is compiled from the shapes of the state and of one batch, for the
executor's device, so profiling puts no state on the device; the session
then runs that executable, and its first iteration does not compile again.
What the step's code counts as it is traced (``spans.count``) goes into the
executor's span log.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.executor import ExecutorReport, SalusExecutor
from repro.core.profiles import profile_executable
from repro.core.session import Session
from repro.core.spans import counting
from repro.core.types import MemoryProfile


class VirtualDevice:
    def __init__(self, executor: SalusExecutor) -> None:
        self.executor = executor
        self._sessions: List[Session] = []

    def create_session(
        self,
        name: str,
        step_fn: Callable,
        init_state: Any,
        data_fn: Callable[[int], Any],
        n_iters: int,
        profile: Optional[MemoryProfile] = None,
        utilization: float = 1.0,
        kind: str = "train",
        iter_time: float = 0.01,
        arrival_time: float = 0.0,
        priority: Optional[int] = None,
        request_times: Optional[tuple] = None,
    ) -> Session:
        """Register one job. ``iter_time``/``arrival_time`` are forwarded to
        the :class:`Session` verbatim — FAIR's service-rate computation and
        ``accounting="nominal"`` both read them off the JobSpec, so dropping
        them here would silently corrupt live scheduling decisions.
        ``request_times`` makes the session an open-loop inference service:
        iteration k serves the request arriving at ``request_times[k]``."""
        jitted = jax.jit(step_fn) if not hasattr(step_fn, "lower") else step_fn
        executable = None
        if profile is None:
            device = getattr(self.executor, "device", None)
            sharding = SingleDeviceSharding(device) if device is not None else None
            shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    jnp.shape(x), jnp.result_type(x), sharding=sharding
                ),
                (init_state, data_fn(0)),
            )
            log = getattr(self.executor, "spans", None)
            with counting(log.counters if log is not None else {}):
                lowered = jitted.lower(*shapes)
            executable = lowered.compile()
            profile = profile_executable(executable)
        sess = Session(
            name=name,
            step_fn=jitted,
            init_state=init_state,
            data_fn=data_fn,
            n_iters=n_iters,
            profile=profile,
            kind=kind,
            utilization=utilization,
            iter_time=iter_time,
            arrival_time=arrival_time,
            priority=priority,
            request_times=request_times,
            executable=executable,
        )
        self._sessions.append(sess)
        self.executor.submit(sess)
        return sess

    def run(self, max_wall: Optional[float] = None) -> ExecutorReport:
        return self.executor.run(max_wall=max_wall)
