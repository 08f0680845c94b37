#!/usr/bin/env python3
"""Smoke run of the Salus main path on TPU chips, at hymba-1.5b's full width.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the four-chip fleet phase, alone

One chip: a hymba-1.5b inference service (PRIORITY policy, 8 open-loop
requests of (4, 16) tokens) and a hymba-1.5b SGD trainer (4 steps) go
through ``VirtualDevice`` -> ``SalusExecutor`` -> ``MemoryManager``, with
the executor's capacity taken from the chip's ``bytes_limit``. The two do
not fit together, so the trainer queues and is admitted when the service
finishes. Checked: every request served and every step taken, finite
losses, no compilation inside the request window, and one request's next
token equal to a direct call of the service's prefill on its parameters.

Four chips: four hymba-1.5b services, 8 requests each, on
``ClusterExecutor(bind_jax_devices=True)``, one per chip. Checked: the
placement log equals the simulated ``Cluster``'s for the same jobs, each
service's state sat only on its placed chip, and each chip's peak memory
is at least its service's persistent bytes.

Weights are random, from fixed seeds. The last line of standard output is
``{"ok": true, "device": {...}}`` only when every check passed; the script
exits non-zero without it when JAX finds no TPU, when a job failed, was
rejected or stopped short, or when a check failed. The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` where that is set, else ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import json
import math
import random
import sys
from pathlib import Path
from typing import Any, Dict, List, Set

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import Cluster, ClusterExecutor, JobSpec, VirtualDevice  # noqa: E402
from repro.core.types import MemoryEventKind  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    make_executor,
    make_service,
    make_trainer,
    poisson_requests,
    run_problems,
)

ARCH = "hymba-1.5b"
PLATFORM = "tpu"
N_REQUESTS = 8
TRAIN_STEPS = 4
REQUEST_RATE = 4.0  # requests/s per service
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) and their
    seconds while it is entered."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1
            self.seconds += duration

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc: Any) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def memory_stat(device: Any, key: str) -> int:
    """One entry of ``device.memory_stats()``; a chip that does not report
    it is an error, not a default."""
    stats = device.memory_stats() or {}
    if key not in stats:
        raise RuntimeError(f"{device.device_kind} reports no {key}")
    return int(stats[key])


def request_streams(n: int, seed: int = 0) -> List[tuple]:
    """``n`` open-loop streams of N_REQUESTS Poisson arrivals each."""
    rng = random.Random(seed)
    streams = []
    for _ in range(n):
        times = poisson_requests(REQUEST_RATE, 10.0 * N_REQUESTS / REQUEST_RATE, rng)
        streams.append(times[:N_REQUESTS])
    return streams


def _gb(nbytes: float) -> str:
    return f"{nbytes / 1e9:.3f} GB"


def _print_profile(sess: Any) -> None:
    p = sess.job.profile
    print(f"  {sess.name}: P={p.persistent} B ({_gb(p.persistent)}), "
          f"E={p.ephemeral} B ({_gb(p.ephemeral)}), P+E={_gb(p.total)}")


def one_chip(smoke: bool = False) -> List[str]:
    """The one-chip phase; returns what went wrong (empty: all passed).
    ``smoke`` builds the 64-wide reduction, for a rehearsal on the CPU."""
    dev = jax.devices()[0]
    capacity = memory_stat(dev, "bytes_limit")
    print(f"[one chip] device_kind={dev.device_kind} bytes_limit={capacity} B "
          f"({_gb(capacity)})")
    with CompileCounter() as compiles:
        ex = make_executor("priority", capacity, dev)
        vdev = VirtualDevice(ex)
        handle, params, data_fn, prefill = make_service(ARCH, smoke)
        (times,) = request_streams(1)
        service = vdev.create_session(
            ARCH, handle, params, data_fn, n_iters=N_REQUESTS,
            kind="inference", utilization=0.3, request_times=times,
        )
        step, tparams, tdata_fn = make_trainer(ARCH, smoke)
        trainer = vdev.create_session(
            f"train:{ARCH}", step, tparams, tdata_fn, n_iters=TRAIN_STEPS,
            kind="train", utilization=0.9,
        )
        del tparams  # only the session holds them now
        setup = (compiles.count, compiles.seconds)
        print(f"[one chip] set-up: {setup[0]} compilations, {setup[1]:.3f} s compiling")
        print("[one chip] profiles (compiled for this device):")
        _print_profile(service)
        _print_profile(trainer)
        report = vdev.run(max_wall=600.0)
        in_window = compiles.count - setup[0]
    print(f"[one chip] compilations inside the request window: {in_window}")
    print("[one chip] memory events, in order: " + ", ".join(
        f"{ev.kind.value} {ev.name}" for ev in report.memory_events
    ))
    sst = report.stats[service.job.job_id]
    tst = report.stats[trainer.job.job_id]
    print(f"[one chip] requests served: {sst.iterations_done}/{N_REQUESTS}")
    print(f"[one chip] trainer steps: {tst.iterations_done}/{TRAIN_STEPS}")
    losses = [float(m["loss"]) for m in trainer.metrics_log]
    print(f"[one chip] trainer losses: {losses}")
    lat = ", ".join(f"{v * 1e3:.1f}" for v in sst.request_latencies)
    print(f"[one chip] request latencies, ms (smoke run, not a benchmark figure): {lat}")
    peak = memory_stat(dev, "peak_bytes_in_use")
    print(f"[one chip] peak_bytes_in_use={peak} B ({_gb(peak)})")

    problems = run_problems(report, [service, trainer])
    if tst.iterations_done != TRAIN_STEPS:
        problems.append(f"trainer took {tst.iterations_done}/{TRAIN_STEPS} steps")
    if len(losses) != TRAIN_STEPS or not all(math.isfinite(x) for x in losses):
        problems.append(f"trainer losses not all finite: {losses}")
    if in_window:
        problems.append(f"{in_window} compilations inside the request window")
    kinds = [ev.kind for ev in report.memory_events if ev.job_id == trainer.job.job_id]
    if service.job.profile.total + trainer.job.profile.total > capacity:
        if kinds[:2] != [MemoryEventKind.QUEUE, MemoryEventKind.SECOND_CHANCE]:
            problems.append(f"trainer was not queued and then admitted: {kinds}")
    # the service's answer against a direct prefill on the same parameters
    k = N_REQUESTS - 1
    logits, _ = prefill(jax.device_put(params, dev), data_fn(k))
    want = np.asarray(jnp.argmax(logits, -1))
    got = np.asarray(service.metrics_log[k]["next_token"]) if service.metrics_log else None
    print(f"[one chip] request {k} next_token: service={got} direct prefill={want}")
    if got is None or not np.array_equal(got, want):
        problems.append(f"request {k}: service next_token != direct prefill")
    return problems


def four_chips(smoke: bool = False) -> List[str]:
    """The four-chip phase; returns what went wrong (empty: all passed)."""
    n = 4
    devices = jax.devices()[:n]  # the devices bind_jax_devices binds
    capacities = [memory_stat(d, "bytes_limit") for d in devices]
    for d, cap in zip(devices, capacities):
        print(f"[four chips] {d} device_kind={d.device_kind} bytes_limit={cap} B")
    fleet = ClusterExecutor(
        n, capacities, "priority", bind_jax_devices=True, concurrency="threads",
    )

    handle, params, data_fn, _ = make_service(ARCH, smoke)
    seen: Dict[str, Set[str]] = {}
    sessions: Dict[str, Any] = {}

    def watched(name: str):
        # records where the session's state lives as each iteration starts
        def fn(i: int):
            sess = sessions.get(name)
            if sess is not None:
                where = seen.setdefault(name, set())
                for leaf in jax.tree_util.tree_leaves(sess.state):
                    if isinstance(leaf, jax.Array):
                        where.update(str(d) for d in leaf.devices())
                    else:
                        where.add("host")
            return data_fn(i)

        return fn

    vdev = VirtualDevice(fleet)
    with CompileCounter() as compiles:
        for i, times in enumerate(request_streams(n)):
            name = f"{ARCH}#{i}"
            sessions[name] = vdev.create_session(
                name, handle, params, watched(name), n_iters=N_REQUESTS,
                kind="inference", utilization=0.3, request_times=times,
            )
        del params  # only the sessions hold them now
        print(f"[four chips] set-up (profiling): {compiles.count} compilations, "
              f"{compiles.seconds:.3f} s compiling")
        print("[four chips] profiles:")
        for sess in sessions.values():
            _print_profile(sess)
        report = vdev.run(max_wall=900.0)
        print(f"[four chips] compilations in set-up and run: {compiles.count}")

    sim = Cluster(n, capacities, "priority").run([
        JobSpec(
            name=s.job.name, profile=s.job.profile, n_iters=s.job.n_iters,
            iter_time=s.job.iter_time, utilization=s.job.utilization,
            arrival_time=s.job.arrival_time, kind=s.job.kind,
            priority=s.job.priority, request_times=s.job.request_times,
        )
        for s in sessions.values()
    ])
    print(f"[four chips] placement log: {report.placement_log()}")
    print(f"[four chips] simulated Cluster's: {sim.placement_log()}")
    problems = run_problems(report, list(sessions.values()))
    if report.placement_log() != sim.placement_log():
        problems.append("placement log differs from the simulated Cluster's")
    for name, sess in sessions.items():
        st = report.stats[sess.job.job_id]
        dev_id = report.plan.assignments.get(sess.job.job_id)
        placed = devices[dev_id] if dev_id is not None else None
        peak = memory_stat(placed, "peak_bytes_in_use") if placed is not None else 0
        print(f"[four chips] {name}: device {dev_id} ({placed}), "
              f"{st.iterations_done}/{N_REQUESTS} requests, state seen on "
              f"{sorted(seen.get(name, ()))}, peak_bytes_in_use={peak} B")
        if seen.get(name) != {str(placed)}:
            problems.append(f"{name}: state seen on {seen.get(name)}, placed on {placed}")
        if peak < sess.job.profile.persistent:
            problems.append(f"{name}: peak {peak} B below its P")
    if len({report.plan.assignments.get(s.job.job_id) for s in sessions.values()}) != n:
        problems.append("services do not sit one per chip")
    return problems


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the four-chip fleet phase",
    )
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        print(f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    print(f"[chip_smoke] compile cache: {enable_compile_cache()}")
    problems = four_chips() if args.chips == 4 else one_chip()
    for p in problems:
        print(f"FAILED {p}")
    if problems:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
