"""Open-loop Salus serving driver (paper §5.3, Fig. 9/10): hold several
inference services resident on one device, feed each a Poisson request
stream, and optionally co-locate one best-effort background training job
that the PRIORITY policy preempts at iteration boundaries — never
mid-iteration. Reports per-service p50/p95/p99 request latency and the
background job's residual throughput.

    PYTHONPATH=src python -m repro.launch.serve --archs gemma-2b,qwen3-8b \\
        --rps 2 --duration 10 --train-background gemma-2b

``--no-smoke`` runs the full-size configs (smoke-scale is the default).
The executor's capacity is the device's ``bytes_limit``; ``--capacity-gb``
may lower it, and is required on a device that reports no limit (the CPU).
The run exits non-zero when a job failed or was rejected, or when a
service answered fewer requests than it was sent.
"""
from __future__ import annotations

import argparse
import random
import time
import zlib
from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import GB, SalusExecutor, VirtualDevice, get_policy
from repro.core.executor import ExecutorReport
from repro.core.tracegen import poisson_arrivals
from repro.launch.cache import enable_compile_cache
from repro.models import Model, ModelOptions, build_model

# On a TPU the SSM scan runs its Pallas kernel where it fits (models/ssm.py),
# and ssm_chunk only sizes the XLA scan it falls back to.
_MODEL_OPTS = ModelOptions(loss_chunk=8, moe_group=16, wkv_chunk=8, ssm_chunk=8)


def stable_seed(name: str) -> int:
    """Deterministic per-service PRNG seed. ``hash(str)`` is salted per
    process (PYTHONHASHSEED), which made serve runs irreproducible; crc32
    is a stable digest."""
    return zlib.crc32(name.encode("utf-8")) % 2**31


def host_init(model: Any, seed: int) -> Any:
    """Initialise ``model``'s parameters on the host CPU as numpy arrays,
    so that a job's state reaches the accelerator only when the executor
    admits it."""
    with jax.default_device(jax.devices("cpu")[0]):
        return jax.device_get(jax.jit(model.init)(jax.random.PRNGKey(seed)))


def serving_model(name: str, smoke: bool) -> Model:
    """The model a service or trainer of ``name`` runs (``smoke``: the
    64-wide reduction of its config)."""
    cfg = get_config(name)
    if smoke:
        cfg = cfg.smoke()
    return build_model(cfg, _MODEL_OPTS)


def service_fns(model: Model, max_len: int = 64):
    """A service's jitted prefill, its request handler (state = params,
    one prefill of a ``(4, 16)`` token batch per request) and its request
    source."""
    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len=max_len))

    def handle(state, request):
        params = state
        logits, _ = prefill(params, request)
        return params, {"next_token": jnp.argmax(logits, -1)}

    def data_fn(i):
        rng = jax.random.PRNGKey(i)
        return {"tokens": jax.random.randint(rng, (4, 16), 0, model.cfg.vocab_size)}

    return prefill, handle, data_fn


def trainer_fns(model: Model):
    """A trainer's SGD step on a ``(2, 16)`` token batch and its batch source."""

    def step(params, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params = jax.tree_util.tree_map(lambda p, g: p - 1e-4 * g, params, grads)
        return params, {"loss": loss}

    def data_fn(i):
        rng = jax.random.PRNGKey(i)
        tokens = jax.random.randint(rng, (2, 16), 0, model.cfg.vocab_size)
        return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=-1)}

    return step, data_fn


def make_service(name: str, smoke: bool, max_len: int = 64):
    """One resident inference service: its handler, host params, request
    source, and the jitted prefill the handler calls."""
    model = serving_model(name, smoke)
    prefill, handle, data_fn = service_fns(model, max_len)
    return handle, host_init(model, stable_seed(name)), data_fn, prefill


def make_trainer(name: str, smoke: bool):
    """The best-effort background training job of the Fig. 9/10 regime:
    a real gradient step so preemption interrupts genuine device work."""
    model = serving_model(name, smoke)
    step, data_fn = trainer_fns(model)
    return step, host_init(model, stable_seed(name) ^ 0x5A105), data_fn


def poisson_requests(rps: float, duration: float, rng: random.Random):
    """Per-service request stream (shared generator, ms-precision times)."""
    return tuple(round(t, 6) for t in poisson_arrivals(rps, duration, rng))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--archs", default="gemma-2b,qwen3-8b,rwkv6-7b")
    # BooleanOptionalAction so --no-smoke actually reaches full-size mode
    # (a store_true with default=True made it unreachable)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction, default=True)
    ap.add_argument("--rps", type=float, default=2.0, help="requests/s per service")
    ap.add_argument("--duration", type=float, default=10.0, help="open-loop window (s)")
    ap.add_argument(
        "--requests", type=int, default=None,
        help="cap on requests per service (default: whatever the stream yields)",
    )
    ap.add_argument(
        "--train-background", default=None, metavar="ARCH",
        help="co-locate one best-effort training job of this arch",
    )
    ap.add_argument("--train-iters", type=int, default=200)
    ap.add_argument(
        "--capacity-gb", type=float, default=None,
        help="executor capacity (default: the device's bytes_limit)",
    )
    ap.add_argument("--policy", default="priority")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def device_capacity(device: Any, requested: Optional[int] = None) -> int:
    """The bytes an executor may admit on ``device``: its ``bytes_limit``,
    or ``requested`` where that is lower. A device that reports no limit
    needs ``requested``; a request above the limit is an error."""
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if limit is None:
        if requested is None:
            raise ValueError(
                f"{device.device_kind} reports no bytes_limit: give a capacity"
            )
        return requested
    if requested is not None and requested > limit:
        raise ValueError(
            f"capacity {requested} B exceeds {device.device_kind}'s "
            f"bytes_limit of {limit} B"
        )
    return int(limit) if requested is None else requested


def make_executor(
    policy: str = "priority", capacity: Optional[int] = None, device: Any = None
) -> SalusExecutor:
    """A live executor on ``device`` (default: the first device), with its
    capacity taken from the device (see :func:`device_capacity`)."""
    device = device if device is not None else jax.devices()[0]
    return SalusExecutor(
        capacity=device_capacity(device, capacity),
        policy=get_policy(policy),
        device=device,
    )


def run_problems(report: ExecutorReport, sessions: List[Any]) -> List[str]:
    """Why a run must not count as a success: each job that failed or was
    rejected, and each service that answered fewer requests than it was
    sent. (A best-effort trainer may stop short when the window closes.)"""
    problems = []
    for s in sessions:
        jid = s.job.job_id
        st = report.stats[jid]
        if jid in report.failures:
            problems.append(f"{s.name}: failed: {report.failures[jid]}")
        elif st.rejected:
            problems.append(f"{s.name}: rejected (P+E = {s.job.profile.total} B)")
        elif s.job.open_loop and st.iterations_done < s.n_iters:
            problems.append(f"{s.name}: {st.iterations_done}/{s.n_iters} requests")
    return problems


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    capacity = None if args.capacity_gb is None else int(args.capacity_gb * GB)
    ex = make_executor(args.policy, capacity)
    vdev = VirtualDevice(ex)
    names = args.archs.split(",")
    rng = random.Random(args.seed)
    sessions = []
    for name in names:
        handle, params, data_fn, _ = make_service(name, args.smoke)
        reqs = poisson_requests(args.rps, args.duration, rng)
        if args.requests is not None:
            reqs = reqs[: args.requests]
        sessions.append(vdev.create_session(
            name, handle, params, data_fn, n_iters=len(reqs),
            kind="inference", utilization=0.3, request_times=reqs,
        ))
    if args.train_background:
        step, params, data_fn = make_trainer(args.train_background, args.smoke)
        sessions.append(vdev.create_session(
            f"train:{args.train_background}", step, params, data_fn,
            n_iters=args.train_iters, kind="train", utilization=0.9,
        ))
    print(f"[serve] packed {len(names)} services into 1 device "
          f"({ex.registry.stats()['n_lanes']} lanes, "
          f"{ex.registry.stats()['free']/2**30:.1f} GiB free"
          + (f", + background training {args.train_background}"
             if args.train_background else "") + ")")
    t0 = time.perf_counter()
    report = vdev.run(max_wall=args.duration + 5.0)
    dt = time.perf_counter() - t0
    total = sum(
        s.iterations_done for jid, s in report.stats.items()
        if ex.sessions[jid].job.kind == "inference"
    )
    print(f"[serve] {total} requests in {dt:.2f}s "
          f"({total/dt:.1f} req/s across {len(names)} resident services)")
    for jid, s in report.stats.items():
        job = ex.sessions[jid].job
        if job.kind == "inference":
            ms = lambda v: f"{v*1e3:.1f}" if v is not None else "n/a"
            print(f"  {job.name}: {s.iterations_done} reqs, latency ms "
                  f"p50={ms(s.p50_latency)} p95={ms(s.p95_latency)} "
                  f"p99={ms(s.p99_latency)}")
        else:
            print(f"  {job.name}: {s.iterations_done} training iterations "
                  f"({s.preemptions} boundary preemptions)")
    problems = run_problems(report, sessions)
    for p in problems:
        print(f"  FAILED {p}")
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
