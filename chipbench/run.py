#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` at the checkout's root) names a configuration
file and a traffic mix (``chipbench/traffic/<traffic>.json``); the limits of
its output check are in ``chipbench/checks/<cell>.json`` and each per-layer
metric is read by ``chipbench/metrics/<metric>.py``. Nothing else names a
cell, so a new one is new files and entries only.

A run: weights from the seed on the device, copied once to the host; the
jobs of the mix submitted to the program's ``VirtualDevice`` (one
``SalusExecutor`` with the chip's ``bytes_limit``); the first trainer's
first three steps and one service request as warm-up; then one
``VirtualDevice.run`` of ``--seconds`` (plus the mix's grace, so that a
request due at the end is answered) with every request falling due in
``[0, seconds)``. After the window: the chip's peak memory, then the
program's state is freed and the plain float32 reference (``reference/``)
checks the served logits of a sample of requests (half of them served
straight after another job's iteration, where the service shares the chip)
and replays the first trainer's every step, the window's included: its
first gradient, its change after the set-up steps and its parameters at the
end of the window. A reading that the cell's check file gives no limit
(the per-step losses: no planted fault or control reads higher than sound
runs) is logged and not compared.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each compared number with its limit).
The run exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import os
import sys
import time

T0 = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # a fixed path inside the checkout: the path is part of the cache's key
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import jobs, trace as tracemod, traffic, weights  # noqa: E402
from chipbench.reference import common as ref_common  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
FOREVER = 10**9  # a trainer with no step limit
SETUP_STEPS = 3  # trainer steps in set-up, before the window
SERVE_BLOCK = 16  # requests per reference call
TRACE_SECONDS = 2.0  # the part of a traced run's window that the profiler records


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


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


# ---------------------------------------------------------------------------
# The cell, from data files
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if name in m.get("workloads", [name] if m["moves"] in e2e_names else [])
    ]
    data = root / "chipbench"
    return Cell(
        name=name,
        chips=int(w["chips"]),
        cfg=json.loads((root / conf["file"]).read_text()),
        mix=json.loads((data / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((data / "checks" / f"{name}.json").read_text())["limits"],
        end_to_end=e2e,
        per_layer=per_layer,
    )


def metric_reader(root: Path, name: str) -> Callable[["Run"], Optional[float]]:
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Spans around the calls into the program's layers
# ---------------------------------------------------------------------------


@dataclass
class Span:
    kind: str  # data_fn | run_iteration | place | release
    job: str
    t0: float
    t1: float


class Spans:
    def __init__(self, annotate: bool) -> None:
        self.annotate = annotate
        self.events: List[Span] = []

    def wrap(self, kind: str, job: str, fn: Callable) -> Callable:
        def wrapped(*a: Any, **k: Any) -> Any:
            t0 = time.perf_counter()
            if self.annotate:
                with jax.profiler.TraceAnnotation(f"chipbench.{kind}:{job}"):
                    out = fn(*a, **k)
            else:
                out = fn(*a, **k)
            self.events.append(Span(kind, job, t0, time.perf_counter()))
            return out

        return wrapped

    def instrument(self, sess: Any) -> None:
        for kind in ("data_fn", "run_iteration", "place", "release"):
            setattr(sess, kind, self.wrap(kind, sess.name, getattr(sess, kind)))

    def of(self, kind: str, job: Optional[str] = None) -> List[Span]:
        return [s for s in self.events if s.kind == kind and (job is None or s.job == job)]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclass
class Trainer:
    session: Any
    feed: jobs.TrainFeed
    batch: int
    seq: int


@dataclass
class Run:
    """Everything a run measured; the per-layer readers take it whole."""

    cell: Cell
    seed: int
    seconds: float
    device: Any
    spans: Spans
    host: Any = None
    executor: Any = None
    vdev: Any = None
    sessions: List[Any] = field(default_factory=list)
    service: Any = None
    service_feed: Optional[jobs.ServiceFeed] = None
    due: tuple = ()
    trainers: List[Trainer] = field(default_factory=list)
    state_bytes: int = 0
    compile_setup_s: float = 0.0
    compiles_setup: int = 0
    compiles_window: int = 0
    t_w0: float = 0.0
    t_w1: float = 0.0
    setup_s: float = 0.0
    report: Any = None
    trace: Optional[tracemod.Trace] = None
    prog: Dict[str, Any] = field(default_factory=dict)  # the program's readings

    # -- what the window did ----------------------------------------------

    def window_end(self) -> float:
        return self.t_w0 + self.seconds

    def request_latencies_ms(self) -> List[float]:
        """Due time to the end of the request's iteration, for every request
        due in the window; one never answered counts its wait until the run
        ended (a lower bound)."""
        if self.service is None:
            return []
        runs = self.spans.of("run_iteration", self.service.name)
        out = [(r.t1 - (self.t_w0 + d)) * 1e3 for r, d in zip(runs, self.due)]
        out += [(self.t_w1 - (self.t_w0 + d)) * 1e3 for d in self.due[len(runs):]]
        return out

    def queue_waits_ms(self) -> List[float]:
        if self.service is None:
            return []
        runs = self.spans.of("run_iteration", self.service.name)
        return [(r.t0 - (self.t_w0 + d)) * 1e3 for r, d in zip(runs, self.due)]

    def served(self) -> int:
        return len(self.spans.of("run_iteration", self.service.name)) if self.service else 0

    def served_after_switch(self) -> List[int]:
        """The requests whose iteration came straight after another job's."""
        if self.service is None:
            return []
        out, k, prev = [], 0, None
        for s in sorted(self.spans.of("run_iteration"), key=lambda s: s.t0):
            if s.job == self.service.name:
                if prev is not None and prev != s.job:
                    out.append(k)
                k += 1
            prev = s.job
        return out

    def train_steps_in_window(self) -> List[Span]:
        names = {t.session.name for t in self.trainers}
        return [s for s in self.spans.of("run_iteration")
                if s.job in names and s.t1 <= self.window_end()]

    def train_work(self) -> List[tuple]:
        """``(trainer, share)`` for each trainer step that ran in the
        window: 1 for one that ended in it, and for the step under way at
        its close the share of that step's time that fell inside it."""
        names = {t.session.name: t for t in self.trainers}
        end = self.window_end()
        return [(names[s.job], min(1.0, (end - s.t0) / (s.t1 - s.t0)))
                for s in self.spans.of("run_iteration") if s.job in names and s.t0 < end]

    def train_tokens(self) -> float:
        return sum(t.batch * t.seq * share for t, share in self.train_work())

    def iteration_summary(self) -> str:
        """Each job's iterations in the window: count, mean and longest."""
        parts = []
        for sess in self.sessions:
            d = [s.t1 - s.t0 for s in self.spans.of("run_iteration", sess.name) if s.t0 < self.window_end()]
            if d:
                parts.append(f"{sess.name} {len(d)} x {1e3 * sum(d) / len(d):.2f} ms (longest {1e3 * max(d):.2f})")
        return "; ".join(parts)


@jax.jit
def _change_sq(new, old):
    return {p: jnp.sum(jnp.square(new[p].astype(jnp.float32) - jnp.asarray(old[p]).astype(jnp.float32)))
            for p in new}


def change_sq(new_tree: Any, old_tree: Any) -> Dict[str, float]:
    """Squared norm of the change of every leaf, on the device."""
    out = _change_sq(weights.flatten(new_tree), weights.flatten(old_tree))
    return {p: float(v) for p, v in jax.device_get(out).items()}


def setup(run: Run, capacity: Optional[int], rate: Optional[float] = None) -> None:
    """Everything before the window: weights, jobs, admission, warm-up and
    the first trainer's first steps."""
    from repro.core import VirtualDevice
    from repro.launch import serve

    cell, cfg, mix = run.cell, run.cell.cfg, run.cell.mix
    model = jobs.program_model(cfg)
    spec = reference_module(cfg).spec(cfg)
    diff = weights.spec_diff(spec, weights.spec_of(jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    if diff:
        raise RuntimeError("the program's parameters differ from the reference's: " + "; ".join(diff))
    flat = weights.make_stacked(run.seed, spec)
    run.host = jax.device_get(weights.unflatten(flat))
    del flat
    run.state_bytes = sum(int(np.prod(s)) * jnp.dtype(d).itemsize for s, d in spec.values())
    run.executor = serve.make_executor(mix["policy"], capacity, run.device)
    run.vdev = vdev = VirtualDevice(run.executor)
    vocab = int(cfg["vocab_size"])

    svc = mix.get("service")
    if svc:
        run.due = traffic.due_times(rate or float(svc["rate_rps"]), run.seconds, run.seed)
        run.service_feed = jobs.ServiceFeed(run.seed, int(svc["batch"]), int(svc["seq"]), vocab)
        run.service = vdev.create_session(
            "service", jobs.service_step_fn(model), run.host, run.service_feed,
            n_iters=len(run.due), kind="inference", utilization=0.3, request_times=run.due,
        )
        run.sessions.append(run.service)
    step = jobs.train_step_fn(model)  # one function object: one compiled program
    for j, tspec in enumerate(mix.get("trainers", [])):
        steps = tspec.get("steps")
        for k, lr in enumerate(traffic.learning_rates(tspec)):
            b, s = int(tspec["batch"]), int(tspec["seq"])
            feed = jobs.TrainFeed(run.seed, 1000 * j + k, b, s, vocab, lr)
            sess = vdev.create_session(
                f"train{j}.{k}", step, run.host, feed,
                n_iters=int(steps) if steps else FOREVER, kind="train", utilization=0.9,
            )
            run.trainers.append(Trainer(sess, feed, b, s))
            run.sessions.append(sess)

    resident = set(run.executor.registry.assignment)
    if run.service is not None:
        if run.service.job.job_id not in resident:
            raise RuntimeError("the service was not admitted at set-up")
        jax.block_until_ready(run.service.executable(run.service.state, run.service_feed.warmup()))
    if run.trainers:
        t = run.trainers[0]
        if t.session.job.job_id not in resident:
            raise RuntimeError("the first trainer was not admitted at set-up")
        p0 = t.session.state
        t.session.run_iteration(0)
        grad_sq = change_sq(t.session.state, p0)
        del p0
        for i in range(1, SETUP_STEPS):
            t.session.run_iteration(i)
        run.prog["grad_sq"] = grad_sq
        run.prog["change_sq"] = change_sq(t.session.state, run.host)
        t.feed.offset = SETUP_STEPS
    for sess in run.sessions:
        run.spans.instrument(sess)


def window(run: Run, grace: float, trace_dir: Optional[Path]) -> None:
    """The measured window: one ``VirtualDevice.run``. A traced run records
    the window's first TRACE_SECONDS only (a longer trace of this chip's
    many small operations overflows the profiler's buffer and silently
    drops the rest); a thread of its own marks the traced part's end and
    stops the profiler while the window goes on."""
    stopper = None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        done = threading.Event()
        stopper = threading.Thread(target=_stop_trace, args=(done, run), daemon=True)
        with jax.profiler.TraceAnnotation(tracemod.WINDOW_START):
            pass
    run.t_w0 = time.perf_counter()
    if stopper is not None:
        stopper.start()
    run.report = run.vdev.run(max_wall=run.seconds + grace)
    run.t_w1 = time.perf_counter()
    if stopper is not None:
        done.set()
        stopper.join()
        run.trace = tracemod.load(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: {run.trace.window_s:.3f} s traced, stopping took {run.prog['trace_stop_s']:.3f} s; "
            f"{run.trace.coverage()}")


def _stop_trace(done: threading.Event, run: Run) -> None:
    done.wait(TRACE_SECONDS)
    with jax.profiler.TraceAnnotation(tracemod.TRACE_END):
        pass
    t0 = time.perf_counter()
    jax.profiler.stop_trace()
    run.prog["trace_stop_s"] = time.perf_counter() - t0


def checked_requests(run: Run) -> List[int]:
    """The served requests the output check compares, drawn from the seed:
    half from those served straight after another job's iteration, the
    rest from all the others."""
    if run.service is None:
        return []
    k = int(run.cell.mix["service"]["sample"])
    first = traffic.sample(run.seed, run.served_after_switch(), k // 2, stream=1)
    rest = sorted(set(range(run.served())) - set(first))
    return sorted(first + traffic.sample(run.seed, rest, k - len(first), stream=2))


def read_trainer(run: Run) -> None:
    """The first trainer's losses of every step it took and its change since
    the seeded start, at the end of the window."""
    if not run.trainers:
        return
    t = run.trainers[0].session
    run.prog["losses"] = [float(m["loss"]) for m in t.metrics_log]
    run.prog["final_change_sq"] = change_sq(t.state, run.host)


def free_program_state(run: Run, keep: List[int]) -> Dict[int, np.ndarray]:
    """Copies the served logits of requests ``keep`` to the host, then drops
    every device array the program's jobs hold."""
    logits = {}
    if run.service is not None:
        logits = {i: np.asarray(run.service.metrics_log[i], np.float32) for i in keep}
    for sess in run.sessions:
        sess.state = None
        sess.metrics_log = []
    gc.collect()
    return logits


def compare(cfg: Dict[str, Any], seed: int, prog: Dict[str, Any],
            service_feed: Optional[jobs.ServiceFeed] = None,
            train_feed: Optional[jobs.TrainFeed] = None) -> Dict[str, float]:
    """The readings of ``prog`` against the plain float32 reference:
    ``prog["logits"]`` (request -> served last-position logits), and the
    first trainer's steps: ``prog["losses"]`` (one per step taken),
    ``prog["grad_sq"]`` (squared change of each leaf after the first step),
    ``prog["change_sq"]`` (after the set-up steps) and
    ``prog["final_change_sq"]`` (after the last step). The program and the
    control both pass here."""
    R = ref_common.Model(reference_module(cfg), cfg, ref_common.dot_f32)
    P = R.params(seed)
    out: Dict[str, float] = {}
    if prog.get("logits"):
        ref = serve_logits(R, P, service_feed, sorted(prog["logits"]))
        prog["logit_errs"] = {i: logit_err(got, ref[i]) for i, got in prog["logits"].items()}
        out["logit_err"] = max(prog["logit_errs"].values())
    if prog.get("losses"):
        ref = train_readings(R, P, train_feed, len(prog["losses"]))
        lr = float(train_feed.lr)
        leaves = sorted(ref["grad_sq"])
        g_ref = np.sqrt([ref["grad_sq"][p] for p in leaves]) / lr
        g_got = np.sqrt([prog["grad_sq"][p] for p in leaves]) / lr
        keep = g_ref >= 1e-3 * np.median(g_ref)
        out["loss_gap"] = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
        out["grad_gap"] = worst_gap(g_ref, g_got, keep)
        prog["leaf_norms"] = {"leaves": leaves, "kept": keep.tolist(),
                              "grad_gap": [g_ref.tolist(), g_got.tolist()]}
        for key, name in (("change_sq", "change_gap"), ("final_change_sq", "final_change_gap")):
            r, g = (np.sqrt([d[key][p] for p in leaves]) for d in (ref, prog))
            out[name] = worst_gap(r, g, keep)
            prog["leaf_norms"][name] = [r.tolist(), g.tolist()]
        prog["ref_losses"] = ref["losses"]
        prog["leaves_left_out"] = [p for p, k in zip(leaves, keep) if not k]
    return out


def logit_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Worst row's error of served logits against the reference's, as a
    share of the reference's spread: ``||c(got) - c(ref)|| / ||c(ref)||``
    with ``c`` taking away the row's mean, to which softmax is blind."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    got = got - got.mean(-1, keepdims=True)
    ref = ref - ref.mean(-1, keepdims=True)
    return float(np.max(np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)))


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> tuple:
    """``(correct, checks)``: every reading finite and within its limit;
    ``checks`` gives each number beside its limit."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in readings.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return correct, checks


def reference_module(cfg: Dict[str, Any]) -> Any:
    return importlib.import_module(f"chipbench.reference.{cfg['model_type']}")


def serve_logits(R: Any, P: Any, feed: jobs.ServiceFeed, idx: List[int]) -> Dict[int, np.ndarray]:
    """The model's last-position logits for requests ``idx``, in blocks."""
    out = {}
    for b in range(0, len(idx), SERVE_BLOCK):
        block = idx[b : b + SERVE_BLOCK]
        logits = R.last_logits(P, np.concatenate([feed(i)["tokens"] for i in block]))
        for j, i in enumerate(block):
            out[i] = logits[j * feed.shape[0] : (j + 1) * feed.shape[0]]
    return out


def train_readings(R: Any, P: Any, feed: jobs.TrainFeed, steps: int) -> Dict[str, Any]:
    """The model's first ``steps`` steps on ``feed``'s rows: losses, and the
    squared change of each leaf after one step, after SETUP_STEPS and after
    all of them."""
    out: Dict[str, Any] = {"losses": []}
    for step in range(steps):
        tokens, labels = feed.rows(step)
        out["losses"].append(R.sgd_step(P, tokens, labels, float(feed.lr)))
        if step == 0:
            out["grad_sq"] = P.change_sq()
        if step == SETUP_STEPS - 1:
            out["change_sq"] = P.change_sq()
    out["final_change_sq"] = P.change_sq()
    return out


def worst_gap(ref: np.ndarray, got: np.ndarray, keep: np.ndarray) -> float:
    """Largest |got - ref| over the kept leaves, each against the larger of
    the reference's norm of that leaf and of the median kept leaf."""
    ref, got = np.asarray(ref, np.float64)[keep], np.asarray(got, np.float64)[keep]
    denom = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(got - ref) / denom))


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def end_to_end(run: Run) -> Dict[str, float]:
    vals: Dict[str, float] = {"setup_s": run.setup_s}
    lat = run.request_latencies_ms()
    if lat:
        vals["serve_p95_ms"] = traffic.percentile(lat, 95)
        vals["serve_p50_ms"] = traffic.percentile(lat, 50)
    if run.trainers:
        vals["train_tokens_per_s"] = run.train_tokens() / run.seconds
    return vals


def memory_stat(device: Any, key: str) -> Optional[int]:
    v = (device.memory_stats() or {}).get(key)
    return int(v) if v is not None else None


def host_meminfo() -> Dict[str, int]:
    try:
        lines = Path("/proc/meminfo").read_text().splitlines()
    except OSError:
        return {}
    out = {}
    for ln in lines:
        k, _, v = ln.partition(":")
        if k in ("MemTotal", "MemAvailable"):
            out[k] = int(v.split()[0]) * 1024
    return out


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None, root: Path = ROOT, platform: str = "tpu",
         capacity: Optional[int] = None) -> int:
    """``platform`` and ``capacity`` exist for the CPU tests, which drive a
    run at smoke size; the command line always asks for a TPU and takes
    the chip's ``bytes_limit``."""
    args = parse(argv)
    cell = load_cell(root, args.workload)
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell.chips:
        log(f"JAX found {len(devices)} {devices[0].platform} device(s); "
            f"the cell needs {cell.chips} {platform}")
        return 2
    dev = devices[0]
    log(f"platform={dev.platform} device_kind={dev.device_kind} count={len(devices)}")
    readers = {m["name"]: metric_reader(root, m["name"]) for m in cell.per_layer} if args.trace else {}

    run = Run(cell=cell, seed=args.seed, seconds=args.seconds, device=dev,
              spans=Spans(annotate=bool(args.trace)))
    with CompileCounter() as compiles:
        setup(run, capacity)
        run.compiles_setup, run.compile_setup_s = compiles.count, compiles.seconds
        trace_dir = root / ".chipbench_trace" / cell.name if args.trace else None
        run.setup_s = time.perf_counter() - T0
        window(run, float(cell.mix.get("grace_s", 0.0)), trace_dir)
        run.compiles_window = compiles.count - run.compiles_setup
    peak = memory_stat(dev, "peak_bytes_in_use")
    rep = run.report
    log(f"set-up: {run.setup_s:.3f} s, {run.compiles_setup} compilations, "
        f"{run.compile_setup_s:.3f} s compiling")
    log(f"compilations inside the window: {run.compiles_window}")
    for sess in run.sessions[:4]:
        p = sess.job.profile
        log(f"profile {sess.name}: P={p.persistent} B E={p.ephemeral} B P+E={p.total} B")
    log("memory events: " + ", ".join(f"{e.kind.value} {e.name}" for e in rep.memory_events))
    log(f"peak_bytes_in_use={peak} host={host_meminfo()}")
    if run.service is not None:
        log(f"requests: {len(run.due)} due, {run.served()} served")
    if run.trainers:
        log(f"trainer steps in window: {len(run.train_steps_in_window())}; in all "
            f"{len(run.trainers[0].session.metrics_log)}")
    log(f"iterations in the window: {run.iteration_summary()}")

    metrics_out: Dict[str, Dict[str, Any]] = {}
    device_out: Dict[str, Any] = {"platform": dev.platform, "kind": dev.device_kind,
                                  "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        for name, read in readers.items():
            v = read(run)
            if v is not None:
                metrics_out[name] = {"value": v, "unit": units[name]}
        if run.trace is not None:
            device_out["busy_s"] = run.trace.busy_s()
            device_out["window_s"] = run.trace.window_s
            breakdown = {"device_ops": run.trace.top_ops(), "idle_gaps": run.trace.idle_gaps()}
    else:
        vals = end_to_end(run)
        for m in cell.end_to_end:
            metrics_out[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}

    # the output check, after the peak is read and the program's state freed
    attempted = len(run.due) + len(run.train_steps_in_window())
    failed = (len(run.due) - run.served()) + len(rep.failures)
    keep = checked_requests(run)
    log(f"checked requests: {len(keep)}, of them {len(set(keep) & set(run.served_after_switch()))} "
        "served straight after another job's iteration")
    read_trainer(run)
    run.prog["logits"] = free_program_state(run, keep)
    t_ref = time.perf_counter()
    readings = compare(cell.cfg, run.seed, run.prog, run.service_feed,
                       run.trainers[0].feed if run.trainers else None)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s; program losses {run.prog.get('losses')}; "
        f"reference losses {run.prog.get('ref_losses')}; leaves left out {run.prog.get('leaves_left_out')}")
    if "logit_errs" in run.prog:
        errs = sorted(run.prog["logit_errs"].values())
        log(f"logit_err over {len(errs)} requests: median {errs[len(errs) // 2]!r}, "
            f"largest {errs[-3:]!r}")
    if "leaf_norms" in run.prog:
        log("leaf norms (reference, program): " + json.dumps(run.prog["leaf_norms"]))
    readings["window_compiles"] = float(run.compiles_window)
    readings["jobs_failed"] = float(len(rep.failures))
    limits = dict(cell.limits, window_compiles=0.0, jobs_failed=0.0)
    for k in sorted(set(readings) - set(limits)):
        log(f"reading {k}: {readings[k]!r} (not compared: it has no upper reading)")
    correct, checks = judge({k: v for k, v in readings.items() if k in limits}, limits)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    result: Dict[str, Any] = {"correct": correct, "attempted": attempted, "failed": failed,
                              "metrics": metrics_out, "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    raise SystemExit(main())
