"""The readers of the program's span log: each new per-layer metric on a
traced smoke run through ``run.py``; ``switch_gap_ms`` on a hand-made trace
and span log whose gap is known; the host-span metrics on the hand-made
run, traced (its traced part only) and not; and every reader on a program
that keeps no span log."""
import json
import math
import types

import pytest

from chipbench import program_spans, run, trace
from chipbench.tests.test_harness import CAPACITY, SEED

NEW = ("executor_self_ms", "serve_input_ms", "serve_dispatch_ms", "switch_gap_ms")
HOST_SPAN_METRICS = ("executor_self_ms", "serve_input_ms", "serve_dispatch_ms")


@pytest.mark.parametrize("name", HOST_SPAN_METRICS)
def test_traced_run_reports_the_host_spans(smoke, capsys, cpu_peak, name):
    rc = run.main(["--workload", "hymba.serve_train", "--seed", str(SEED), "--seconds", "2",
                   "--trace", "1"], root=smoke, platform="cpu", capacity=CAPACITY)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert math.isfinite(res["metrics"][name]["value"]) and res["metrics"][name]["unit"] == "ms"
    assert "switch_gap_ms" not in res["metrics"]  # a CPU trace has no device plane


def _span(log, name, job, t0, t1, index=None, parent=None):
    s = log.span(name, job, index)
    s.t0, s.t1, s.parent = t0, t1, parent
    log.spans.append(s)
    return s


MS = 1_000_000
BASE = 1_000 * MS  # perf_counter_ns at the hand-made window's start


def _hand_made(offset_ns=5_000_000_000):
    """Three iterations, service -> trainer -> service, 10 ms apart on the
    program's clock; the trace's clock is ``offset_ns`` ahead. The device
    is idle 6 ms going to the trainer and 7 ms going back; of its 27 ms of
    idle in the window, 12 ms lie inside the program's spans."""
    from repro.core.spans import SpanLog

    ms, base = MS, BASE
    log = SpanLog()
    log.counters["switches"] = 2
    order = [("service", 0, 10), ("trainer", 12, 40), ("service", 42, 50)]
    ours = run.Spans(False)
    for k, (job, a, b) in enumerate(order):
        it = _span(log, "iteration", job, base + a * ms, base + b * ms, index=k)
        _span(log, "session.dispatch", job, base + a * ms, base + (a + 1) * ms, k, it)
        ours.events.append(run.Span("run_iteration", job, (base + a * ms) / 1e9, (base + b * ms) / 1e9))
    t = lambda x: base + x * ms + offset_ns  # noqa: E731
    tr = trace.Trace(
        window=(t(-1), t(60)),
        ops=[[(t(2), t(9), "fusion.1"), (t(15), t(38), "while.2"), (t(45), t(49), "fusion.1")]],
        modules=[(t(2), t(9), "jit_serve_step(7)"), (t(15), t(38), "jit_train_step(9)"),
                 (t(45), t(49), "jit_serve_step(7)")],
        spans=[(t(a), t(b), f"chipbench.run_iteration:{job}") for job, a, b in order],
    )
    r = run.Run(cell=None, seed=SEED, seconds=1.0, device=None, spans=ours)
    r.executor = types.SimpleNamespace(spans=log)
    r.trace, r.t_w0 = tr, base / 1e9
    return r


def test_switch_gap_on_a_known_trace(capsys):
    r = _hand_made()
    gaps = program_spans.switch_gaps(r)
    assert gaps == [("trainer", pytest.approx(6e6)), ("service", pytest.approx(7e6))]
    assert run.metric_reader(run.ROOT, "switch_gap_ms")(r) == pytest.approx(6.5)
    err = capsys.readouterr().err
    assert "offset 5000000000 ns" in err
    assert "2 by the program's counter" in err
    assert "in the traced part 2 by the program's spans, 2 by the device's programs in the trace" in err
    assert "27.000 ms, 44.44% of it inside a program span" in err
    assert ("split by the innermost program span (ms): 11.000 ms (no program span 10.000, "
            "iteration:service 1.000); 7.000 ms (iteration:trainer 2.000, no program span 2.000, "
            "iteration:service 2.000, session.dispatch:service 1.000); ") in err


@pytest.mark.parametrize("name", NEW)
def test_program_without_a_span_log_reads_nothing(name):
    r = _hand_made()
    r.executor = types.SimpleNamespace()
    r.service = types.SimpleNamespace(name="service")
    assert run.metric_reader(run.ROOT, name)(r) is None


@pytest.mark.parametrize("traced", [True, False], ids=["traced", "untraced"])
@pytest.mark.parametrize("name, span, whole, part", [
    ("serve_dispatch_ms", "session.dispatch", 3.0, 1.0),
    ("serve_input_ms", "session.data_fn", 5.0, 1.0),
    ("executor_self_ms", "executor.pick", 10 / 3, 1 / 3),
])
def test_host_span_metrics_read_the_traced_part(name, span, whole, part, traced):
    """A 1 ms span in the traced part and a 9 ms one after it, both in the
    1 s window: a traced run reads the first alone, the others both."""
    r = _hand_made()
    r.service = types.SimpleNamespace(name="service")
    for a, b in ((3, 4), (70, 79)):
        _span(r.executor.spans, span, "service", BASE + a * MS, BASE + b * MS)
    if not traced:
        r.trace = None
    got = run.metric_reader(run.ROOT, name)(r)
    assert got == pytest.approx(part if traced else whole)


@pytest.mark.parametrize("name", HOST_SPAN_METRICS)
def test_trace_without_the_clock_reads_nothing(name):
    """A traced run whose spans cannot be put on the trace's clock (no
    benchmark annotations in the trace) reads nothing, not the window."""
    r = _hand_made()
    r.service = types.SimpleNamespace(name="service")
    r.trace.spans = []
    assert run.metric_reader(run.ROOT, name)(r) is None
