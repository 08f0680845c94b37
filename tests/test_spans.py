"""The program's span log (``core/spans.py``): nesting and self time, the
spans the executor and its sessions write for each iteration, the timing
lists that are views of the log, and the profiler's mirror of each span."""
import glob
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GB, MemoryProfile, SalusExecutor, VirtualDevice, get_policy
from repro.core.memory import MemoryConfig
from repro.core.session import Session
from repro.core.spans import SpanLog

PROFILE = MemoryProfile(1 << 20, 1 << 20)


def _step(state, batch):
    return state + batch.sum(), {"loss": batch.sum()}


def _data(i):
    return np.full((4,), float(i), np.float32)


def _slow_data(i):
    time.sleep(0.003)  # the trainer's iteration outlasts the gap between requests
    return _data(i)


def _serve_beside_train(n_requests=10, train_iters=8):
    """A PRIORITY service of open-loop requests, 5 ms apart, beside a
    best-effort trainer, both on the CPU device: they interleave, and the
    service waits for its last requests alone."""
    ex = SalusExecutor(capacity=1 * GB, policy=get_policy("priority"))
    vdev = VirtualDevice(ex)
    step = jax.jit(_step)
    # compiled for a placed state before the requests fall due
    jax.block_until_ready(step(jax.device_put(jnp.zeros(()), ex.device), _data(0)))
    vdev.create_session("service", step, jnp.zeros(()), _data, n_iters=n_requests,
                        profile=PROFILE, kind="inference",
                        request_times=tuple(0.005 * k for k in range(n_requests)))
    vdev.create_session("trainer", step, jnp.zeros(()), _slow_data, n_iters=train_iters,
                        profile=PROFILE, kind="train")
    return ex, vdev.run()


def test_nesting_parents_and_self_time():
    log = SpanLog()
    with log.span("outer", "a", 1) as outer:
        with log.span("inner", "a", 1) as first:
            time.sleep(0.002)
        with log.span("inner", "a", 1) as second:
            with log.span("leaf") as leaf:
                pass
    assert [s.name for s in log.spans] == ["inner", "leaf", "inner", "outer"]  # by close
    assert outer.parent is None and first.parent is outer and leaf.parent is second
    assert outer.t0 <= first.t0 < first.t1 <= second.t0 <= leaf.t0 <= leaf.t1 <= second.t1 <= outer.t1
    got = log.self_ns([outer, second, leaf])
    assert got == [
        (outer.t1 - outer.t0) - (first.t1 - first.t0) - (second.t1 - second.t0),
        (second.t1 - second.t0) - (leaf.t1 - leaf.t0),
        leaf.t1 - leaf.t0,
    ]
    assert log.of("inner") == [first, second] and log.of("inner", job="b") == []


def test_span_closes_on_error_and_log_is_bounded():
    log = SpanLog(bound=3)
    with pytest.raises(ValueError):
        with log.span("fails"):
            raise ValueError("x")
    assert [s.name for s in log.spans] == ["fails"] and not log._open
    for k in range(5):
        with log.span("k", index=k):
            pass
    assert [s.index for s in log.spans] == [2, 3, 4]
    assert log.counters["switches"] == 0  # a counter starts at 0


def test_request_spans_share_job_and_index():
    sess = Session("svc", jax.jit(_step), jnp.zeros(()), _data, n_iters=9, profile=PROFILE)
    sess.run_iteration(3)
    sess.run_iteration(4)
    got = [(s.name, s.job, s.index) for s in sess.spans.of("session.data_fn", "session.dispatch",
                                                            "session.block")]
    assert got == [(n, "svc", i) for i in (3, 4)
                   for n in ("session.data_fn", "session.dispatch", "session.block")]


def test_executor_iteration_spans_and_switch_count():
    ex, rep = _serve_beside_train()
    assert rep.spans is ex.spans
    its = ex.spans.of("iteration")
    names = {jid: sess.name for jid, sess in ex.sessions.items()}
    assert [(s.job, s.index) for s in its] == [(names[r.job_id], r.index) for r in rep.records]
    picks = ex.spans.of("executor.pick")
    for it in its:
        before = [p for p in picks if p.t1 <= it.t0]
        assert before and before[-1].parent is None  # a pick came first
        kids = {s.name for s in ex.spans.spans if s.parent is it}
        assert {"session.data_fn", "session.dispatch", "session.block", "executor.tick"} <= kids
        assert all(s.index == it.index and s.job == it.job for s in ex.spans.spans
                   if s.parent is it and s.name.startswith("session.") and s.index is not None)
    jobs = [r.job_id for r in rep.records]
    changes = sum(a != b for a, b in zip(jobs, jobs[1:]))
    assert changes >= 2 and ex.spans.counters["switches"] == changes
    assert ex.spans.of("executor.wait")  # the service waited for a request
    assert {s.name for s in ex.spans.spans if s.name.startswith("session.")} >= {
        "session.place", "session.release"}


def test_switch_latencies_are_a_view_of_the_log():
    ex, rep = _serve_beside_train()
    marks = {}
    for s in ex.spans.spans:
        if s.parent is not None and s.name in ("session.block", "session.dispatch"):
            marks[(s.parent.job, s.parent.index, s.name)] = s.t1
    its = ex.spans.of("iteration")
    waits = ex.spans.of("executor.wait")
    want = []
    for a, b in zip(its, its[1:]):
        if a.job != b.job and b.index != 0:
            end = marks[(a.job, a.index, "session.block")]
            start = marks[(b.job, b.index, "session.dispatch")]
            waited = sum(w.t1 - w.t0 for w in waits if end <= w.t0 and w.t1 <= start)
            want.append((start - end - waited) / 1e9)
    assert want and rep.switch_latencies == pytest.approx(want, abs=1e-12)
    assert all(0 < x < 1.0 for x in want)


def test_transfer_latencies_are_the_pagers_spans():
    ex = SalusExecutor(10 * GB, get_policy("fifo"), memory=MemoryConfig(paging=True))
    prof = lambda p, e: MemoryProfile(int(p * GB), int(e * GB))  # noqa: E731
    for name, (p, e), iters in (("a", (3, 2), 3), ("b", (3, 2), 3), ("c", (1, 6), 2)):
        ex.submit(Session(name, lambda s, b: s + 1.0, jnp.zeros((16,), jnp.float32),
                          lambda i: None, iters, profile=prof(p, e), iter_time=0.002))
    rep = ex.run()
    pages = ex.spans.of("executor.page")
    assert rep.registry_stats["page_outs"] >= 2 and len(pages) >= 2
    assert rep.transfer_latencies == [s.seconds for s in pages]
    for page in pages:
        kids = [s.name for s in ex.spans.spans if s.parent is page]
        assert kids in (["session.release"], ["session.place"])


def test_profiler_mirrors_each_span_at_a_constant_offset(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        ex, _ = _serve_beside_train()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1]
    marks = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("salus."):
                    marks.setdefault(ev.name, []).append(ev.start_ns)
    spans = {}
    for s in sorted(ex.spans.spans, key=lambda s: s.t0):
        label = f"salus.{s.name}" if s.job is None else f"salus.{s.name}:{s.job}"
        spans.setdefault(label, []).append(s.t0)
    assert {"salus.iteration:service", "salus.session.dispatch:trainer", "salus.executor.pick"} <= set(spans)
    assert {k: len(v) for k, v in marks.items()} == {k: len(v) for k, v in spans.items()}
    offsets = [m - t for k in spans for m, t in zip(sorted(marks[k]), spans[k])]
    assert max(offsets) - min(offsets) < 1_000_000, statistics.quantiles(offsets, n=4)
