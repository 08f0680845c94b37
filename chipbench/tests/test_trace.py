"""The trace reduction: the loader on a trace recorded here (host spans and
the window; a CPU run has no TPU plane), and the device arithmetic on a
hand-made trace whose answers are known."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import trace


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace")
    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation(trace.WINDOW_START):
        pass
    for _ in range(3):
        with jax.profiler.TraceAnnotation("chipbench.run_iteration:service"):
            step(x).block_until_ready()
    with jax.profiler.TraceAnnotation(trace.TRACE_END):
        pass
    jax.profiler.stop_trace()
    return trace.load(str(out))


def test_loader_finds_window_and_spans(recorded):
    assert recorded.window is not None and recorded.window_s > 0
    names = [name for _, _, name in recorded.spans]
    assert names == ["chipbench.run_iteration:service"] * 3  # the marks are not spans
    assert all(recorded.window[0] <= s and e <= recorded.window[1] for s, e, _ in recorded.spans)


def _hand_made():
    ms = 1_000_000
    return trace.Trace(
        window=(0, 100 * ms),
        ops=[[(10 * ms, 30 * ms, "fusion.1"), (20 * ms, 40 * ms, "convolution.2"),
              (60 * ms, 70 * ms, "fusion.1"), (95 * ms, 120 * ms, "copy.3")]],
        modules=[(10 * ms, 40 * ms, "jit_serve_step(7)"), (60 * ms, 70 * ms, "jit_train_step(9)"),
                 (95 * ms, 120 * ms, "jit_serve_step(7)")],
        spans=[(40 * ms, 58 * ms, "chipbench.release:service"),
               (5 * ms, 41 * ms, "chipbench.run_iteration:service")],
    )


def test_busy_union_and_idle_share():
    tr = _hand_made()
    assert tr.busy_intervals(0) == [(10e6, 40e6), (60e6, 70e6), (95e6, 100e6)]
    assert tr.busy_s() == pytest.approx(0.045)
    assert tr.window_s == pytest.approx(0.1)


def test_top_ops_and_idle_gaps():
    tr = _hand_made()
    assert tr.top_ops() == [["fusion.1", pytest.approx(0.03)], ["convolution.2", pytest.approx(0.02)],
                            ["copy.3", pytest.approx(0.005)]]
    gaps = tr.idle_gaps()
    assert [g[1] for g in gaps] == [pytest.approx(0.025), pytest.approx(0.02), pytest.approx(0.01)]
    assert gaps[1][0] == "chipbench.release:service"  # 40-60 ms: the release was open
    assert gaps[2][0] == "chipbench.run_iteration:service"  # 0-10 ms
    assert sum(g[1] for g in gaps) + tr.busy_s() == pytest.approx(tr.window_s)


def test_module_time_counts_whole_runs_in_the_window():
    tr = _hand_made()
    assert tr.module_time("serve_step") == (pytest.approx(0.03), 1)  # the second ends past the window
    assert tr.module_time("train_step") == (pytest.approx(0.01), 1)


def test_op_names_drop_the_hlo_text():
    assert trace.op_name("%fusion.141 = (f32[4]) fusion(f32[4] %a), kind=kLoop") == "%fusion.141"
    assert trace.op_name("copy.3") == "copy.3"


def test_chip_trace_fixture():
    """A trace recorded on one TPU v5e: three runs of a jitted 1024 x 1024
    bf16 step, each followed by 20 ms on the host in a ``release`` span,
    between the window's two marks."""
    from pathlib import Path

    tr = trace.load(str(Path(__file__).parent / "fixtures" / "v5e_three_steps.xplane.pb"))
    assert len(tr.ops) == 1 and len(tr.ops[0]) == 9
    assert tr.window_s == pytest.approx(0.0649, abs=1e-4)
    assert tr.busy_s() == pytest.approx(4.435e-05, rel=1e-3)
    assert tr.module_time("jit") == (pytest.approx(4.437e-05, rel=1e-3), 3)
    assert tr.top_ops(1)[0][0] == "%fusion"
    gaps = tr.idle_gaps(3)
    assert [g[0] for g in gaps] == ["chipbench.release:service"] * 3
    assert all(g[1] == pytest.approx(0.0216, abs=5e-4) for g in gaps)
