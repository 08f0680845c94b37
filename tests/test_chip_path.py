"""The path that runs on the chip, rehearsed on the CPU: a queued job holds
no device memory, the fleet places each job's state on its own device,
``serve.main`` fails the run over a failed or rejected job, the compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says or to ``.jax_cache/``, and
``chip_smoke.py`` runs end to end at smoke width (with its platform check
and the chip's memory statistics stood in for by the test) and refuses to
run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from repro.core import MemoryProfile, SalusExecutor, get_policy
from repro.core.profiles import profile_executable
from repro.core.session import Session
from repro.launch import cache, serve

ROOT = Path(__file__).resolve().parents[1]


def _run(script: str, *, cwd: Path = ROOT, devices: int = 1, pythonpath: bool = True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    if pythonpath:
        env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, *script], capture_output=True, text=True, env=env,
        cwd=cwd, timeout=600,
    )


def _smoke_total(step_of: str) -> int:
    """P+E of the smoke-width hymba service or trainer, as profiled."""
    model = serve.serving_model(chip_smoke.ARCH, smoke=True)
    if step_of == "service":
        _, step, data_fn = serve.service_fns(model)
    else:
        step, data_fn = serve.trainer_fns(model)
    compiled = jax.jit(step).lower(
        model.abstract_params(), jax.eval_shape(data_fn, 0)
    ).compile()
    return profile_executable(compiled).total


# ---------------------------------------------------------------------------
# a queued job holds no device memory
# ---------------------------------------------------------------------------


def test_queued_session_state_waits_on_host_until_admission():
    ex = SalusExecutor(capacity=100, policy=get_policy("fifo"))
    seen = {}

    def make(name):
        def step(state, batch):
            seen.setdefault(name, set()).update(state.devices())
            return state + 1.0

        return Session(
            name, step, jnp.zeros((4,), jnp.float32), lambda i: None, 2,
            profile=MemoryProfile(persistent=60, ephemeral=40),
        )

    a, b = make("a"), make("b")
    ex.submit(a)
    ex.submit(b)  # does not fit beside a: queued
    assert isinstance(a.state, jax.Array) and a.state.devices() == {ex.device}
    assert isinstance(b.state, np.ndarray), "a queued job's state left the host"
    ex.run()
    assert seen == {"a": {ex.device}, "b": {ex.device}}
    # a finished job's state is back on the host, and it ran two steps
    assert isinstance(b.state, np.ndarray) and float(b.state[0]) == 2.0


# ---------------------------------------------------------------------------
# serve.main fails the run over a failed or rejected job
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "case,capacity_gb,rc",
    [("serves", 1.0, 0), ("step raises", 1.0, 1), ("rejected", 1e-9, 1)],
)
def test_serve_main_exit_code(monkeypatch, case, capacity_gb, rc):
    def fake_service(name, smoke, max_len=64):
        def handle(state, request):
            return state, {"y": state.sum() + request}

        def data_fn(i):
            if case == "step raises" and i == 1:
                raise RuntimeError("boom")
            return jnp.float32(i)

        return handle, np.ones((8,), np.float32), data_fn, None

    monkeypatch.setattr(serve, "make_service", fake_service)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    argv = ["--archs", "tiny", "--rps", "50", "--duration", "1", "--requests", "3",
            "--capacity-gb", str(capacity_gb)]
    assert serve.main(argv) == rc


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
    assert cache.enable_compile_cache() == "/elsewhere/jax"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cache.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_capacity_above_the_device_limit_is_an_error():
    class Dev:
        device_kind = "fake"

        def memory_stats(self):
            return {"bytes_limit": 1000}

    assert serve.device_capacity(Dev()) == 1000
    assert serve.device_capacity(Dev(), 600) == 600
    with pytest.raises(ValueError, match="exceeds"):
        serve.device_capacity(Dev(), 1001)
    Dev.memory_stats = lambda self: None
    with pytest.raises(ValueError, match="no bytes_limit"):
        serve.device_capacity(Dev())


# ---------------------------------------------------------------------------
# the fleet places each job's state on its own device
# ---------------------------------------------------------------------------

FLEET_SCRIPT = textwrap.dedent(
    """
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import ClusterExecutor, VirtualDevice

    fleet = ClusterExecutor(4, 1 << 30, "fifo", bind_jax_devices=True)
    vdev = VirtualDevice(fleet)
    step = lambda state, batch: (state * 2.0 + batch, {"y": state.sum()})
    sessions = [
        vdev.create_session(f"j{i}", step, np.ones((3,), np.float32),
                            lambda k: jnp.ones((3,)), n_iters=2)
        for i in range(4)
    ]
    report = vdev.run()
    out = []
    for s in sessions:
        dev = fleet.executors[report.plan.assignments[s.job.job_id]].device
        out.append({
            "placed": str(dev),
            "ran_on": sorted({str(d) for m in s.metrics_log for d in m["y"].devices()}),
            "compiled_for": sorted({str(d) for sh in s.executable.input_shardings[0]
                                    for d in sh.device_set}),
            "iterations": report.stats[s.job.job_id].iterations_done,
        })
    try:
        ClusterExecutor(5, 1 << 30, "fifo", bind_jax_devices=True)
        out.append("five executors on four devices were accepted")
    except ValueError as e:
        out.append(str(e))
    print(json.dumps(out))
    """
)


def test_fleet_binds_each_session_state_to_its_own_device():
    res = _run(["-c", FLEET_SCRIPT], devices=4)
    assert res.returncode == 0, res.stderr[-2000:]
    *jobs, refusal = json.loads(res.stdout.strip().splitlines()[-1])
    assert len({j["placed"] for j in jobs}) == 4
    for j in jobs:
        assert j["ran_on"] == [j["placed"]] and j["compiled_for"] == [j["placed"]]
        assert j["iterations"] == 2
    assert "5 executors but only 4 devices" in refusal


# ---------------------------------------------------------------------------
# chip_smoke.py, rehearsed at smoke width
# ---------------------------------------------------------------------------


def test_chip_smoke_one_chip_phase_at_smoke_width(monkeypatch, capsys):
    # room for either job alone, not for both: the trainer must queue
    cap = max(_smoke_total("service"), _smoke_total("trainer")) + 1024
    stats = {"bytes_limit": cap, "peak_bytes_in_use": 1 << 40}
    monkeypatch.setattr(chip_smoke, "memory_stat", lambda device, key: stats[key])
    assert chip_smoke.one_chip(smoke=True) == []
    out = capsys.readouterr().out
    assert "requests served: 8/8" in out
    assert "trainer steps: 4/4" in out
    assert "compilations inside the request window: 0" in out
    assert "queue train:hymba-1.5b" in out and "second_chance train:hymba-1.5b" in out


FOUR_CHIP_SCRIPT = textwrap.dedent(
    """
    import json
    import jax
    import chip_smoke
    from repro.core.profiles import profile_executable
    from repro.launch import serve

    model = serve.serving_model(chip_smoke.ARCH, smoke=True)
    _, handle, data_fn = serve.service_fns(model)
    compiled = jax.jit(handle).lower(
        model.abstract_params(), jax.eval_shape(data_fn, 0)
    ).compile()
    # room for one service per device
    stats = {"bytes_limit": profile_executable(compiled).total + 1024,
             "peak_bytes_in_use": 1 << 40}
    chip_smoke.memory_stat = lambda device, key: stats[key]
    print(json.dumps(chip_smoke.four_chips(smoke=True)))
    """
)


def test_chip_smoke_four_chip_phase_on_four_host_devices():
    res = _run(["-c", FOUR_CHIP_SCRIPT], devices=4)
    assert res.returncode == 0, res.stderr[-2000:]
    assert json.loads(res.stdout.strip().splitlines()[-1]) == [], res.stdout[-3000:]
    assert res.stdout.count("8/8 requests") == 4


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path, where):
    if where == "checkout":
        res = _run([str(ROOT / "chip_smoke.py")])
    else:  # a directory holding the script and nothing else of the repo
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        res = _run(["chip_smoke.py"], cwd=tmp_path, pythonpath=False)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
