"""Whole runs at smoke size on the CPU: the harness's look for a chip is
skipped (``platform="cpu"``, a capacity given), the rest of a run is the
one the chip sees. The smoke root's limits are its own (``smoke.py``), set
from sound runs at that width: a sound run of each cell is correct at
them; a run with the timed path broken underneath is not, once per fault
a cell can have; the control is not either, by the same decision; and a
new cell is found from its files alone."""
import json
import math

import jax.numpy as jnp
import pytest

from chipbench import control, jobs, run

CAPACITY = 64 << 30
SEED = 2**35 + 77


def _run(root, cell, capsys, seconds="2", trace="0"):
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds", seconds, "--trace", trace],
                  root=root, platform="cpu", capacity=CAPACITY)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)


def test_sound_run(smoke, capsys):
    res = _run(smoke, "hymba.serve_train", capsys)
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"]
    checks = res["checks"]
    assert checks["window_compiles"]["value"] == 0 and checks["jobs_failed"]["value"] == 0
    assert all(math.isfinite(c["value"]) for c in checks.values())
    assert res["correct"], checks


def test_traced_run_reports_per_layer_metrics(smoke, capsys, cpu_peak):
    res = _run(smoke, "hymba.serve_train", capsys, trace="1")
    assert {"setup_compile_s", "queue_wait_p95_ms", "serve_p50_ms.train", "sched_gap_ms.train"} <= set(res["metrics"])
    assert "busy_s" in res["device"] and "breakdown" in res


def test_no_chip_no_result(smoke, capsys):
    rc = run.main(["--workload", "hymba.serve_train", "--seed", "1", "--seconds", "1"], root=smoke)
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


_train, _serve = jobs.train_step_fn, jobs.service_step_fn


def _unchanged(model):
    step = _train(model)

    def train_step(params, batch):
        return params, step(params, batch)[1]

    return train_step


def _half_batch(model):
    step = _train(model)

    def train_step(params, batch):
        n = batch["tokens"].shape[0] // 2
        return step(params, {k: (v[:n] if k != "lr" else v) for k, v in batch.items()})

    return train_step


def _altered_answer(model):
    step = _serve(model)

    def serve_step(params, batch):
        params, logits = step(params, batch)
        return params, jnp.roll(logits, 1, axis=-1)

    return serve_step


def _state_lost_at_switch(monkeypatch):
    """The executor hands a trainer, after another job's iteration, the
    state it was first given instead of the one it left."""
    from repro.core import session

    run_iteration = session.Session.run_iteration
    seen = {}

    def faulty(self, index):
        if self.job.kind == "train":
            first = seen.setdefault(self.name, self.state)
            if seen.get("last", self.name) != self.name:
                self.state = first
        seen["last"] = self.name
        return run_iteration(self, index)

    monkeypatch.setattr(session.Session, "run_iteration", faulty)


@pytest.mark.parametrize("fault,where,cell", [
    (_unchanged, "train_step_fn", "hymba.serve_train"),
    (_half_batch, "train_step_fn", "hymba.serve_train"),
    (_altered_answer, "service_step_fn", "hymba.serve_train"),
    (None, "state_lost_at_switch", "hymba.serve_train"),
])
def test_fault_is_not_correct(smoke, capsys, monkeypatch, fault, where, cell):
    if fault is None:
        _state_lost_at_switch(monkeypatch)
    else:
        monkeypatch.setattr(jobs, where, fault)
    res = _run(smoke, cell, capsys)
    assert not res["correct"], res["checks"]


def test_control_is_not_correct(smoke):
    got = control.judged(run.load_cell(smoke, "hymba.serve_train"), SEED, 2.0, 40)
    assert not got["correct"], got["checks"]


def test_new_cell_is_found_by_its_files(smoke, capsys):
    bench = json.loads((smoke / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "hymba.tiny", "config": "hymba-1.5b", "traffic": "tiny",
                               "chips": 1, "why": "a cell added by files alone"})
    (smoke / "BENCHMARK.json").write_text(json.dumps(bench))
    (smoke / "chipbench" / "traffic" / "tiny.json").write_text(json.dumps(
        {"policy": "fifo", "grace_s": 1.0, "trainers": [],
         "service": {"rate_rps": 5.0, "batch": 1, "seq": 8, "sample": 2}}))
    (smoke / "chipbench" / "checks" / "hymba.tiny.json").write_text(json.dumps(
        {"limits": {"logit_err": 0.1}}))
    res = _run(smoke, "hymba.tiny", capsys, seconds="1")
    assert res["correct"] and res["attempted"] == 5
    assert set(res["metrics"]) == {"setup_s"}


def test_checked_requests_take_half_from_those_after_a_switch():
    cell = run.Cell("c", 1, {}, {"service": {"sample": 4}}, {}, [], [])
    r = run.Run(cell=cell, seed=SEED, seconds=1.0, device=None, spans=run.Spans(False))
    r.service = type("S", (), {"name": "service"})()
    order = ["service", "train0.0", "service", "service", "service", "train0.0", "service", "service"]
    r.spans.events = [run.Span("run_iteration", job, t, t + 0.5) for t, job in enumerate(order)]
    assert r.served_after_switch() == [1, 4]
    keep = run.checked_requests(r)
    assert len(keep) == 4 and {1, 4} <= set(keep)
