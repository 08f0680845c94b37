"""``ssm_scan_kernel_share``: the share of the traced SSM-scan call sites
that took the Pallas kernel, from the counters of the program's span log;
on a traced smoke run (the CPU takes the XLA scan) and on hand-made logs."""
import json
import types

import pytest

from chipbench import run
from chipbench.tests.test_harness import CAPACITY, SEED

READ = run.metric_reader(run.ROOT, "ssm_scan_kernel_share")


def test_traced_smoke_run_reads_the_xla_path(smoke, capsys, cpu_peak):
    rc = run.main(["--workload", "hymba.serve_train", "--seed", str(SEED), "--seconds", "2",
                   "--trace", "1"], root=smoke, platform="cpu", capacity=CAPACITY)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["metrics"]["ssm_scan_kernel_share"] == {"value": 0.0, "unit": "%"}


@pytest.mark.parametrize(
    "counters,share",
    [
        ({"switches": 3, "ssm_scan.kernel": 2}, 100.0),
        ({"ssm_scan.kernel": 1, "ssm_scan.xla": 3}, 25.0),
        ({"ssm_scan.xla": 2}, 0.0),
        ({"switches": 3}, None),  # a program that counts no scan path
    ],
)
def test_share_of_the_counters(counters, share):
    log = types.SimpleNamespace(counters=counters)
    assert READ(types.SimpleNamespace(executor=types.SimpleNamespace(spans=log))) == share


def test_a_program_with_no_span_log_reads_nothing():
    assert READ(types.SimpleNamespace(executor=object())) is None
