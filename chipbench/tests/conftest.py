"""CPU tests of the benchmark: ``python -m pytest chipbench/tests`` from the
repository's root. They run at smoke size (``smoke.py``) and never ask for
a chip."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def cpu_peak(monkeypatch):
    """A made-up peak for the CPU, so that the readers that divide by a peak
    run; no test reads their values."""
    from chipbench import peaks

    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    from chipbench.tests.smoke import smoke_root

    return smoke_root(tmp_path_factory.mktemp("root"))
