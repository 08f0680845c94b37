"""A copy of the benchmark's data at smoke width, for the CPU tests: the
same cells, configuration files cut to 64 wide and 2 layers, traffic small
enough for a run of a few seconds on the host, and limits of the smoke
width's own (``SMOKE_LIMITS``).

The configurations are the benchmark's (``chipbench/configs``) and those
that only the tests use (``tests/configs``: ``rwkv6-7b.l8``, whose
reference is built and tested but which is in no cell yet)."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CONFIG_DIRS = (REPO / "chipbench" / "configs", Path(__file__).resolve().parent / "configs")

SMOKE_SIZES = {
    "hymba": dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, intermediate_size=128, vocab_size=256,
                  mamba_d_state=8, mamba_dt_rank=4, sliding_window=32),
    "rwkv6": dict(num_hidden_layers=2, hidden_size=64, attention_hidden_size=64, head_size=16,
                  num_attention_heads=4, intermediate_size=128, vocab_size=256),
}
SMOKE_TRAFFIC = {"batch": 2, "seq": 16}
SMOKE_LR = 0.0625  # the published width's lr of 1.0 makes the 64-wide model diverge

# Limits at smoke width, set from sound runs here (``python -m
# chipbench.tests.smoke 9`` prints their readings), as far below the control
# and the planted faults (``test_harness.py``) as the width lets them be:
#   logit_err         sound <= 0.027; control 0.43, altered answer 1.5
#   grad_gap          sound <= 0.0073; control 0.042, half batch 0.47
#   change_gap        sound <= 0.0082; control 0.037, half batch 0.44, unchanged 1
#   final_change_gap  sound <= 0.055; state lost at a switch 0.64, half batch 0.79
# bfloat16 over 64-wide rows reads higher than at the published width, so the
# chip's limits do not serve here. The per-step loss gap is read and not
# compared, here as on the chip: nothing planted reads higher than sound runs.
SMOKE_LIMITS = {"logit_err": 0.1, "grad_gap": 0.02, "change_gap": 0.02, "final_change_gap": 0.2}


def config(root: Path, name: str) -> dict:
    """Configuration ``name`` as ``root`` has it."""
    return json.loads((root / "chipbench" / "configs" / f"{name}.json").read_text())


def published_config(name: str) -> dict:
    for d in CONFIG_DIRS:
        if (d / f"{name}.json").exists():
            return json.loads((d / f"{name}.json").read_text())
    raise FileNotFoundError(name)


def smoke_root(tmp: Path) -> Path:
    """A checkout-like root under ``tmp``: BENCHMARK.json and chipbench's
    data directories, every configuration cut to smoke size and every
    traffic mix to smoke shapes. The code stays the repository's."""
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for sub in ("traffic", "checks", "metrics"):
        shutil.copytree(REPO / "chipbench" / sub, tmp / "chipbench" / sub)
    for d in CONFIG_DIRS:
        shutil.copytree(d, tmp / "chipbench" / "configs", dirs_exist_ok=True)
    for f in (tmp / "chipbench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg.update(SMOKE_SIZES[cfg["model_type"]])
        f.write_text(json.dumps(cfg))
    for f in (tmp / "chipbench" / "traffic").glob("*.json"):
        mix = json.loads(f.read_text())
        if mix.get("service"):
            mix["service"].update(SMOKE_TRAFFIC, rate_rps=20.0, sample=8)
        mix["grace_s"] = 0.5
        for t in mix.get("trainers", []):
            t.update(SMOKE_TRAFFIC, lr=[SMOKE_LR])
            if t.get("steps"):
                t.update(steps=4, count=3)
        f.write_text(json.dumps(mix))
    for f in (tmp / "chipbench" / "checks").glob("*.json"):
        limits = json.loads(f.read_text())["limits"]
        f.write_text(json.dumps({"limits": {k: SMOKE_LIMITS[k] for k in limits}}))
    return tmp


def readings(seeds, cells=("hymba.serve_train",), seconds="2"):
    """Sound runs at smoke width on the CPU: yields (cell, seed, checks)."""
    import contextlib
    import io
    import tempfile

    from chipbench import run

    with tempfile.TemporaryDirectory() as tmp:
        root = smoke_root(Path(tmp))
        for cell in cells:
            for seed in seeds:
                out = io.StringIO()
                with contextlib.redirect_stdout(out):
                    run.main(["--workload", cell, "--seed", str(seed), "--seconds", seconds],
                             root=root, platform="cpu", capacity=64 << 30)
                yield cell, seed, json.loads(out.getvalue().strip().splitlines()[-1])["checks"]


if __name__ == "__main__":
    import os
    import sys

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for cell, seed, checks in readings(range(2**35, 2**35 + int(sys.argv[1]) if len(sys.argv) > 1 else 2**35 + 6)):
        print(cell, seed, {k: round(c["value"], 6) for k, c in checks.items()}, flush=True)
