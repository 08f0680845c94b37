#!/usr/bin/env python3
"""The output check's control: the reference put in the program's place and
computed one precision below the configuration's (bfloat16 parameters ->
float8 e4m3 matrix products), then judged by the same comparison and the
same limits as a run.

    python3 chipbench/control.py --workload <cell> --seconds <s> --train-steps <n> --seeds 1,2,3

For each seed it prints one JSON line: ``correct`` (which has to be false)
and each number beside its limit. Served requests: the control's logits at
the prompts a run with that seed would serve. Training: the control's first
``--train-steps`` steps on the first trainer's rows, as many as a run's
trainer takes in set-up, window and grace. The benchmark's own runs do not
run it.

``--variant half_batch`` reads a fault in the same way: the float32
reference put in the program's place, trained on half of each batch (the
mean taken over the rest).
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

import jax  # noqa: E402

from chipbench import jobs, run as bench, traffic  # noqa: E402
from chipbench.reference import common as ref_common  # noqa: E402


class HalfBatch:
    """A trainer's feed with half of each batch left out."""

    def __init__(self, feed: jobs.TrainFeed) -> None:
        self.feed, self.lr = feed, feed.lr

    def rows(self, step: int):
        tokens, labels = self.feed.rows(step)
        n = tokens.shape[0] // 2
        return tokens[:n], labels[:n]


def readings(cell: bench.Cell, seed: int, seconds: float, train_steps: int,
             variant: str = "control") -> Dict[str, float]:
    """The control's (or a planted fault's) readings for one seed, on the
    requests and rows a run of ``cell`` with that seed would have."""
    cfg, mix = cell.cfg, cell.mix
    vocab = int(cfg["vocab_size"])
    dot = ref_common.dot_f8 if variant == "control" else ref_common.dot_f32
    R = ref_common.Model(bench.reference_module(cfg), cfg, dot)
    prog: Dict[str, Any] = {}
    svc_feed: Optional[jobs.ServiceFeed] = None
    train_feed: Optional[jobs.TrainFeed] = None
    svc = mix.get("service")
    if svc and variant == "control":
        due = traffic.due_times(float(svc["rate_rps"]), seconds, seed)
        svc_feed = jobs.ServiceFeed(seed, int(svc["batch"]), int(svc["seq"]), vocab)
        keep = traffic.sample(seed, range(len(due)), int(svc["sample"]))
        P = R.params(seed)
        prog["logits"] = bench.serve_logits(R, P, svc_feed, keep)
        del P
        gc.collect()
    trainers = mix.get("trainers") or []
    if trainers:
        t = trainers[0]
        lr = traffic.learning_rates(t)[0]
        train_feed = jobs.TrainFeed(seed, 0, int(t["batch"]), int(t["seq"]), vocab, lr)
        P = R.params(seed)
        feed = HalfBatch(train_feed) if variant == "half_batch" else train_feed
        prog.update(bench.train_readings(R, P, feed, max(train_steps, bench.SETUP_STEPS)))
        del P
        gc.collect()
    out = bench.compare(cfg, seed, prog, svc_feed, train_feed)
    if "leaf_norms" in prog:
        print(f"[control] seed {seed} losses {prog.get('losses')} reference {prog.get('ref_losses')}; "
              "leaf norms (reference, control): " + json.dumps(prog["leaf_norms"]),
              file=sys.stderr, flush=True)
    return out


def judged(cell: bench.Cell, seed: int, seconds: float, train_steps: int,
           variant: str = "control") -> Dict[str, Any]:
    """The control's readings through the run's own decision."""
    got = readings(cell, seed, seconds, train_steps, variant)
    correct, checks = bench.judge({k: v for k, v in got.items() if k in cell.limits}, cell.limits)
    return {"correct": correct, "checks": checks,
            "not_compared": {k: v for k, v in got.items() if k not in cell.limits}}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--train-steps", type=int, default=bench.SETUP_STEPS)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--variant", choices=("control", "half_batch"), default="control")
    args = ap.parse_args(argv)
    cell = bench.load_cell(ROOT, args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control: JAX found no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = judged(cell, seed, args.seconds, args.train_steps, args.variant)
        print(json.dumps({"workload": cell.name, "variant": args.variant, "seed": seed,
                          "seconds": time.perf_counter() - t0, **out}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
