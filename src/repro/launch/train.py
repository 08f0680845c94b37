"""End-to-end training CLI.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \\
        --steps 200 --ckpt-dir /tmp/ckpt --ckpt-every 20 \\
        --inject-failure 77 --mesh 1,1

Features exercised here (and by examples/train_lm.py + tests):
  * sharded train step on an arbitrary mesh (data, model),
  * async checkpointing + resume (restart supervisor),
  * failure injection (--inject-failure N kills the step loop at N),
  * straggler monitor on per-step wall times,
  * optional int8 error-feedback gradient compression (--compress-grads).
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import SyntheticLM
from repro.dist.api import use_sharding
from repro.dist.fault import (
    FailureInjector,
    InjectedFailure,
    RestartSupervisor,
    StragglerMonitor,
)
from repro.dist.sharding import batch_shardings, make_context, param_shardings
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import ModelOptions, build_model
from repro.train.grad_compress import ErrorFeedbackCompressor
from repro.train.optimizer import AdamW, AdamWConfig
from repro.train.train_step import TrainRunConfig, make_train_step
from repro.configs.base import ShapeConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="1,1", help="data,model mesh shape")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--inject-failure", type=int, action="append", default=None)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    mesh_shape = tuple(int(x) for x in args.mesh.split(","))
    mesh = make_mesh(mesh_shape, ("data", "model"))
    ctx = make_context(mesh, cfg)

    model = build_model(
        cfg,
        ModelOptions(
            loss_chunk=min(512, args.seq_len),
            moe_group=min(4096, args.batch * args.seq_len),
            wkv_chunk=min(64, args.seq_len),
            ssm_chunk=min(128, args.seq_len),
        ),
    )
    opt = AdamW(AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                            total_steps=args.steps))
    pipe = SyntheticLM(cfg.vocab_size, args.seq_len, args.batch, seed=0)
    step_fn = jax.jit(
        make_train_step(model, opt, TrainRunConfig(num_microbatches=args.microbatches))
    )

    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    injector = FailureInjector(args.inject_failure or [])
    monitor = StragglerMonitor()
    compressor = ErrorFeedbackCompressor() if args.compress_grads else None

    shape = ShapeConfig("cli", "train", args.seq_len, args.batch)
    b_sh = batch_shardings(cfg, shape, mesh)

    state = {}

    def fresh_state():
        params = model.init(jax.random.PRNGKey(0))
        p_sh = param_shardings(params, cfg, mesh)
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(
            opt.init(params), param_shardings(opt.init(params), cfg, mesh)
        )
        resid = compressor.init(params) if compressor else None
        return params, opt_state, resid

    def resume_step() -> int:
        if mgr is not None:
            mgr.wait()  # drain in-flight async saves before picking latest
        if mgr is None or mgr.latest_step() is None:
            state["params"], state["opt"], state["resid"] = fresh_state()
            return 0
        if "params" in state:
            template = {"params": state["params"], "opt": state["opt"]}
        else:  # fresh process resuming an existing run: abstract template
            aparams = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            template = {"params": aparams, "opt": jax.eval_shape(opt.init, aparams)}
        shardings = {
            "params": param_shardings(template["params"], cfg, mesh),
            "opt": param_shardings(template["opt"], cfg, mesh),
        }
        step, tree, meta = mgr.restore_tree(template, shardings=shardings)
        state["params"], state["opt"] = tree["params"], tree["opt"]
        if compressor is not None and state.get("resid") is None:
            state["resid"] = compressor.init(state["params"])
        print(f"[train] resumed from checkpoint step {step}")
        return step

    def body(start: int) -> int:
        with mesh, use_sharding(ctx):
            for i in range(start, args.steps):
                injector.maybe_fail(i)
                t0 = time.perf_counter()
                batch = {
                    k: jax.device_put(jnp.asarray(v), b_sh[k])
                    for k, v in pipe.batch(i).items()
                }
                if compressor is not None:
                    loss, grads = jax.value_and_grad(model.loss)(state["params"], batch)
                    grads, state["resid"] = compressor.apply(grads, state["resid"])
                    state["params"], state["opt"], metrics = opt.update(
                        grads, state["opt"], state["params"]
                    )
                    metrics["loss"] = loss
                else:
                    state["params"], state["opt"], metrics = step_fn(
                        state["params"], state["opt"], batch
                    )
                jax.block_until_ready(metrics["loss"])
                dur = time.perf_counter() - t0
                rep = monitor.observe(i, dur)
                if rep is not None:
                    print(f"[straggler] step {i}: {dur*1e3:.0f}ms ({rep.sigma:.1f} sigma)")
                if i % args.log_every == 0:
                    print(
                        f"step {i:5d} loss {float(metrics['loss']):.4f} "
                        f"gnorm {float(metrics['grad_norm']):.3f} {dur*1e3:.0f}ms"
                    )
                if mgr is not None and (i + 1) % args.ckpt_every == 0:
                    mgr.save(i + 1, {"params": state["params"], "opt": state["opt"]})
        if mgr is not None:
            mgr.save(args.steps, {"params": state["params"], "opt": state["opt"]})
            mgr.wait()
        return args.steps

    sup = RestartSupervisor(max_restarts=3)
    sup.run(body, resume_step)
    if sup.restarts:
        print(f"[train] completed after {sup.restarts} restart(s)")
    print(f"[train] done: {args.steps} steps; stragglers flagged: {len(monitor.flagged)}")


if __name__ == "__main__":
    main()
