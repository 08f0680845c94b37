"""The plain references against the program at smoke width, both in
float32 at the highest matmul precision: the same loss, gradients and
served logits."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from chipbench import jobs, traffic, weights
from chipbench.reference import common
from chipbench.run import reference_module


def _setup(smoke, name):
    cfg = json.loads((smoke / "chipbench" / "configs" / f"{name}.json").read_text())
    cfg = dict(cfg, param_dtype="float32")
    model = jobs.program_model(cfg)
    model.opts = dataclasses.replace(model.opts, compute_dtype="float32")
    mod = reference_module(cfg)
    return cfg, model, mod, mod.spec(cfg)


@pytest.mark.parametrize("name", ["hymba-1.5b", "rwkv6-7b.l8"])
def test_loss_and_sgd_step_match_program(smoke, name):
    cfg, model, mod, spec = _setup(smoke, name)
    seed, lr = 11, 1.0
    with jax.default_matmul_precision("highest"):
        params = weights.unflatten(weights.make_stacked(seed, spec))
        tokens, labels = traffic.train_rows(seed, 0, 0, 2, 16, cfg["vocab_size"])
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, {"tokens": tokens, "labels": labels})
    R = common.Model(mod, cfg, common.dot_f32)
    P = R.params(seed)
    ref_loss = R.sgd_step(P, tokens, labels, lr)
    assert abs(float(loss) - ref_loss) <= 1e-5 * abs(ref_loss)
    flat0, g = weights.flatten(params), weights.flatten(grads)
    norms = {p: float(np.linalg.norm(np.asarray(v))) for p, v in g.items()}
    med = np.median(list(norms.values()))
    for p, gv in g.items():
        new = np.stack([np.asarray(l[p]) for l in P.layers]) if p.startswith("layers/") else np.asarray(P.top[p])
        ref_g = (np.asarray(flat0[p]) - new) / lr
        if norms[p] < 1e-3 * med:  # nought to rounding: left out, as the check does
            continue
        err = np.linalg.norm(np.asarray(gv) - ref_g) / max(norms[p], med)
        assert err < 1e-3, (p, err)


@pytest.mark.parametrize("name", ["hymba-1.5b", "rwkv6-7b.l8"])
def test_last_logits_match_prefill(smoke, name):
    cfg, model, mod, spec = _setup(smoke, name)
    seed = 12
    tokens = traffic.tokens(seed, traffic.SERVICE, 0, (3, 16), cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        params = weights.unflatten(weights.make_stacked(seed, spec))
        got, _ = jax.jit(model.prefill)(params, {"tokens": tokens})
    R = common.Model(mod, cfg, common.dot_f32)
    ref = R.last_logits(R.params(seed), tokens)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4, atol=1e-4 * np.abs(ref).max())
