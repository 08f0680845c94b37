"""The users' programs, in Salus terms: a service and a trainer, built on the
program's model through its serving path, and the feeds that give them
requests and rows from the seed.

* A service handles one request per iteration: one ``Model.prefill`` of a
  ``(batch, seq)`` token block, returning ``(state, last-position logits)``.
* A trainer takes one SGD step per iteration: ``Model.loss`` under
  ``jax.value_and_grad``, and ``p - lr * g`` with the learning rate taken
  from the batch and cast to each leaf's dtype (a float32 scalar would
  promote the update of the bfloat16 leaves).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import numpy as np

from chipbench import traffic

# configuration-file key -> the program's ArchConfig field
CONFIG_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "mamba_d_state": "ssm_state",
    "mamba_expand": "ssm_expand",
    "mamba_d_conv": "ssm_conv",
    "sliding_window": "sliding_window",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "head_size": "rwkv_head_dim",
}


def program_model(cfg: Dict[str, Any]):
    """The program's model for a configuration file: its registered
    architecture with the file's sizes, and the serving path's model
    options with the file's parameter dtype."""
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models import build_model

    arch = get_config(cfg["program_arch"])
    arch = dataclasses.replace(
        arch, **{f: cfg[k] for k, f in CONFIG_KEYS.items() if k in cfg}
    )
    opts = dataclasses.replace(serve._MODEL_OPTS, param_dtype=cfg["param_dtype"])
    return build_model(arch, opts)


def service_step_fn(model):
    def serve_step(params, batch):
        logits, _ = model.prefill(params, batch)
        return params, logits

    return serve_step


def train_step_fn(model):
    def train_step(params, batch):
        rows = {"tokens": batch["tokens"], "labels": batch["labels"]}
        loss, grads = jax.value_and_grad(model.loss)(params, rows)
        lr = batch["lr"]
        params = jax.tree_util.tree_map(lambda p, g: p - lr.astype(p.dtype) * g, params, grads)
        return params, {"loss": loss}

    return train_step


class ServiceFeed:
    """Request ``i``'s tokens; a request is ``(batch, seq)`` ids."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int) -> None:
        self.seed, self.shape, self.vocab = seed, (batch, seq), vocab

    def __call__(self, i: int) -> Dict[str, np.ndarray]:
        return {"tokens": traffic.tokens(self.seed, traffic.SERVICE, i, self.shape, self.vocab)}

    def warmup(self) -> Dict[str, np.ndarray]:
        return {"tokens": traffic.tokens(self.seed, traffic.WARMUP, 0, self.shape, self.vocab)}


class TrainFeed:
    """Step ``i + offset``'s rows and the trainer's learning rate. Set-up
    runs the first steps, then raises ``offset`` so that the executor's
    count, which starts again at 0, gets rows that all differ."""

    def __init__(self, seed: int, job: int, batch: int, seq: int, vocab: int, lr: float) -> None:
        self.seed, self.job, self.batch, self.seq, self.vocab = seed, job, batch, seq, vocab
        self.lr = np.float32(lr)
        self.offset = 0

    def rows(self, step: int):
        return traffic.train_rows(self.seed, self.job, step, self.batch, self.seq, self.vocab)

    def __call__(self, i: int) -> Dict[str, np.ndarray]:
        tokens, labels = self.rows(i + self.offset)
        return {"tokens": tokens, "labels": labels, "lr": self.lr}
