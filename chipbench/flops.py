"""Model FLOPs of a step, from the configuration file's ``flops`` entry.

The entry is analytic (``tests/test_flops.py`` checks it against the
parameter shapes):

* ``layers_per_token``: 2 x the matrix-multiply parameters of all layers,
  plus each layer's fixed per-token mixer work (the SSM or WKV recurrence);
* ``head_per_token``: 2 x vocab x hidden, one vocabulary projection;
* ``attention_per_key``: 4 x heads x head_dim x layers, the score and value
  products for one query against one key.

A prefill needs the head at the last position only; training needs it at
every position, and three times the forward work (forward and backward).
Recomputation is not counted. Attention counts the keys a causal window
reaches: ``sum_t min(t + 1, window)``.
"""
from __future__ import annotations

from typing import Any, Dict


def keys_attended(seq: int, window: int) -> int:
    if window <= 0 or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def forward(f: Dict[str, Any], batch: int, seq: int, head_positions: int) -> float:
    attn = f["attention_per_key"] * keys_attended(seq, int(f.get("window", 0)))
    return batch * (seq * f["layers_per_token"] + head_positions * f["head_per_token"] + attn)


def prefill(f: Dict[str, Any], batch: int, seq: int) -> float:
    return forward(f, batch, seq, head_positions=1)


def train_step(f: Dict[str, Any], batch: int, seq: int) -> float:
    return 3.0 * forward(f, batch, seq, head_positions=seq)
