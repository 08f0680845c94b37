"""Traffic from a seed: request due times, request tokens, training rows.

Arrivals are open-loop and Poisson-like, but every seed gets the same set
of gaps: the ``n`` quantiles of an exponential distribution with the mix's
rate, ``n = rate * seconds``, in an order drawn from the seed. So the seed
changes which requests come in bursts and not how much work there is, and
all requests fall due in ``[0, seconds)``. (The program's
``core.tracegen.poisson_arrivals`` draws gaps freely, so its request count
and load vary from seed to seed.)
"""
from __future__ import annotations

import math
from typing import List, Tuple

import numpy as np

SERVICE, TRAINER, WARMUP = 1, 2, 3  # streams of the seed


def due_times(rate: float, seconds: float, seed: int) -> Tuple[float, ...]:
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / (gaps.sum() * (1 + 0.5 / n))  # the last falls due before `seconds`
    rng = np.random.default_rng([seed, SERVICE])
    rng.shuffle(gaps)
    times = np.cumsum(gaps)  # gaps[k] is the wait before request k
    return tuple(float(round(t, 6)) for t in times)


def tokens(seed: int, stream: int, index: int, shape: Tuple[int, ...], vocab: int,
           job: int = 0) -> np.ndarray:
    """Token ids for request or step ``index`` of ``stream``."""
    rng = np.random.default_rng([seed, stream, job, index])
    return rng.integers(0, vocab, size=shape, dtype=np.int32)


def train_rows(seed: int, job: int, step: int, batch: int, seq: int, vocab: int):
    """(tokens, labels) of one training step: next-token prediction over
    ``seq + 1`` drawn ids."""
    ids = tokens(seed, TRAINER, step, (batch, seq + 1), vocab, job)
    return ids[:, :-1], ids[:, 1:]


def bf16_exact(x: float) -> float:
    """``x`` rounded to the nearest bfloat16, so a float32 learning rate and
    its bfloat16 cast are one number."""
    import ml_dtypes

    return float(np.float32(np.array(x, dtype=np.float32).astype(ml_dtypes.bfloat16)))


def learning_rates(spec: dict) -> List[float]:
    """A trainer entry's learning rates: ``lr`` as listed, or ``count``
    log-spaced over ``lr_range``."""
    if "lr" in spec:
        lrs = list(spec["lr"])
    else:
        lo, hi = spec["lr_range"]
        n = int(spec["count"])
        lrs = [lo * (hi / lo) ** (i / max(1, n - 1)) for i in range(n)]
    return [bf16_exact(x) for x in lrs]


def sample(seed: int, ids, k: int, stream: int = 0) -> List[int]:
    """``k`` of ``ids`` (all of them if fewer), drawn from the seed."""
    ids = list(ids)
    rng = np.random.default_rng([seed, 99, stream])
    pick = rng.choice(len(ids), size=min(k, len(ids)), replace=False)
    return sorted(int(ids[i]) for i in pick)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else math.nan
