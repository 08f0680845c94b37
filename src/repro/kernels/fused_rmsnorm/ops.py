"""Public wrapper: arbitrary leading dims. The kernel runs compiled for the
TPU unless the caller asks for the interpreter with ``interpret=True``."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.fused_rmsnorm.kernel import fused_rmsnorm


def rmsnorm(
    x: jnp.ndarray,  # (..., d)
    scale: jnp.ndarray,
    residual: Optional[jnp.ndarray] = None,
    *,
    eps: float = 1e-6,
    interpret: bool = False,
) -> jnp.ndarray:
    shape = x.shape
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, shape[-1])
    r2 = residual.reshape(rows, shape[-1]) if residual is not None else None
    block = rows
    for cand in (128, 64, 32, 16, 8, 4, 2, 1):
        if rows % cand == 0:
            block = cand
            break
    out = fused_rmsnorm(x2, scale, r2, eps=eps, block_rows=block, interpret=interpret)
    return out.reshape(shape)
