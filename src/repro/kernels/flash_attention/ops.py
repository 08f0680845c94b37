"""jit'd public wrapper: model layout (b, s, h, d) <-> kernel layout
(b, h, s, d). The kernel runs compiled for the TPU unless the caller asks
for the interpreter with ``interpret=True``."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd


def flash_attention(
    q: jnp.ndarray,  # (b, s_q, hq, d)
    k: jnp.ndarray,  # (b, s_k, hkv, d)
    v: jnp.ndarray,  # (b, s_k, hkv, d)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    q_offset: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(
        qt,
        kt,
        vt,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        q_offset=q_offset,
        interpret=interpret,
    )
    return jnp.swapaxes(out, 1, 2)
