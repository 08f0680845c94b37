"""jit'd public wrapper for the WKV6 kernel: model layout (b, s, h, d) <->
kernel layout (b, h, s, d). The kernel runs compiled for the TPU unless the
caller asks for the interpreter with ``interpret=True``."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels.rwkv_scan.kernel import wkv6_bhsd


def wkv6(
    r: jnp.ndarray,  # (b, s, h, dk)
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,  # (h, dk)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    tr = lambda t: jnp.swapaxes(t, 1, 2).astype(jnp.float32)
    o, s_final = wkv6_bhsd(
        tr(r), tr(k), tr(v), tr(w), u.astype(jnp.float32), chunk=chunk, interpret=interpret
    )
    return jnp.swapaxes(o, 1, 2), s_final
