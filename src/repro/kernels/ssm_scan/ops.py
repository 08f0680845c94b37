"""The selective scan's public entry: ``ssm_scan``, the Pallas forward
kernel with a ``jax.custom_vjp`` whose backward is the Pallas backward
kernel, and ``fits``, which says where it may run.

The kernels take A, B and C transposed so that the state's 16 entries lie
on sublanes and channels on lanes; the transposes here are of small
arrays. The kernel runs compiled for the TPU unless the caller asks for the
interpreter with ``interpret=True``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.dist import api as dist_api
from repro.kernels.ssm_scan.kernel import scan_bwd, scan_fwd

LANES = 128
MAX_BLOCK = 128  # time steps per block
MAX_TILE = 5 * LANES  # channels per tile


def blocks(s: int, c: int) -> Tuple[int, int]:
    """``(time block, channel tile)`` for a (.., s, c) scan: the block
    ``min(s, 128)``, the tile the widest multiple of 128 up to 640 that
    divides ``c``."""
    tile = max(t for t in range(LANES, MAX_TILE + 1, LANES) if c % t == 0)
    return min(s, MAX_BLOCK), tile


def fits(s: int, c: int) -> bool:
    """Whether the kernel runs here for a (.., s, c) scan: on a TPU, outside
    any sharding context (the kernel takes whole arrays), with the channels
    in 128-lane tiles and the sequence in time blocks."""
    return (
        on_tpu()
        and dist_api.current() is None
        and c % LANES == 0
        and s % 8 == 0
        and s % min(s, MAX_BLOCK) == 0
    )


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssm_scan(
    dt: jnp.ndarray,  # (b, s, c) fp32
    a: jnp.ndarray,  # (c, n) (negative)
    b_in: jnp.ndarray,  # (b, s, n)
    c_in: jnp.ndarray,  # (b, s, n)
    x: jnp.ndarray,  # (b, s, c)
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(y (b, s, c) fp32, h_final (b, c, n) fp32)``, as ``ssm_scan_ref``;
    every gradient in its primal's dtype."""
    return _fwd(dt, a, b_in, c_in, x, interpret, save=False)[0]


def _t(m: jnp.ndarray) -> jnp.ndarray:
    """(b, i, j) -> (b, j, i), in float32."""
    return jnp.swapaxes(m, 1, 2).astype(jnp.float32)


def _fwd(dt, a, b_in, c_in, x, interpret, save=True):
    block, tile = blocks(*dt.shape[1:])
    out = scan_fwd(
        dt.astype(jnp.float32), x, a.T.astype(jnp.float32), _t(b_in), _t(c_in),
        block=block, tile=tile, save=save, interpret=interpret,
    )
    y, h_final = out[:2]
    return (y, _t(h_final)), (dt, a, b_in, c_in, x, out[2] if save else None)


def _bwd(interpret, res, cot):
    dt, a, b_in, c_in, x, h_blocks = res
    dy, dh_final = cot
    block, tile = blocks(*dt.shape[1:])
    ddt, dx, da, db, dc = scan_bwd(
        dt.astype(jnp.float32), x, a.T.astype(jnp.float32), _t(b_in), _t(c_in), h_blocks,
        dy.astype(jnp.float32), _t(dh_final), block=block, tile=tile, interpret=interpret,
    )
    return (
        ddt.astype(dt.dtype),
        da.sum(0).T.astype(a.dtype),
        _t(db.sum(1)).astype(b_in.dtype),
        _t(dc.sum(1)).astype(c_in.dtype),
        dx,
    )


ssm_scan.defvjp(_fwd, _bwd)
