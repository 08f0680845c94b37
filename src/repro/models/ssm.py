"""Mamba-style selective SSM branch (hymba's parallel SSM heads).

Four paths, one math:
  * ``ssm_scan_ref``      — step-by-step lax.scan (oracle; the chunked
                            form's fallback),
  * ``ssm_scan_chunked``  — chunk-sequential / intra-chunk-parallel
                            (associative-scan) form used for train/prefill,
  * the Pallas kernels (``repro.kernels.ssm_scan``), which take the place
    of ``ssm_scan_chunked`` in ``ssm_apply`` where ``ops.fits`` says they
    run: on a TPU, unsharded, at shapes that tile (hymba's 3200 channels
    do). The trace counts which path each call took (``ssm_scan.kernel``
    or ``ssm_scan.xla``, ``core/spans.py``),
  * decode single-step with conv ring state.

The recurrence (diagonal A, per-channel dt):
    h_t = exp(dt_t * A) .. h_{t-1} + dt_t * B_t x_t      h: (c, n)
    y_t = <h_t, C_t> + D * x_t
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import spans
from repro.dist.api import constrain
from repro.kernels.ssm_scan import ops as scan_ops
from repro.models.layers import Params, dense_init


def ssm_dims(cfg: ArchConfig) -> Tuple[int, int, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    dt_rank = max(1, math.ceil(cfg.d_model / 16))
    return d_inner, dt_rank, cfg.ssm_state


def ssm_init(rng, cfg: ArchConfig, dtype) -> Params:
    d_inner, dt_rank, n = ssm_dims(cfg)
    keys = jax.random.split(rng, 6)
    a = jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (d_inner, n))
    return {
        "in_proj": dense_init(keys[0], cfg.d_model, 2 * d_inner, dtype),
        "conv_w": (jax.random.normal(keys[1], (cfg.ssm_conv, d_inner), jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((d_inner,), dtype),
        "x_proj": dense_init(keys[2], d_inner, dt_rank + 2 * n, dtype),
        "dt_proj": dense_init(keys[3], dt_rank, d_inner, dtype),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((d_inner,), 0.01, jnp.float32))),  # softplus^-1
        "a_log": jnp.log(a),
        "d_skip": jnp.ones((d_inner,), jnp.float32),
        "out_proj": dense_init(keys[4], d_inner, cfg.d_model, dtype),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv over seq. x: (b, s, c), w: (k, c)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        out = out + xp[:, i : i + x.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def _ssm_inputs(p: Params, cfg: ArchConfig, xz: jnp.ndarray):
    """Shared pre-scan computation. xz: (b, s, 2*d_inner) from in_proj."""
    d_inner, dt_rank, n = ssm_dims(cfg)
    x, z = jnp.split(xz, 2, axis=-1)
    x = _causal_conv(x, p["conv_w"], p["conv_b"])
    x = jax.nn.silu(x)
    proj = jnp.einsum("bsc,cp->bsp", x, p["x_proj"])
    dt_in, b_in, c_in = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", dt_in, p["dt_proj"]).astype(jnp.float32)
        + p["dt_bias"]
    )  # (b, s, c) fp32
    a = -jnp.exp(p["a_log"])  # (c, n)
    return x, z, dt, b_in.astype(jnp.float32), c_in.astype(jnp.float32), a


def ssm_scan_ref(
    dt: jnp.ndarray,  # (b, s, c) fp32
    a: jnp.ndarray,  # (c, n) fp32 (negative)
    b_in: jnp.ndarray,  # (b, s, n)
    c_in: jnp.ndarray,  # (b, s, n)
    x: jnp.ndarray,  # (b, s, c)
    h0: jnp.ndarray | None = None,  # (b, c, n)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential oracle. Returns (y (b,s,c) fp32, h_final (b,c,n))."""
    bsz, s, c = dt.shape
    n = a.shape[1]
    if h0 is None:
        h0 = jnp.zeros((bsz, c, n), jnp.float32)

    def step(h, inp):
        dt_t, b_t, c_t, x_t = inp
        decay = jnp.exp(dt_t[..., None] * a)  # (b, c, n)
        h = decay * h + (dt_t * x_t.astype(jnp.float32))[..., None] * b_t[:, None, :]
        y = jnp.einsum("bcn,bn->bc", h, c_t)
        return h, y

    xs = (
        jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(b_in, 1, 0),
        jnp.moveaxis(c_in, 1, 0),
        jnp.moveaxis(x, 1, 0),
    )
    h_final, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h_final


def ssm_scan_chunked(
    dt: jnp.ndarray,
    a: jnp.ndarray,
    b_in: jnp.ndarray,
    c_in: jnp.ndarray,
    x: jnp.ndarray,
    *,
    chunk: int = 128,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunk-sequential scan: sequential over seq/chunk steps, parallel
    (associative scan) within each chunk. Identical numerics to the ref
    (both fp32 state)."""
    bsz, s, c = dt.shape
    n = a.shape[1]
    if s % chunk != 0:
        return ssm_scan_ref(dt, a, b_in, c_in, x)
    n_chunks = s // chunk

    def rearr(t):  # (b, s, ...) -> (n_chunks, b, chunk, ...)
        return jnp.moveaxis(
            t.reshape(bsz, n_chunks, chunk, *t.shape[2:]), 1, 0
        )

    dt_c, b_c, c_c, x_c = rearr(dt), rearr(b_in), rearr(c_in), rearr(x)

    def chunk_step(h0, inp):
        dt_t, b_t, c_t, x_t = inp  # (b, chunk, ...)
        log_decay = dt_t[..., None] * a  # (b, L, c, n), negative
        u = (dt_t * x_t.astype(jnp.float32))[..., None] * b_t[:, :, None, :]  # (b,L,c,n)

        def combine(lhs, rhs):
            la, lb = lhs
            ra, rb = rhs
            return la + ra, jnp.exp(ra) * lb + rb

        cum_log, h_scan = jax.lax.associative_scan(
            combine, (log_decay, u), axis=1
        )
        h_all = h_scan + jnp.exp(cum_log) * h0[:, None]  # fold in carry
        y = jnp.einsum("blcn,bln->blc", h_all, c_t)
        return h_all[:, -1], y

    h_final, ys = jax.lax.scan(
        chunk_step, jnp.zeros((bsz, c, n), jnp.float32), (dt_c, b_c, c_c, x_c)
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, c)
    return y, h_final


def ssm_apply(
    p: Params,
    cfg: ArchConfig,
    xin: jnp.ndarray,  # (b, s, d_model)
    *,
    chunk: int = 128,
    return_state: bool = False,
):
    """Full-sequence SSM branch (train / prefill). With ``return_state``,
    also returns (h_final (b,c,n), conv ring state (b, conv_w-1, c)).
    ``chunk`` sizes the XLA scan; where the kernel runs it is unused."""
    xz = jnp.einsum("bsd,dc->bsc", xin, p["in_proj"])
    xz = constrain(xz, ("data", None, "model"))
    x, z, dt, b_in, c_in, a = _ssm_inputs(p, cfg, xz)
    if scan_ops.fits(*dt.shape[1:]):
        spans.count("ssm_scan.kernel")
        y, h_final = scan_ops.ssm_scan(dt, a, b_in, c_in, x)
    else:
        spans.count("ssm_scan.xla")
        y, h_final = ssm_scan_chunked(dt, a, b_in, c_in, x, chunk=chunk)
    y = y + p["d_skip"] * x.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(xin.dtype)
    out = jnp.einsum("bsc,cd->bsd", y, p["out_proj"])
    if return_state:
        x_pre_conv = jnp.split(xz, 2, axis=-1)[0]
        conv_state = x_pre_conv[:, -(cfg.ssm_conv - 1):]
        return out, (h_final, conv_state)
    return out


# ---------------------------------------------------------------------------
# Decode (recurrent state: conv ring + ssm state)
# ---------------------------------------------------------------------------


def ssm_init_state(cfg: ArchConfig, batch: int, dtype) -> Dict:
    d_inner, _, n = ssm_dims(cfg)
    return {
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1, d_inner), dtype),
        "h": jnp.zeros((cfg.n_layers, batch, d_inner, n), jnp.float32),
    }


def ssm_decode(
    p: Params,
    cfg: ArchConfig,
    xin: jnp.ndarray,  # (b, 1, d_model)
    state: Dict,  # {"conv": (b, k-1, c), "h": (b, c, n)} (this layer's slice)
) -> Tuple[jnp.ndarray, Dict]:
    d_inner, dt_rank, n = ssm_dims(cfg)
    xz = jnp.einsum("bsd,dc->bsc", xin, p["in_proj"])
    x_new, z = jnp.split(xz, 2, axis=-1)  # (b, 1, c)
    window = jnp.concatenate([state["conv"], x_new], axis=1)  # (b, k, c)
    x = jnp.einsum(
        "bkc,kc->bc", window.astype(jnp.float32), p["conv_w"].astype(jnp.float32)
    ) + p["conv_b"].astype(jnp.float32)
    x = jax.nn.silu(x).astype(xin.dtype)[:, None, :]  # (b, 1, c)
    proj = jnp.einsum("bsc,cp->bsp", x, p["x_proj"])
    dt_in, b_in, c_in = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", dt_in, p["dt_proj"]).astype(jnp.float32) + p["dt_bias"]
    )[:, 0]  # (b, c)
    a = -jnp.exp(p["a_log"])
    decay = jnp.exp(dt[..., None] * a)  # (b, c, n)
    h = decay * state["h"] + (dt * x[:, 0].astype(jnp.float32))[..., None] * b_in.astype(
        jnp.float32
    )[:, 0, None, :]
    y = jnp.einsum("bcn,bn->bc", h, c_in.astype(jnp.float32)[:, 0])
    y = y + p["d_skip"] * x[:, 0].astype(jnp.float32)
    y = (y * jax.nn.silu(z[:, 0].astype(jnp.float32))).astype(xin.dtype)
    out = jnp.einsum("bc,cd->bd", y, p["out_proj"])[:, None, :]
    new_state = {"conv": window[:, 1:], "h": h}
    return out, new_state
