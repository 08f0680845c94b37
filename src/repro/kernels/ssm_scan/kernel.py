"""Mamba selective scan (hymba's SSM heads) as Pallas TPU kernels, forward
and backward.

The recurrence, per batch row, channel c and state n (diagonal A):
    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t        h: (n, c)
    y_t = sum_n C_t * h_t

The XLA path (``models.ssm.ssm_scan_chunked``) materialises every chunk's
(b, L, c, n) decays, inputs and scan levels in HBM. Here the state never
leaves VMEM except at time-block boundaries:

  * grid = (batch, channel tiles, time blocks); time blocks are the minor,
    sequential axis, so the (n, ct) float32 state persists in VMEM scratch
    between them, as the WKV kernel keeps its state;
  * the state is laid out (n, ct): the 16 states on sublanes, channels on
    lanes. B and C come in transposed, (n, T) per block, and step t takes
    its column by a lane select and a lane sum (exact: one term is
    non-zero), so nothing is relaid out per step;
  * the forward kernel writes y, the final state and, for the backward
    pass, the state at the start of every time block;
  * the backward kernel sweeps the time blocks in reverse. In a block it
    recomputes the block's states from the saved boundary state into VMEM,
    then runs the reverse recurrence
        g_t = C_t dy_t + exp(dt_{t+1} A) g_{t+1}      (g past the end: dh_final)
    and accumulates d dt, dx, dA, dB and dC. The state is never stepped
    backwards by dividing by the decay (it underflows). dA is a per-batch
    partial, dB and dC per-channel-tile partials, summed by the caller.

All state and exp math is float32. Working set of one backward block at
T = 128, ct = 640: the block's 129 states (5.3 MB) and double-buffered
(T, ct) rows (about 3 MB), under the 32 MB limit asked for.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 32 * 2**20
UNROLL = 8  # time steps written out per loop iteration


def _steps(block: int, body, carry):
    """``body(i, carry)`` for i in [0, block), UNROLL steps written out per
    loop iteration, so that the work of one step that does not feed the
    state (decays, columns, y) overlaps the state's chain through others."""
    unroll = min(UNROLL, block)

    def group(g, carry):
        for j in range(unroll):
            carry = body(g * unroll + j, carry)
        return carry

    return jax.lax.fori_loop(0, block // unroll, group, carry)


def _column(m: jnp.ndarray, sel: jnp.ndarray) -> jnp.ndarray:
    """Column ``sel`` of an (n, T) block as (n, 1): a select and a lane sum."""
    return jnp.sum(jnp.where(sel, m, 0.0), axis=1, keepdims=True)


def _fwd_kernel(dt_ref, x_ref, a_ref, b_ref, c_ref, y_ref, hfin_ref, *rest, block: int, save: bool):
    if save:
        hb_ref, h_scr, v_scr = rest
    else:
        h_scr, v_scr = rest
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    if save:
        hb_ref[0, 0] = h_scr[...]
    v_scr[...] = dt_ref[0] * x_ref[0].astype(jnp.float32)  # (T, ct): dt * x
    a = a_ref[...]  # (n, ct)
    bm, cm = b_ref[0], c_ref[0]  # (n, T)
    lane = jax.lax.broadcasted_iota(jnp.int32, bm.shape, 1)

    def step(t, h):
        dt_t = dt_ref[0, pl.ds(t, 1), :]  # (1, ct)
        sel = lane == t
        h = jnp.exp(dt_t * a) * h + _column(bm, sel) * v_scr[pl.ds(t, 1), :]
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(_column(cm, sel) * h, axis=0, keepdims=True)
        return h

    h = _steps(block, step, h_scr[...])
    h_scr[...] = h

    @pl.when(it == pl.num_programs(2) - 1)
    def _emit():
        hfin_ref[0] = h


def _bwd_kernel(dt_ref, x_ref, a_ref, b_ref, c_ref, hb_ref, dy_ref, dhf_ref,
                ddt_ref, dx_ref, da_ref, db_ref, dc_ref,
                g_scr, hs_scr, x_scr, dx_scr, *, block: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        g_scr[...] = dhf_ref[0]
        da_ref[0] = jnp.zeros(da_ref.shape[1:], jnp.float32)

    x_scr[...] = x_ref[0].astype(jnp.float32)
    a = a_ref[...]  # (n, ct)
    bm, cm = b_ref[0], c_ref[0]  # (n, T)
    lane = jax.lax.broadcasted_iota(jnp.int32, bm.shape, 1)

    # the block's states: hs[t] is the state before step t, hs[T] after the last
    hs_scr[0] = hb_ref[0, 0]

    def forward(t, h):
        dt_t = dt_ref[0, pl.ds(t, 1), :]
        v_t = dt_t * x_scr[pl.ds(t, 1), :]
        h = jnp.exp(dt_t * a) * h + _column(bm, lane == t) * v_t
        hs_scr[t + 1] = h
        return h

    _steps(block, forward, hs_scr[0])

    def reverse(i, carry):
        gn, da, db, dc = carry  # gn: exp(dt_{t+1} A) g_{t+1}
        t = block - 1 - i
        dt_t = dt_ref[0, pl.ds(t, 1), :]  # (1, ct)
        x_t = x_scr[pl.ds(t, 1), :]
        dy_t = dy_ref[0, pl.ds(t, 1), :]
        sel = lane == t
        b_t, c_t = _column(bm, sel), _column(cm, sel)  # (n, 1)
        h_prev, h_t = hs_scr[t], hs_scr[t + 1]
        decay = jnp.exp(dt_t * a)
        g = c_t * dy_t + gn  # dL/dh_t
        w = g * decay * h_prev
        gb = jnp.sum(g * b_t, axis=0, keepdims=True)  # (1, ct)
        dx_scr[pl.ds(t, 1), :] = dt_t * gb
        ddt_ref[0, pl.ds(t, 1), :] = x_t * gb + jnp.sum(a * w, axis=0, keepdims=True)
        db = db + jnp.where(sel, jnp.sum(g * (dt_t * x_t), axis=1, keepdims=True), 0.0)
        dc = dc + jnp.where(sel, jnp.sum(h_t * dy_t, axis=1, keepdims=True), 0.0)
        return decay * g, da + dt_t * w, db, dc

    zeros = jnp.zeros(bm.shape, jnp.float32)
    gn, da, db, dc = _steps(block, reverse, (g_scr[...], da_ref[0], zeros, zeros))
    g_scr[...] = gn
    da_ref[0] = da
    db_ref[0, 0] = db
    dc_ref[0, 0] = dc
    dx_ref[0] = dx_scr[...].astype(dx_ref.dtype)


def _check(s: int, c: int, block: int, tile: int) -> None:
    if s % block or c % tile or block % min(UNROLL, block):
        raise ValueError(f"seq {s} and channels {c} must tile into blocks {block} "
                         f"(a multiple of {UNROLL} or less) and tiles {tile}")


def _params(interpret: bool):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT,
    )}


@functools.partial(jax.jit, static_argnames=("block", "tile", "save", "interpret"))
def scan_fwd(
    dt: jnp.ndarray,  # (b, s, c) f32
    x: jnp.ndarray,  # (b, s, c)
    a_t: jnp.ndarray,  # (n, c) f32: A transposed
    b_t: jnp.ndarray,  # (b, n, s) f32: B transposed
    c_t: jnp.ndarray,  # (b, n, s) f32: C transposed
    *,
    block: int,
    tile: int,
    save: bool,
    interpret: bool = False,
):
    """``(y (b, s, c) f32, h_final (b, n, c)[, h_blocks (b, s/block, n, c)])``:
    with ``save``, also the state at the start of every time block."""
    bsz, s, c = dt.shape
    _check(s, c, block, tile)
    n = a_t.shape[0]
    grid = (bsz, c // tile, s // block)
    rows = pl.BlockSpec((1, block, tile), lambda ib, ic, it: (ib, it, ic))
    cols = pl.BlockSpec((1, n, block), lambda ib, ic, it: (ib, 0, it))
    state = pl.BlockSpec((1, n, tile), lambda ib, ic, it: (ib, 0, ic))
    out_specs = [rows, state]
    out_shape = [jax.ShapeDtypeStruct((bsz, s, c), jnp.float32),
                 jax.ShapeDtypeStruct((bsz, n, c), jnp.float32)]
    if save:
        out_specs.append(pl.BlockSpec((1, 1, n, tile), lambda ib, ic, it: (ib, it, 0, ic)))
        out_shape.append(jax.ShapeDtypeStruct((bsz, s // block, n, c), jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block=block, save=save),
        grid=grid,
        in_specs=[rows, rows, pl.BlockSpec((n, tile), lambda ib, ic, it: (0, ic)), cols, cols],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32), pltpu.VMEM((block, tile), jnp.float32)],
        interpret=interpret,
        name="ssm_scan_fwd",
        **_params(interpret),
    )(dt, x, a_t, b_t, c_t)


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def scan_bwd(
    dt: jnp.ndarray,  # (b, s, c) f32
    x: jnp.ndarray,  # (b, s, c)
    a_t: jnp.ndarray,  # (n, c)
    b_t: jnp.ndarray,  # (b, n, s)
    c_t: jnp.ndarray,  # (b, n, s)
    h_blocks: jnp.ndarray,  # (b, s/block, n, c)
    dy: jnp.ndarray,  # (b, s, c) f32
    dh_final: jnp.ndarray,  # (b, n, c) f32
    *,
    block: int,
    tile: int,
    interpret: bool = False,
):
    """``(d dt (b, s, c) f32, dx (b, s, c) x.dtype, dA partials (b, n, c),
    dB and dC partials (b, c/tile, n, s))``."""
    bsz, s, c = dt.shape
    _check(s, c, block, tile)
    n = a_t.shape[0]
    nt = s // block
    grid = (bsz, c // tile, nt)
    rows = pl.BlockSpec((1, block, tile), lambda ib, ic, it: (ib, nt - 1 - it, ic))
    cols = pl.BlockSpec((1, n, block), lambda ib, ic, it: (ib, 0, nt - 1 - it))
    state = pl.BlockSpec((1, n, tile), lambda ib, ic, it: (ib, 0, ic))
    partial_cols = pl.BlockSpec((1, 1, n, block), lambda ib, ic, it: (ib, ic, 0, nt - 1 - it))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block=block),
        grid=grid,
        in_specs=[
            rows, rows, pl.BlockSpec((n, tile), lambda ib, ic, it: (0, ic)), cols, cols,
            pl.BlockSpec((1, 1, n, tile), lambda ib, ic, it: (ib, nt - 1 - it, 0, ic)),
            rows, state,
        ],
        out_specs=[rows, rows, state, partial_cols, partial_cols],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, c), jnp.float32),
            jax.ShapeDtypeStruct((bsz, s, c), x.dtype),
            jax.ShapeDtypeStruct((bsz, n, c), jnp.float32),
            jax.ShapeDtypeStruct((bsz, c // tile, n, s), jnp.float32),
            jax.ShapeDtypeStruct((bsz, c // tile, n, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, tile), jnp.float32),  # g carried across blocks
            pltpu.VMEM((block + 1, n, tile), jnp.float32),  # the block's states
            pltpu.VMEM((block, tile), jnp.float32),  # x in float32
            pltpu.VMEM((block, tile), jnp.float32),  # dx rows
        ],
        interpret=interpret,
        name="ssm_scan_bwd",
        **_params(interpret),
    )(dt, x, a_t, b_t, c_t, h_blocks, dy, dh_final)
