"""WKV6 (RWKV-6 time-mix) as a Pallas TPU kernel.

TPU adaptation of the CUDA wkv6 kernel (RWKV-LM) / fla's chunked Triton
form: instead of one-thread-per-channel sequential CUDA scans, the sequence
is processed in VMEM-resident chunks —
  * grid = (batch, heads, n_chunks); chunks are the minor (sequential) axis
    so the (dk, dv) state matrix persists in VMEM scratch between chunks;
  * within a chunk of length L the recurrence is closed-form:
        o  = (r * e^{cum_prev}) @ S
           + [(r_t . k_s e^{cum_prev_t - cum_s})]_{s<t} @ v + (r.u*k) v
        S' = e^{cum_L} * S + (k * e^{cum_L - cum})^T @ v
    which is two MXU matmuls plus an (L, L, dk) masked-decay contraction —
    exactly the math of models.rwkv.wkv_chunked, tiled for VMEM;
  * all state math in fp32 (the decay products underflow bf16 quickly).

Block shapes: r/k/v/w tiles are (1, 1, L, d); with L=64, dk=dv=64 the
working set is ~6 VMEM slabs of 16 KB + one (L, L, dk) fp32 intermediate
(1 MB) — comfortably inside the ~16 MB VMEM budget.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sfin_ref, state_scr, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0, 0].astype(jnp.float32)  # (L, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)  # (L, dv)
    w = w_ref[0, 0].astype(jnp.float32)  # (L, dk), in (0, 1)
    u = u_ref[0].astype(jnp.float32)  # (1, dk)
    L = r.shape[0]

    tpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    spos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    logw = jnp.log(w)
    # cum[t] = sum_{s<=t} log w_s as a lower-triangular matmul: Mosaic has
    # no cumsum lowering, and fp32 contraction keeps the decays exact
    cum = jax.lax.dot_general(
        (tpos >= spos).astype(jnp.float32), logw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (L, dk)
    cum_prev = cum - logw  # cum[t-1], zero at t=0

    state = state_scr[...]  # (dk, dv)
    # inter-chunk: queries decayed back to chunk start
    r_dec = r * jnp.exp(cum_prev)
    o_inter = jax.lax.dot_general(
        r_dec, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, dv)
    # intra-chunk: pairwise strictly-lower-triangular scores with decay
    decay = jnp.exp(cum_prev[:, None, :] - cum[None, :, :])  # (t, s, dk)
    mask = (tpos > spos).astype(jnp.float32)
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=-1) * mask  # (L, L)
    o_intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # diagonal bonus
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # (L, 1)
    o = o_inter + o_intra + diag * v
    o_ref[0, 0] = o.astype(o_ref.dtype)

    # state update to end of chunk
    cum_last = jnp.sum(logw, axis=0, keepdims=True)  # (1, dk)
    k_dec = k * jnp.exp(cum_last - cum)  # (L, dk)
    # e^{cum_L} along the state's rows, as a (dk, dv) matrix: a transposed
    # contraction, since a (1, dk) -> (dk, 1) relayout does not lower
    row_decay = jnp.exp(jax.lax.dot_general(
        logw, jnp.ones((L, v.shape[1]), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    ))
    state_scr[...] = row_decay * state + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ic == nc - 1)
    def _emit_state():
        sfin_ref[0, 0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_bhsd(
    r: jnp.ndarray,  # (b, h, s, dk) fp32
    k: jnp.ndarray,
    v: jnp.ndarray,  # (b, h, s, dv)
    w: jnp.ndarray,  # (b, h, s, dk)
    u: jnp.ndarray,  # (h, dk)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, h, s, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"seq {s} must divide chunk {chunk}")
    grid = (b, h, s // chunk)
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    o, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dv), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, dk), lambda ib, ih, ic: (ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dv), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, dk))
    return o, s_final
