"""Hymba reference block: attention heads and a Mamba branch side by side
on the same normed input, averaged, then a SwiGLU MLP (arXiv:2411.13676).

It follows the configuration's ``assumed`` list: every layer has the
sliding window, no meta tokens, no cross-layer KV sharing, the branches are
averaged without per-branch norms, and the SSM has no inner layernorms.
Rotary embeddings rotate the two halves of each head. The SSM recurrence is
run one position at a time:

    h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t * B_t,   y_t = <h_t, C_t> + D x_t
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import Dot, rms


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    return dict(
        d=d, L=int(cfg["num_hidden_layers"]), H=int(cfg["num_attention_heads"]),
        KV=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        f=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
        n=int(cfg["mamba_d_state"]), di=int(cfg["mamba_expand"]) * d,
        conv=int(cfg["mamba_d_conv"]), r=int(cfg["mamba_dt_rank"]),
    )


def spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The leaves the program stores: path -> (shape, dtype)."""
    k = dims(cfg)
    L, d, di, n = k["L"], k["d"], k["di"], k["n"]
    pd = cfg["param_dtype"]
    s = {
        "embed/table": ((k["V"], d), pd),
        "final_norm/scale": ((d,), pd),
        "layers/attn_norm/scale": ((L, d), pd),
        "layers/attn/wq": ((L, d, k["H"] * k["hd"]), pd),
        "layers/attn/wk": ((L, d, k["KV"] * k["hd"]), pd),
        "layers/attn/wv": ((L, d, k["KV"] * k["hd"]), pd),
        "layers/attn/wo": ((L, k["H"] * k["hd"], d), pd),
        "layers/ssm/in_proj": ((L, d, 2 * di), pd),
        "layers/ssm/conv_w": ((L, k["conv"], di), pd),
        "layers/ssm/conv_b": ((L, di), pd),
        "layers/ssm/x_proj": ((L, di, k["r"] + 2 * n), pd),
        "layers/ssm/dt_proj": ((L, k["r"], di), pd),
        "layers/ssm/dt_bias": ((L, di), "float32"),
        "layers/ssm/a_log": ((L, di, n), "float32"),
        "layers/ssm/d_skip": ((L, di), "float32"),
        "layers/ssm/out_proj": ((L, di, d), pd),
        "layers/mlp_norm/scale": ((L, d), pd),
        "layers/mlp/w_gate": ((L, d, k["f"]), pd),
        "layers/mlp/w_up": ((L, d, k["f"]), pd),
        "layers/mlp/w_down": ((L, k["f"], d), pd),
    }
    if not cfg.get("tie_word_embeddings"):
        s["lm_head/table"] = ((k["V"], d), pd)
    return s


def _rope(x: jax.Array, theta: float) -> jax.Array:
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (s, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, window: int, dot: Dot) -> jax.Array:
    b, s, h, hd = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)  # query head j reads kv head j // rep
    v = jnp.repeat(v, rep, axis=2)
    scores = dot("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    mask = (ki <= qi) & (ki > qi - window)
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return dot("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * hd)


def _ssm(p, x, k, dot: Dot) -> jax.Array:
    xz = dot("bsd,dc->bsc", x, p["layers/ssm/in_proj"])
    xs, z = xz[..., : k["di"]], xz[..., k["di"]:]
    w = p["layers/ssm/conv_w"]  # (conv, di): tap i reads x_{t-(conv-1)+i}
    xp = jnp.pad(xs, ((0, 0), (k["conv"] - 1, 0), (0, 0)))
    s = xs.shape[1]
    xs = sum(xp[:, i : i + s] * w[i] for i in range(k["conv"])) + p["layers/ssm/conv_b"]
    xs = jax.nn.silu(xs)
    proj = dot("bsc,cp->bsp", xs, p["layers/ssm/x_proj"])
    r, n = k["r"], k["n"]
    dt_in, bm, cm = proj[..., :r], proj[..., r : r + n], proj[..., r + n :]
    dt = jax.nn.softplus(dot("bsr,rc->bsc", dt_in, p["layers/ssm/dt_proj"]) + p["layers/ssm/dt_bias"])
    a = -jnp.exp(p["layers/ssm/a_log"])  # (di, n)

    def step(h, inp):
        dt_t, b_t, c_t, x_t = inp
        h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    h0 = jnp.zeros((xs.shape[0], k["di"], n), jnp.float32)
    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, bm, cm, xs))
    _, ys = jax.lax.scan(step, h0, seq)
    y = jnp.moveaxis(ys, 0, 1) + p["layers/ssm/d_skip"] * xs
    y = y * jax.nn.silu(z)
    return dot("bsc,cd->bsd", y, p["layers/ssm/out_proj"])


def layer(p: Dict[str, jax.Array], x: jax.Array, cfg: Dict[str, Any], dot: Dot) -> jax.Array:
    k = dims(cfg)
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    theta = float(cfg["rope_theta"])
    b, s, _ = x.shape
    h = rms(x, p["layers/attn_norm/scale"], eps)
    q = dot("bsd,de->bse", h, p["layers/attn/wq"]).reshape(b, s, k["H"], k["hd"])
    kk = dot("bsd,de->bse", h, p["layers/attn/wk"]).reshape(b, s, k["KV"], k["hd"])
    v = dot("bsd,de->bse", h, p["layers/attn/wv"]).reshape(b, s, k["KV"], k["hd"])
    att = _attention(_rope(q, theta), _rope(kk, theta), v, int(cfg["sliding_window"]), dot)
    att = dot("bse,ed->bsd", att, p["layers/attn/wo"])
    x = x + 0.5 * (att + _ssm(p, h, k, dot))
    h = rms(x, p["layers/mlp_norm/scale"], eps)
    g = jax.nn.silu(dot("bsd,df->bsf", h, p["layers/mlp/w_gate"]))
    u = dot("bsd,df->bsf", h, p["layers/mlp/w_up"])
    return x + dot("bsf,fd->bsd", g * u, p["layers/mlp/w_down"])
