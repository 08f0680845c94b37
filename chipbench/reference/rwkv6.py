"""RWKV-6 "Finch" reference block: a time-mix with data-dependent token
shift and decay, then a channel-mix (arXiv:2404.05892).

It follows the configuration's ``assumed`` list: RMSNorm before each half
(the published model uses LayerNorm), ln_x a per-head norm without bias,
LoRA widths 32 (token shift, five streams r, k, v, w, g) and 64 (decay).
The WKV recurrence is run one position at a time, per head:

    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t),   S_t = diag(w_t) S_{t-1} + k_t^T v_t
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import Dot, rms, shift

N_MIX, LORA_MIX, LORA_DECAY = 5, 32, 64
LN_X_EPS = 64e-5


def spec(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The leaves the program stores: path -> (shape, dtype)."""
    L, d, f, V = (int(cfg[k]) for k in
                  ("num_hidden_layers", "hidden_size", "intermediate_size", "vocab_size"))
    hd = int(cfg["head_size"])
    pd, f32 = cfg["param_dtype"], "float32"
    s = {
        "embed/table": ((V, d), pd),
        "final_norm/scale": ((d,), pd),
        "layers/norm1/scale": ((L, d), pd),
        "layers/norm2/scale": ((L, d), pd),
        "layers/tmix/mu_x": ((L, d), f32),
        "layers/tmix/mu": ((L, N_MIX, d), f32),
        "layers/tmix/mix_w1": ((L, d, N_MIX * LORA_MIX), f32),
        "layers/tmix/mix_w2": ((L, N_MIX, LORA_MIX, d), f32),
        "layers/tmix/decay_base": ((L, d), f32),
        "layers/tmix/decay_w1": ((L, d, LORA_DECAY), f32),
        "layers/tmix/decay_w2": ((L, LORA_DECAY, d), f32),
        "layers/tmix/bonus": ((L, d // hd, hd), f32),
        "layers/tmix/ln_x": ((L, d), f32),
        "layers/cmix/mu_k": ((L, d), f32),
        "layers/cmix/mu_r": ((L, d), f32),
        "layers/cmix/wk": ((L, d, f), pd),
        "layers/cmix/wv": ((L, f, d), pd),
        "layers/cmix/wr": ((L, d, d), pd),
    }
    for w in ("wr", "wk", "wv", "wg", "wo"):
        s[f"layers/tmix/{w}"] = ((L, d, d), pd)
    if not cfg.get("tie_word_embeddings"):
        s["lm_head/table"] = ((V, d), pd)
    return s


def _wkv(r, k, v, w, u, block: int = 32):
    """(b, s, h, hd) each; u (h, hd). Sequential over positions; the
    backward pass keeps the state only every ``block`` positions and
    recomputes between them, so a layer's VJP fits beside the weights."""
    b, s, h, hd = r.shape

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp  # (b, h, hd)
        kv = k_t[..., :, None] * v_t[..., None, :]
        o = jnp.sum(r_t[..., :, None] * (S + u[..., :, None] * kv), axis=-2)
        return w_t[..., :, None] * S + kv, o

    @jax.checkpoint
    def run_block(S, inp):
        return jax.lax.scan(step, S, inp)

    if s % block:
        block = s
    seq = tuple(jnp.moveaxis(t, 1, 0).reshape(s // block, block, b, h, hd) for t in (r, k, v, w))
    S0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    _, os = jax.lax.scan(run_block, S0, seq)
    return jnp.moveaxis(os.reshape(s, b, h, hd), 0, 1)


def _tmix(p, x, cfg, dot: Dot):
    b, s, d = x.shape
    hd = int(cfg["head_size"])
    h = d // hd
    xx = shift(x) - x
    base = x + xx * p["layers/tmix/mu_x"]
    lora = jnp.tanh(dot("bsd,dm->bsm", base, p["layers/tmix/mix_w1"]))
    lora = lora.reshape(b, s, N_MIX, LORA_MIX)
    delta = dot("bsnm,nmd->bsnd", lora, p["layers/tmix/mix_w2"])
    mixed = x[:, :, None] + xx[:, :, None] * (p["layers/tmix/mu"] + delta)
    xr, xk, xv, xw, xg = (mixed[:, :, i] for i in range(N_MIX))
    r = dot("bsd,de->bse", xr, p["layers/tmix/wr"])
    k = dot("bsd,de->bse", xk, p["layers/tmix/wk"])
    v = dot("bsd,de->bse", xv, p["layers/tmix/wv"])
    g = jax.nn.silu(dot("bsd,de->bse", xg, p["layers/tmix/wg"]))
    dec = dot("bsm,md->bsd", jnp.tanh(dot("bsd,dm->bsm", xw, p["layers/tmix/decay_w1"])),
              p["layers/tmix/decay_w2"])
    w = jnp.exp(-jnp.exp(p["layers/tmix/decay_base"] + dec))
    heads = lambda t: t.reshape(b, s, h, hd)
    o = _wkv(heads(r), heads(k), heads(v), heads(w), p["layers/tmix/bonus"])
    mean = jnp.mean(o, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(o - mean), axis=-1, keepdims=True)
    o = ((o - mean) * jax.lax.rsqrt(var + LN_X_EPS)).reshape(b, s, d) * p["layers/tmix/ln_x"]
    return dot("bsd,de->bse", o * g, p["layers/tmix/wo"])


def _cmix(p, x, dot: Dot):
    xx = shift(x) - x
    xk = x + xx * p["layers/cmix/mu_k"]
    xr = x + xx * p["layers/cmix/mu_r"]
    k = jnp.square(jax.nn.relu(dot("bsd,df->bsf", xk, p["layers/cmix/wk"])))
    kv = dot("bsf,fd->bsd", k, p["layers/cmix/wv"])
    return jax.nn.sigmoid(dot("bsd,de->bse", xr, p["layers/cmix/wr"])) * kv


def layer(p: Dict[str, jax.Array], x: jax.Array, cfg: Dict[str, Any], dot: Dot) -> jax.Array:
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    x = x + _tmix(p, rms(x, p["layers/norm1/scale"], eps), cfg, dot)
    return x + _cmix(p, rms(x, p["layers/norm2/scale"], eps), dot)
