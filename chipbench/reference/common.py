"""Plain float32 reference: the pieces every architecture shares, and the
drivers that run a model layer by layer so that it fits beside nothing else.

Nothing here imports the program. A model file (``hymba.py``, ``rwkv6.py``)
gives ``spec(cfg)``, the leaves the program stores with their shapes and
dtypes, and ``layer(p, x, cfg, dot)``, one block of the residual stack on a
dict of that layer's leaves. Matrix products go through ``dot``: the
reference's is float32 at ``HIGHEST`` precision; the control's rounds both
operands, and backward each cotangent, to float8 (e4m3, one scale per
tensor) first.

Departure common to all models: the SGD update is computed in float32 and
the result is stored rounded to the leaf's dtype, as the configuration
stores its parameters (bfloat16); a float32 leaf stays float32.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0

Dot = Callable[..., jax.Array]


def dot_f32(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32), precision=HIGHEST)


def _round_f8(t: jax.Array) -> jax.Array:
    t = t.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / F8_MAX
    return (t / scale).astype(F8).astype(jnp.float32) * scale


@jax.custom_vjp
def _to_f8(t: jax.Array) -> jax.Array:
    return _round_f8(t)


def _to_f8_fwd(t):
    return _round_f8(t), None


def _to_f8_bwd(_, g):
    return (_round_f8(g),)  # the cotangent gets a scale of its own


_to_f8.defvjp(_to_f8_fwd, _to_f8_bwd)


def dot_f8(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """The control: both operands, and in the backward pass each cotangent,
    rounded to float8 e4m3 with a per-tensor scale, then multiplied
    exactly."""
    return jnp.einsum(eq, _to_f8(a), _to_f8(b), precision=HIGHEST)


def rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def shift(x: jax.Array) -> jax.Array:
    """x_{t-1} along the sequence, zeros before the first position."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


class Params:
    """float32 copies of the seeded weights: the leaves outside the stack in
    ``top`` and one dict per layer in ``layers``."""

    def __init__(self, seed: int, spec: weights.Spec, n_layers: int) -> None:
        self.seed = seed
        self.spec = spec
        self.lspec = weights.layer_spec(spec, n_layers)
        self.top = weights.make_top(seed, spec)
        self.layers = [weights.make_layer(seed, self.lspec, i) for i in range(n_layers)]

    def initial_layer(self, i: int) -> Dict[str, jax.Array]:
        return weights.make_layer(self.seed, self.lspec, i)

    def initial_top(self) -> Dict[str, jax.Array]:
        return weights.make_top(self.seed, self.spec)

    def change_sq(self) -> Dict[str, float]:
        """Squared norm, per leaf of the program's tree, of the change since
        the seeded start."""
        out: Dict[str, float] = {}
        top0 = self.initial_top()
        for p, v in self.top.items():
            out[p] = float(_sq(v - top0[p]))
        for i, lp in enumerate(self.layers):
            l0 = self.initial_layer(i)
            for p, v in lp.items():
                out[p] = out.get(p, 0.0) + float(_sq(v - l0[p]))
        return out


@jax.jit
def _sq(t: jax.Array) -> jax.Array:
    return jnp.sum(jnp.square(t.astype(jnp.float32)))


def _store(p: jax.Array, stored: str) -> jax.Array:
    return p.astype(stored).astype(jnp.float32) if stored != "float32" else p


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------


class Model:
    """One architecture's reference: embedding, the layer stack run one
    layer per call, the final norm and the head."""

    def __init__(self, module: Any, cfg: Dict[str, Any], dot: Dot) -> None:
        self.m = module
        self.cfg = cfg
        self.dot = dot
        self.n_layers = int(cfg["num_hidden_layers"])
        self.head_path = "embed/table" if cfg.get("tie_word_embeddings") else "lm_head/table"
        self.eps = float(cfg.get("rms_norm_eps", 1e-6))
        layer = lambda p, x: module.layer(p, x, cfg, dot)
        self._fwd = jax.jit(layer)
        self._bwd = jax.jit(
            lambda p, x, dx, lr, stored: _layer_bwd(layer, p, x, dx, lr, stored),
            static_argnums=4, donate_argnums=0,
        )
        self._head_logits = jax.jit(self._logits_last)
        self._head_loss = jax.jit(jax.value_and_grad(self._loss, argnums=(0, 1)))

    def spec(self) -> weights.Spec:
        return self.m.spec(self.cfg)

    def params(self, seed: int) -> Params:
        return Params(seed, self.spec(), self.n_layers)

    # -- pieces -----------------------------------------------------------

    def _final(self, top: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
        return rms(x, top["final_norm/scale"], self.eps)

    def _logits_last(self, top, x):
        return self.dot("bd,vd->bv", self._final(top, x)[:, -1], top[self.head_path])

    def _loss(self, top, x, labels):
        logits = self.dot("bsd,vd->bsv", self._final(top, x), top[self.head_path])
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    def _stack(self, P: Params, tokens: jax.Array) -> List[jax.Array]:
        xs = [jnp.take(P.top["embed/table"], tokens, axis=0)]
        for lp in P.layers:
            xs.append(self._fwd(lp, xs[-1]))
        return xs

    # -- serving ----------------------------------------------------------

    def last_logits(self, P: Params, tokens: np.ndarray) -> np.ndarray:
        """float32 logits at the last position of each row of ``tokens``."""
        with jax.default_matmul_precision("highest"):
            xs = self._stack(P, jnp.asarray(tokens))
            return np.asarray(self._head_logits(P.top, xs[-1]))

    # -- training ---------------------------------------------------------

    def sgd_step(self, P: Params, tokens: np.ndarray, labels: np.ndarray, lr: float) -> float:
        """One SGD step, layer by layer: forward keeping each layer's input,
        then each layer's VJP from the top down, updating that layer's
        leaves as soon as its gradient is known. Returns the loss."""
        with jax.default_matmul_precision("highest"):
            tokens = jnp.asarray(tokens)
            xs = self._stack(P, tokens)
            head = {k: P.top[k] for k in ("final_norm/scale", self.head_path)}
            loss, (g_head, dx) = self._head_loss(head, xs[-1], jnp.asarray(labels))
            lr32 = jnp.float32(lr)
            for i in reversed(range(self.n_layers)):
                stored = tuple(sorted((p, d) for p, (_, d) in P.lspec.items()))
                P.layers[i], dx = self._bwd(P.layers[i], xs[i], dx, lr32, stored)
            g_top = dict(g_head)
            g_embed = jnp.zeros_like(P.top["embed/table"]).at[tokens].add(dx)
            g_top["embed/table"] = g_top.get("embed/table", 0.0) + g_embed
            for k, g in g_top.items():
                P.top[k] = _store(P.top[k] - lr32 * g, P.spec[k][1])
            return float(loss)


def _layer_bwd(layer, p, x, dx, lr, stored):
    _, vjp = jax.vjp(layer, p, x)
    g, dx_in = vjp(dx)
    dtypes = dict(stored)
    new = {k: _store(p[k] - lr * g[k], dtypes[k]) for k in p}
    return new, dx_in
