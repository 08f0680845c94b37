"""Seeded weights, made on the device, for the program and the reference alike.

Every leaf is named by its path (``layers/attn/wq``) and drawn from a key
that depends only on the run's seed, that path and, for a leaf of the layer
stack, the layer's index. So the program's stacked tree and the reference's
list of per-layer trees hold the same numbers, and neither takes anything
from the other. A leaf's distribution follows its name (norm scales are
ones, token-shift mixes 0.5, and so on); a matrix is normal with standard
deviation 1/sqrt(fan-in). The values are rounded to the leaf's stored dtype
before the reference upcasts them, so both sides start from equal numbers.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# leaf name -> how it is drawn: ("const", value) or ("normal", std)
_RULES: Dict[str, Tuple[str, float]] = {
    "scale": ("const", 1.0),
    "ln_x": ("const", 1.0),
    "d_skip": ("const", 1.0),
    "conv_b": ("const", 0.0),
    "mu_x": ("const", 0.5),
    "mu": ("const", 0.5),
    "mu_k": ("const", 0.5),
    "mu_r": ("const", 0.5),
    "decay_base": ("const", -6.0),
    "dt_bias": ("const", math.log(math.expm1(0.01))),  # softplus^-1(0.01)
    "conv_w": ("normal", 0.1),
    "mix_w2": ("normal", 0.02),
    "bonus": ("normal", 0.02),
    "table": ("normal", 0.02),
}

Spec = Dict[str, Tuple[Tuple[int, ...], str]]  # path -> (shape, dtype)


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size (the driver's exceed 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(key, (seed // 2**32) % 2**32)


def _path_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()))


def draw(key: jax.Array, path: str, shape: Tuple[int, ...], dtype: str) -> jax.Array:
    """One leaf (or one layer's slice of a stacked leaf), rounded to ``dtype``."""
    name = path.rsplit("/", 1)[-1]
    if name == "a_log":  # S4D-real init: A = -(1..n) for every channel
        n = shape[-1]
        val = jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), shape)
    elif name in _RULES:
        kind, v = _RULES[name]
        if kind == "const":
            val = jnp.full(shape, v, jnp.float32)
        else:
            val = jax.random.normal(key, shape, jnp.float32) * v
    elif len(shape) >= 2:
        val = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
    else:
        raise KeyError(f"no rule draws the leaf {path!r} of shape {shape}")
    return val.astype(jnp.dtype(dtype))


def layer_spec(spec: Spec, n_layers: int) -> Spec:
    """The per-layer slice of every ``layers/...`` leaf."""
    out = {}
    for path, (shape, dtype) in spec.items():
        if path.startswith("layers/"):
            if shape[0] != n_layers:
                raise ValueError(f"{path}: leading axis {shape[0]} != {n_layers} layers")
            out[path] = (tuple(shape[1:]), dtype)
    return out


def make_stacked(seed: int, spec: Spec) -> Dict[str, jax.Array]:
    """The program's flat tree ``{path: array}``, stacked over layers, in one
    jitted call on the default device."""

    def build(key):
        out = {}
        for path, (shape, dtype) in sorted(spec.items()):
            pk = _path_key(key, path)
            if path.startswith("layers/"):
                keys = jax.vmap(lambda i: jax.random.fold_in(pk, i))(jnp.arange(shape[0]))
                out[path] = jax.vmap(lambda k: draw(k, path, tuple(shape[1:]), dtype))(keys)
            else:
                out[path] = draw(pk, path, tuple(shape), dtype)
        return out

    return jax.jit(build)(base_key(seed))


def make_layer(seed: int, spec: Spec, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves (``spec`` from :func:`layer_spec`), upcast to
    float32. Compiled once for every layer index."""
    return _make_layer_jit(tuple(sorted(spec.items())))(base_key(seed), jnp.int32(layer))


def make_top(seed: int, spec: Spec) -> Dict[str, jax.Array]:
    """The leaves outside the layer stack, upcast to float32."""
    top = tuple(sorted((p, v) for p, v in spec.items() if not p.startswith("layers/")))
    return _make_top_jit(top)(base_key(seed))


@functools.lru_cache(maxsize=None)
def _make_top_jit(items):
    def build(key):
        return {p: draw(_path_key(key, p), p, tuple(s), d).astype(jnp.float32) for p, (s, d) in items}

    return jax.jit(build)


@functools.lru_cache(maxsize=None)
def _make_layer_jit(items):
    def build(key, i):
        return {
            p: draw(jax.random.fold_in(_path_key(key, p), i), p, tuple(s), d).astype(jnp.float32)
            for p, (s, d) in items
        }

    return jax.jit(build)


def flatten(tree: Any) -> Dict[str, Any]:
    """A nested dict of leaves -> ``{"a/b/c": leaf}``."""
    out: Dict[str, Any] = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, f"{prefix}/{k}" if prefix else k)
        else:
            out[prefix] = node

    walk(tree, "")
    return out


def unflatten(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def spec_of(tree: Any) -> Spec:
    """``{path: (shape, dtype)}`` of a tree of arrays or shape structs."""
    return {
        p: (tuple(int(d) for d in np.shape(x)), jnp.dtype(x.dtype).name)
        for p, x in flatten(tree).items()
    }


def spec_diff(want: Spec, got: Spec) -> List[str]:
    """How two specs differ (empty when equal)."""
    diff = [f"missing {p}" for p in sorted(set(want) - set(got))]
    diff += [f"unexpected {p}" for p in sorted(set(got) - set(want))]
    diff += [
        f"{p}: {got[p]} != {want[p]}" for p in sorted(set(want) & set(got)) if want[p] != got[p]
    ]
    return diff
