"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses here what the chip would refuse: a block not
aligned to the tiling, a primitive Mosaic cannot lower, a program larger
than the device. These tests hold the full-width hymba-1.5b steps of
``launch/serve.py``, with the selective-scan kernel selected as on the chip,
and the four Pallas kernels, at the shapes of the configurations that use
them, to that compiler. They run nothing.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.profiles import profile_executable
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.fused_rmsnorm.ops import rmsnorm
from repro.kernels.rwkv_scan.ops import wkv6
from repro.kernels.ssm_scan import ops as ssm_scan_ops
from repro.launch.serve import serving_model, service_fns, trainer_fns

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described ``v5e:2x2`` host, with JAX's persistent
    compilation cache off: entries compiled for a described chip cannot be
    read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    had_log_dir = "TPU_LOG_DIR" in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if not had_log_dir:
            os.environ.pop("TPU_LOG_DIR", None)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def _compile(fn, sharding, *args):
    return jax.jit(fn).lower(*_shapes(args, sharding)).compile()


@pytest.mark.parametrize("job", ["service", "trainer"])
def test_hymba_full_width_step_fits_one_chip(one_chip, monkeypatch, job):
    # the program asks the default backend, the CPU here, whether it runs on a TPU
    monkeypatch.setattr(ssm_scan_ops, "on_tpu", lambda: True)
    model = serving_model("hymba-1.5b", smoke=False)
    if job == "service":
        _, step, data_fn = service_fns(model)
    else:
        step, data_fn = trainer_fns(model)
    batch = jax.eval_shape(data_fn, 0)
    compiled = _compile(step, one_chip, model.abstract_params(), batch)
    assert "ssm_scan_fwd" in compiled.as_text()
    profile = profile_executable(compiled)
    # P alone is the 1.61 B float32 parameters
    assert profile.persistent > 6 * 10**9
    assert profile.total <= V5E_HBM, profile


@pytest.mark.parametrize(
    "hq,hkv,d,window",
    [(25, 5, 64, 1024), (32, 8, 128, None)],
    ids=["hymba-1.5b", "qwen3-8b"],
)
def test_flash_attention_compiles(one_chip, hq, hkv, d, window):
    b, s = 1, 4096
    q = jax.ShapeDtypeStruct((b, s, hq, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True, window=window)
    assert "tpu_custom_call" in _compile(fn, one_chip, q, kv, kv).as_text()


def test_fused_rmsnorm_compiles(one_chip):
    x = jax.ShapeDtypeStruct((4096, 1600), jnp.bfloat16)
    scale = jax.ShapeDtypeStruct((1600,), jnp.float32)
    assert "tpu_custom_call" in _compile(rmsnorm, one_chip, x, scale).as_text()


def test_wkv6_compiles_at_rwkv6_7b_heads(one_chip):
    b, s, h, d = 1, 512, 64, 64
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.float32)
    u = jax.ShapeDtypeStruct((h, d), jnp.float32)
    fn = lambda r, k, v, w, u: wkv6(r, k, v, w, u, chunk=64)
    assert "tpu_custom_call" in _compile(fn, one_chip, x, x, x, x, u).as_text()


def test_ssm_scan_and_its_vjp_compile_at_hymba_trainer_shape(one_chip):
    b, s, c, n = 4, 512, 3200, 16
    rows = jax.ShapeDtypeStruct((b, s, c), jnp.float32)
    a = jax.ShapeDtypeStruct((c, n), jnp.float32)
    bc = jax.ShapeDtypeStruct((b, s, n), jnp.float32)
    x = jax.ShapeDtypeStruct((b, s, c), jnp.bfloat16)

    def loss(*args):
        y, h = ssm_scan_ops.ssm_scan(*args)
        return jnp.sum(y) + jnp.sum(h)

    assert "tpu_custom_call" in _compile(ssm_scan_ops.ssm_scan, one_chip, rows, a, bc, bc, x).as_text()
    text = _compile(jax.grad(loss, argnums=range(5)), one_chip, rows, a, bc, bc, x).as_text()
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
