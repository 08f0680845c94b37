"""Selective-scan Pallas kernels (interpret mode) against the sequential
oracle: outputs, final state and the custom VJP's gradients; where the
kernel may run, and the trace counters that record the path each
``ssm_apply`` took."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import GB, SalusExecutor, VirtualDevice, get_policy
from repro.core.spans import count, counting
from repro.dist import api as dist_api
from repro.kernels.ssm_scan import ops
from repro.kernels.ssm_scan.kernel import scan_bwd, scan_fwd
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.models import ssm

CASES = [
    # (b, s, c, n)
    (2, 64, 128, 16),  # one time block, s < 128
    (1, 256, 128, 16),  # two time blocks
    (1, 384, 256, 8),  # three time blocks, a 256-lane tile
    (3, 40, 128, 4),  # one short block
]


def _inputs(case, regime, seed=0):
    b, s, c, n = case
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    shift = -4.0 if regime == "slow" else 0.5  # dt about 0.02 or 1: decay near 1 or fast
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, c)) + shift)
    a = -jnp.exp(jax.random.normal(ks[1], (c, n)) * 0.5)
    b_in = jax.random.normal(ks[2], (b, s, n))
    c_in = jax.random.normal(ks[3], (b, s, n))
    x = jax.random.normal(ks[4], (b, s, c)).astype(jnp.bfloat16)
    wy = jax.random.normal(ks[5], (b, s, c))
    wh = jax.random.normal(ks[6], (b, c, n))
    return (dt, a, b_in, c_in, x), (wy, wh)


def _loss(scan, w):
    wy, wh = w

    def loss(*args):
        y, h = scan(*args)
        return jnp.sum(y * wy) + jnp.sum(h * wh)

    return loss


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


def _kernel(*args):
    return ops.ssm_scan(*args, True)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("regime", ["slow", "fast"])
def test_ssm_scan_vs_ref(case, regime):
    args, w = _inputs(case, regime)
    y, h = jax.jit(_kernel)(*args)
    y_ref, h_ref = ssm_scan_ref(*args)
    _close(y, y_ref)
    _close(h, h_ref)
    grads = jax.jit(jax.grad(_loss(_kernel, w), argnums=range(5)))(*args)
    grads_ref = jax.grad(_loss(ssm_scan_ref, w), argnums=range(5))(*args)
    for name, g, g_ref in zip(("dt", "a", "b", "c", "x"), grads, grads_ref):
        assert g.shape == g_ref.shape and g.dtype == g_ref.dtype, name
        _close(g, g_ref, tol=1e-2 if name == "x" else 2e-5)  # dx is bfloat16


def test_short_time_blocks_chain_state():
    """Blocks of 32 steps carry the state across block boundaries, forward
    and backward, as one block of the whole sequence does."""
    (dt, a, b_in, c_in, x), (wy, wh) = _inputs((2, 128, 128, 16), "slow", seed=3)
    a_t, b_t, c_t = a.T, jnp.swapaxes(b_in, 1, 2), jnp.swapaxes(c_in, 1, 2)
    outs = {}
    for block in (32, 128):
        y, h, hb = scan_fwd(dt, x, a_t, b_t, c_t, block=block, tile=128, save=True, interpret=True)
        grads = scan_bwd(dt, x, a_t, b_t, c_t, hb, wy, jnp.swapaxes(wh, 1, 2),
                         block=block, tile=128, interpret=True)
        outs[block] = (y, h) + tuple(grads[:3]) + tuple(g.sum(1) for g in grads[3:])
    assert hb.shape == (2, 1, 16, 128)
    for got, want in zip(outs[32], outs[128]):
        _close(got, want)


@pytest.mark.parametrize(
    "s,c,on_tpu,sharded,fits",
    [
        (512, 3200, True, False, True),  # hymba's trainer
        (256, 3200, True, False, True),  # hymba's prefill
        (40, 128, True, False, True),
        (512, 3200, False, False, False),  # not on a TPU: the XLA scan
        (512, 3200, True, True, False),  # under a sharding context
        (512, 3000, True, False, False),  # channels not in 128-lane tiles
        (200, 3200, True, False, False),  # sequence not in 128-step blocks
        (12, 128, True, False, False),  # sequence not in sublane rows
    ],
)
def test_fits(monkeypatch, s, c, on_tpu, sharded, fits):
    monkeypatch.setattr(ops, "on_tpu", lambda: on_tpu)
    if sharded:
        monkeypatch.setattr(dist_api, "current", lambda: object())
    assert ops.fits(s, c) is fits


def test_blocks_pick_tiles_of_the_channels():
    assert ops.blocks(512, 3200) == (128, 640)
    assert ops.blocks(64, 384) == (64, 384)
    assert ops.blocks(256, 896) == (128, 128)


def _hymba_ssm():
    cfg = get_config("hymba-1.5b").smoke()
    p = ssm.ssm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    xin = jax.ShapeDtypeStruct((2, 16, cfg.d_model), jnp.float32)
    return cfg, p, xin


@pytest.mark.parametrize("on_tpu", [False, True])
def test_ssm_apply_counts_the_path_it_traced(monkeypatch, on_tpu):
    """The CPU takes the XLA scan; where the kernel fits, the trace calls it
    (traced only: nothing is lowered here)."""
    monkeypatch.setattr(ops, "on_tpu", lambda: on_tpu)
    cfg, p, xin = _hymba_ssm()
    assert ssm.ssm_dims(cfg)[0] % 128 == 0
    with counting({}) as counters:
        out = jax.eval_shape(lambda x: ssm.ssm_apply(p, cfg, x, chunk=8), xin)
    assert out.shape == xin.shape
    assert counters == {("ssm_scan.kernel" if on_tpu else "ssm_scan.xla"): 1}


def test_counts_go_nowhere_outside_counting():
    count("ssm_scan.xla")  # no counters routed: nothing to record, no error
    with counting({}) as outer:
        with counting({}) as inner:
            count("a")
        count("b")
    assert inner == {"a": 1} and outer == {"b": 1}


def test_adaptor_counts_into_the_executor_log():
    """A job whose step runs the SSM branch leaves the path its trace took
    in the executor's span log, beside ``switches``: the XLA scan on the CPU."""
    cfg, p, xin = _hymba_ssm()

    def step(state, batch):
        return state, ssm.ssm_apply(state, cfg, batch, chunk=8)

    ex = SalusExecutor(capacity=1 * GB, policy=get_policy("fifo"))
    VirtualDevice(ex).create_session(
        "ssm", step, p, lambda i: np.zeros(xin.shape, np.float32), n_iters=1,
    )
    assert ex.spans.counters.get("ssm_scan.xla") == 1
    assert "ssm_scan.kernel" not in ex.spans.counters


@pytest.mark.parametrize("block,tile", [(12, 128), (40, 128), (32, 96)])
def test_blocks_that_do_not_tile_are_refused(block, tile):
    (dt, a, b_in, c_in, x), _ = _inputs((1, 96, 128, 4), "slow")
    with pytest.raises(ValueError):
        scan_fwd(dt, x, a.T, jnp.swapaxes(b_in, 1, 2), jnp.swapaxes(c_in, 1, 2),
                 block=block, tile=tile, save=False, interpret=True)
