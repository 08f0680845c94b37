
import jax
import numpy as np
import pytest

from chipbench import jobs, weights
from chipbench.run import reference_module
from chipbench.tests.smoke import config, published_config

CONFIGS = ["hymba-1.5b", "rwkv6-7b.l8"]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("size", ["smoke", "full"])
def test_program_stores_the_reference_leaves(smoke, name, size):
    cfg = config(smoke, name) if size == "smoke" else published_config(name)
    model = jobs.program_model(cfg)
    got = weights.spec_of(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert weights.spec_diff(reference_module(cfg).spec(cfg), got) == []


@pytest.mark.parametrize("name", CONFIGS)
def test_stacked_and_per_layer_weights_agree(smoke, name):
    cfg = config(smoke, name)
    spec = reference_module(cfg).spec(cfg)
    seed = 2**33 + 5  # wider than 32 bits, as the driver's seeds are
    flat = weights.make_stacked(seed, spec)
    lspec = weights.layer_spec(spec, cfg["num_hidden_layers"])
    for i in range(cfg["num_hidden_layers"]):
        layer = weights.make_layer(seed, lspec, i)
        for p, v in layer.items():
            np.testing.assert_array_equal(np.asarray(flat[p][i], np.float32), np.asarray(v))
    for p, v in weights.make_top(seed, spec).items():
        np.testing.assert_array_equal(np.asarray(flat[p], np.float32), np.asarray(v))
    other = weights.make_stacked(seed + 1, spec)
    assert not np.array_equal(np.asarray(other["embed/table"]), np.asarray(flat["embed/table"]))
