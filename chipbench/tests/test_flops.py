"""The analytic FLOP entries of the configuration files against counts from
the parameter shapes."""
import math

import pytest

from chipbench import flops
from chipbench.run import reference_module
from chipbench.tests.smoke import config, published_config

# leaves that enter a matrix product once per token
MATMUL = {"wq", "wk", "wv", "wo", "wr", "wg", "in_proj", "x_proj", "dt_proj", "out_proj",
          "w_gate", "w_up", "w_down", "mix_w1", "mix_w2", "decay_w1", "decay_w2"}


def _mixer(cfg):
    """Per-token recurrence work of one layer."""
    if cfg["model_type"] == "hymba":
        di = cfg["mamba_expand"] * cfg["hidden_size"]
        return 8 * di * cfg["mamba_d_state"] + 2 * cfg["mamba_d_conv"] * di
    hd = cfg["head_size"]
    return 6 * hd * cfg["hidden_size"]  # 6 x head_size^2 x heads


@pytest.mark.parametrize("name", ["hymba-1.5b", "rwkv6-7b.l8"])
def test_flops_entry_matches_shapes(name):
    cfg = published_config(name)
    spec = reference_module(cfg).spec(cfg)
    L = cfg["num_hidden_layers"]
    mm = sum(math.prod(s) for p, (s, _) in spec.items()
             if p.startswith("layers/") and p.rsplit("/", 1)[-1] in MATMUL)
    f = cfg["flops"]
    assert f["layers_per_token"] == 2 * mm + L * _mixer(cfg)
    assert f["head_per_token"] == 2 * cfg["vocab_size"] * cfg["hidden_size"]
    heads = cfg.get("num_attention_heads", 0) if cfg["model_type"] == "hymba" else 0
    assert f["attention_per_key"] == 4 * heads * cfg.get("head_dim", 0) * L


def test_causal_keys_and_window():
    assert flops.keys_attended(4, 0) == 10
    assert flops.keys_attended(4, 8) == 10
    assert flops.keys_attended(5, 2) == 3 + 3 * 2
    f = {"layers_per_token": 10.0, "head_per_token": 3.0, "attention_per_key": 1.0}
    assert flops.prefill(f, 2, 4) == 2 * (4 * 10 + 3 + 10)
    assert flops.train_step(f, 2, 4) == 3 * 2 * (4 * 10 + 4 * 3 + 10)
