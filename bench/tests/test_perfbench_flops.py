"""The benchmark's operation counts against hand counts."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops, models

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_param_count_is_the_published_one():
    # conv 5*5*3*6+6, conv 5*5*6*16+16, dense 400*120+120, 120*84+84,
    # 84*10+10: NIID-Bench's CIFAR-10 CNN
    hand = 456 + 2_416 + 48_120 + 10_164 + 850
    cfg = config("cifar10_cnn")
    assert flops.param_count(cfg) == hand == 62_006
    assert cfg["system"]["model_params"] == hand


@pytest.mark.parametrize("name", ["cifar10_cnn"])
def test_param_count_matches_the_weights_made(name):
    import jax
    cfg = config(name)
    p = models.of(cfg).init_params(jax.random.PRNGKey(0), cfg)
    assert sum(int(np.prod(a.shape)) for a in p.values()) == \
        flops.param_count(cfg)


def test_forward_flops_cifar():
    # 2 x multiply-adds: conv1 28*28*6*75, conv2 10*10*16*150,
    # dense 400*120, 120*84, 84*10
    hand = 2 * (28 * 28 * 6 * 75 + 10 * 10 * 16 * 150 + 400 * 120
                + 120 * 84 + 84 * 10)
    assert flops.forward_flops(config("cifar10_cnn")) == hand == 1_303_440
    assert flops.train_flops(config("cifar10_cnn")) == 3 * hand


def test_forward_pass_shapes():
    import jax
    import jax.numpy as jnp
    cfg = config("cifar10_cnn")
    net = models.of(cfg)
    p = net.init_params(jax.random.PRNGKey(1), cfg)
    out = net.logits(p, jnp.zeros((3, 32, 32, 3)), cfg)
    assert out.shape == (3, 10)
    # zero images: the logits are what the biases alone make
    assert bool(jnp.all(jnp.isfinite(out)))
