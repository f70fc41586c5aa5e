"""The CIFAR-10 CNN of NIID-Bench (Li et al., arXiv:2102.02079).

Two 5x5 convolutions (VALID, with bias) of ``conv_channels`` = 6 and 16
channels, each followed by ReLU and 2x2 max pooling, then dense layers of
``dense`` = 120 and 84 units with ReLU and a dense layer to the classes:
LeNet-5's shape, the ``SimpleCNN`` that NIID-Bench and MOON
(arXiv:2103.16257) train on CIFAR-10.  62,006 parameters at 32x32x3 and
10 classes.  Weights are drawn as PyTorch's layers draw them by default:
uniform in +-1/sqrt(fan_in), biases too.

Everything the benchmark needs of the architecture is here: the
parameter shapes, the weights from a key (on the device, in one call),
the forward pass, the parameter count, the forward FLOPs per example,
and :class:`Task`, the object the program trains.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _spatial(cfg: dict):
    """(height, width) after each conv + pool block."""
    h, w, _ = cfg["image_shape"]
    k = cfg["kernel_size"]
    out = []
    for _ in cfg["conv_channels"]:
        h, w = (h - k + 1) // 2, (w - k + 1) // 2
        out.append((h, w))
    return out


def shapes(cfg: dict) -> dict:
    """Every parameter's shape, by name, in the order of the layers."""
    k = cfg["kernel_size"]
    cin = cfg["image_shape"][2]
    out = {}
    for i, c in enumerate(cfg["conv_channels"]):
        out[f"conv{i}"] = (k, k, cin, c)
        out[f"conv{i}_b"] = (c,)
        cin = c
    h, w = _spatial(cfg)[-1]
    units = [h * w * cin] + list(cfg["dense"]) + [cfg["num_classes"]]
    for i, (a, b) in enumerate(zip(units[:-1], units[1:])):
        out[f"fc{i}"] = (a, b)
        out[f"fc{i}_b"] = (b,)
    return out


def param_count(cfg: dict) -> int:
    return int(sum(np.prod(s) for s in shapes(cfg).values()))


def forward_flops(cfg: dict) -> int:
    """One example's forward pass: 2 FLOPs per multiply-add of every
    convolution and dense layer (biases, activations and pooling left
    out, as is usual)."""
    h, w, _ = cfg["image_shape"]
    k = cfg["kernel_size"]
    named = shapes(cfg)
    macs = 0
    for i, _ in enumerate(cfg["conv_channels"]):
        kh, kw, cin, cout = named[f"conv{i}"]
        h, w = h - k + 1, w - k + 1
        macs += h * w * kh * kw * cin * cout
        h, w = h // 2, w // 2
    for name, s in named.items():
        if name.startswith("fc") and not name.endswith("_b"):
            macs += s[0] * s[1]
    return 2 * macs


def _key_cfg(cfg: dict) -> tuple:
    return (tuple(cfg["image_shape"]), cfg["num_classes"],
            tuple(cfg["conv_channels"]), cfg["kernel_size"],
            tuple(cfg["dense"]))


def _cfg(key_cfg: tuple) -> dict:
    image_shape, classes, conv, k, dense = key_cfg
    return dict(image_shape=image_shape, num_classes=classes,
                conv_channels=conv, kernel_size=k, dense=dense)


@partial(jax.jit, static_argnames=("key_cfg",))
def _init(key, key_cfg):
    named = shapes(_cfg(key_cfg))
    keys = jax.random.split(key, len(named))
    out, fan_in = {}, 1
    for k, (name, shape) in zip(keys, named.items()):
        if not name.endswith("_b"):
            fan_in = int(np.prod(shape[:-1]))
        bound = 1.0 / np.sqrt(fan_in)
        out[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    return out


def init_params(key, cfg: dict) -> dict:
    """The weights from ``key``, on the device, in one call (float32, the
    type the program trains in)."""
    return _init(key, _key_cfg(cfg))


def logits(params: dict, x, cfg: dict):
    for i, _ in enumerate(cfg["conv_channels"]):
        x = jax.lax.conv_general_dilated(
            x, params[f"conv{i}"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + params[f"conv{i}_b"]
        x = jax.lax.reduce_window(jax.nn.relu(x), -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    layers = len(cfg["dense"]) + 1
    for i in range(layers):
        x = x @ params[f"fc{i}"] + params[f"fc{i}_b"]
        if i < layers - 1:
            x = jax.nn.relu(x)
    return x


def xent(lg, y):
    logp = jax.nn.log_softmax(lg.astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(
        logp, y[:, None].astype(jnp.int32), axis=-1))


@dataclasses.dataclass(frozen=True)
class Task:
    """The program's ``Task`` protocol over this network: ``init``,
    ``loss_fn`` and ``metrics`` (the evaluation in one jitted call)."""
    key_cfg: tuple

    @classmethod
    def of(cls, cfg: dict) -> "Task":
        return cls(_key_cfg(cfg))

    def init(self, rng):
        return _init(rng, self.key_cfg)

    def loss_fn(self, params, batch):
        return xent(logits(params, batch["x"], _cfg(self.key_cfg)),
                    batch["y"])

    def metrics(self, params, batch):
        return _metrics(params, batch["x"], batch["y"], self.key_cfg)


@partial(jax.jit, static_argnames=("key_cfg",))
def _metrics(params, x, y, key_cfg):
    lg = logits(params, x, _cfg(key_cfg))
    return {"accuracy": jnp.mean((jnp.argmax(lg, -1) == y)
                                 .astype(jnp.float32)),
            "loss": xent(lg, y)}
