"""Operations the algorithm needs, counted from shapes.

* :func:`forward_flops` — one example's forward pass of the
  configuration's model (``bench/models/<model>.py`` counts it).
* :func:`train_flops` — one trained example: 3 x the forward.
* :func:`param_count` — the model's parameters.
"""

from __future__ import annotations

from bench import models


def forward_flops(cfg: dict) -> int:
    return models.of(cfg).forward_flops(cfg)


def train_flops(cfg: dict) -> int:
    return 3 * forward_flops(cfg)


def param_count(cfg: dict) -> int:
    return models.of(cfg).param_count(cfg)
