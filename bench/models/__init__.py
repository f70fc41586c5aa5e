"""One file per model architecture, found by the configuration's
``model`` key: ``bench/models/<model>.py``."""

from __future__ import annotations

import importlib


def of(cfg: dict):
    """The module of ``cfg``'s architecture."""
    return importlib.import_module(f"bench.models.{cfg['model']}")
