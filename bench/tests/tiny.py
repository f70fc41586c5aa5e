"""Tiny versions of the benchmark's cells, for runs on the CPU in tests."""

from __future__ import annotations

import copy

from bench import run as bench_run

#: the end-to-end metrics each kind of loop reports
RATES = {"arena": ("arena_scenario_rounds_per_s",),
         "trainer": ("trainer_rounds_per_s", "trainer_round_p90_ms")}


def tiny_cell(name: str) -> dict:
    """Cell ``name`` (``<config>.<traffic>``) from its files, at a size a
    CPU test holds:
    12 clients, K = 4, 16x16x1 images, the network at widths 2/3 and
    8/6."""
    cfg, cell = copy.deepcopy(bench_run.cell_files(name,
                                                   *name.split(".", 1)))
    e2e = RATES[cell["kind"]] + ("setup_s",)
    c = dict(cfg=cfg, cell=cell, chips=1, per_layer=[],
             e2e=[{"name": m, "unit": "-"} for m in e2e])
    cfg.update(num_devices=12, sample_count=4, image_shape=[16, 16, 1],
               num_classes=4, conv_channels=[2, 3], dense=[8, 6],
               eval_examples=32)
    if cfg["data"]["kind"] == "dirichlet":
        cfg["data"].update(examples=3000, min_per_device=4)
    else:
        cfg["data"].update(size_mean=40, size_std=20, size_min=8,
                           size_max=90, label_profile=3, test_examples=64)
    cfg["rounds"] = 4
    if "eval_examples" in cell:
        cell["eval_examples"] = 48
    if cell["kind"] == "arena":
        cell["chunk_size"] = min(cell["chunk_size"], 2)
        cell["eval_every"] = min(cell["eval_every"], 2)
    return c
