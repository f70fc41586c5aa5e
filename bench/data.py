"""The benchmark's own inputs: client data, channel gains and weights.

Kept apart from ``repro.data`` and ``repro.fl.environment`` so the
yardstick does not move when the program's generators change.

Sizes are fixed by the configuration's ``layout_seed``: every ``--seed``
gives the same clients with the same sizes and label mixes (so the same
tier ladder and the same work), with seed-dependent images and weights.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass
class Testbed:
    """One configuration's data, as the program and the reference get it."""
    clients: List[Tuple[np.ndarray, np.ndarray]]   # per client (x, y)
    test_x: np.ndarray
    test_y: np.ndarray

    @property
    def sizes(self) -> np.ndarray:
        return np.asarray([len(y) for _, y in self.clients], np.int64)


def _dirichlet_labels(rng, labels: np.ndarray, num_devices: int,
                      concentration: float, min_per_device: int
                      ) -> List[np.ndarray]:
    """Per-device label arrays of a Dirichlet(concentration) split per
    class (Hsu et al.), redrawn until every device holds enough rows."""
    classes = np.unique(labels)
    for _ in range(256):
        parts: List[list] = [[] for _ in range(num_devices)]
        for c in classes:
            n_c = int(np.sum(labels == c))
            probs = rng.dirichlet(np.full(num_devices, concentration))
            counts = np.diff(np.concatenate(
                [[0], (np.cumsum(probs) * n_c).astype(int)[:-1], [n_c]]))
            for dev, k in enumerate(counts):
                parts[dev].append(np.full(k, c, np.int32))
        sizes = np.asarray([sum(len(a) for a in p) for p in parts])
        if sizes.min() >= min_per_device:
            break
    return [np.concatenate(p) for p in parts]


def _writer_labels(rng, num_devices: int, num_classes: int, mean: float,
                   std: float, lo: int, hi: int, profile: int
                   ) -> List[np.ndarray]:
    """FEMNIST-style writers: a size from N(mean, std) clipped to
    [lo, hi], labels from a personal subset of ``profile`` classes."""
    out = []
    for _ in range(num_devices):
        size = int(np.clip(round(rng.normal(mean, std)), lo, hi))
        classes = rng.choice(num_classes, size=profile, replace=False)
        weights = rng.dirichlet(np.full(profile, 0.8))
        out.append(rng.choice(classes, size=size, p=weights).astype(np.int32))
    return out


def layout(cfg: dict) -> Tuple[List[np.ndarray], np.ndarray]:
    """(per-client labels, test labels), fixed by ``layout_seed``."""
    d = cfg["data"]
    rng = np.random.default_rng(cfg["layout_seed"])
    n, classes = cfg["num_devices"], cfg["num_classes"]
    if d["kind"] == "dirichlet":
        labels = rng.integers(0, classes, d["examples"]).astype(np.int32)
        n_test = int(round(d["test_fraction"] * d["examples"]))
        test_y, train_y = labels[:n_test], labels[n_test:]
        parts = _dirichlet_labels(rng, train_y, n, d["concentration"],
                                  d["min_per_device"])
    elif d["kind"] == "writers":
        parts = _writer_labels(rng, n, classes, d["size_mean"],
                               d["size_std"], d["size_min"], d["size_max"],
                               d["label_profile"])
        test_y = rng.integers(0, classes, d["test_examples"]).astype(np.int32)
    else:
        raise ValueError(f"unknown data kind {d['kind']!r}")
    return parts, test_y


def make_testbed(cfg: dict, seed: int) -> Testbed:
    """Images ``template[y] + noise`` (unit-norm low-rank class templates)
    for the layout's labels; templates, noise and the order of each
    client's rows from ``seed``."""
    parts, test_y = layout(cfg)
    d = cfg["data"]
    rng = np.random.default_rng([seed, 1])
    shape = tuple(cfg["image_shape"])
    dim = int(np.prod(shape))
    classes, rank = cfg["num_classes"], d["rank"]
    u = rng.standard_normal((classes, rank, dim), np.float32)
    coeff = rng.standard_normal((classes, rank), np.float32)
    templates = np.einsum("kr,krd->kd", coeff, u) / np.sqrt(rank)
    templates /= np.linalg.norm(templates, axis=1, keepdims=True)

    def images(y):
        x = rng.standard_normal((len(y), dim), np.float32)
        x *= np.float32(d["noise"])
        x += templates[y]
        return x.reshape((len(y),) + shape)

    clients = []
    for labels in parts:
        y = rng.permutation(labels)
        clients.append((images(y), y))
    return Testbed(clients, images(test_y), test_y)


def channel_gains(cfg: dict, modes: List[str], rounds: int, seed: int
                  ) -> np.ndarray:
    """``[S, T, N]`` float32 gains, one lane per entry of ``modes``:
    'iid' draws a truncated exponential (redraw until inside
    [min_gain, max_gain]); 'markov' modulates its mean by a per-client
    Gilbert-Elliott chain started from its stationary law."""
    ch = cfg["channel"]
    n = cfg["num_devices"]
    rng = np.random.default_rng([seed, 2])
    out = np.empty((len(modes), rounds, n), np.float32)
    lo, hi = ch["min_gain"], ch["max_gain"]
    for s, mode in enumerate(modes):
        mean = np.full((rounds, n), ch["mean_gain"])
        if mode == "markov":
            p_gb, p_bg = ch["p_gb"], ch["p_bg"]
            bad = rng.random(n) < p_gb / (p_gb + p_bg)
            for t in range(rounds):
                mean[t, bad] = ch["bad_gain"]
                u = rng.random(n)
                bad = np.where(bad, u >= p_bg, u < p_gb)
        elif mode != "iid":
            raise ValueError(f"unknown channel mode {mode!r}")
        h = rng.exponential(1.0, (rounds, n)) * mean
        for _ in range(64):
            bad_draw = (h < lo) | (h > hi)
            if not bad_draw.any():
                break
            h[bad_draw] = rng.exponential(1.0, bad_draw.sum()) * mean[bad_draw]
        out[s] = np.clip(h, lo, hi)
    return out


def derived_seed(seed: int, stream: int) -> int:
    """A 31-bit seed for the program's own PRNG keys, drawn from
    ``seed`` (which may exceed 32 bits)."""
    return int(np.random.default_rng([seed, 3, stream]).integers(0, 2**31 - 1))
