"""The plain reference: what the program should compute, written apart.

It imports nothing of the program.  Two halves:

* the model path in ``jax.numpy`` — the configuration's network
  (``bench/models/<model>.py``), E epochs of mini-batch SGD with
  heavy-ball momentum for one client at a time, the eq.-(4) sum
  ``theta + sum_i w_i / (K q_i) * delta_i`` and the test-set loss —
  at float32 with ``highest`` matmul precision (``dtype=bfloat16`` gives
  the control: the same math one precision lower);
* the control plane in float64 numpy — the paper's time and energy model
  (eqs. 5-17), the virtual energy queues (eqs. 19-20) and the seven
  decision rules (Algorithm 2 for LROA, the baselines' closed forms),
  plus the selection rules the program's draws are checked against.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import models

# -- model path ---------------------------------------------------------------


def _precision(dtype):
    return "highest" if dtype == jnp.float32 else "default"


@partial(jax.jit, static_argnames=("net", "epochs", "batch", "momentum",
                                   "dtype"))
def local_sgd(params, x, y, n, lr, key, *, net, epochs, batch, momentum,
              dtype):
    """One client's E epochs of SGD; returns (update, mean epoch loss).

    ``x``/``y`` hold the client's ``n`` true rows tiled cyclically to a
    fixed length.  Each epoch draws ``uniform(epoch_key, (len,))``, puts
    rows ``>= n`` last and takes the first ``max(n // batch, 1) * batch``
    of the stable argsort in batches of ``batch`` (a client with fewer
    than ``batch`` rows fills its one batch with the tiled rows that
    follow).  Momentum: ``m = momentum m + g``, ``p -= lr m``.  ``net``
    is the network's loss, ``net.loss_fn(params, {"x", "y"})``.
    """
    with jax.default_matmul_precision(_precision(dtype)):
        p0 = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        x = x.astype(dtype)
        lr = lr.astype(dtype)
        steps = jnp.maximum(n // batch, 1)
        length = x.shape[0]

        def epoch(carry, ekey):
            p, m = carry
            scores = jax.random.uniform(ekey, (length,))
            scores = jnp.where(jnp.arange(length) < n, scores, 2.0)
            perm = jnp.argsort(scores, stable=True)

            def step(s, c):
                p, m, tot = c
                idx = jax.lax.dynamic_slice(perm, (s * batch,), (batch,))
                loss, g = jax.value_and_grad(net.loss_fn)(
                    p, {"x": jnp.take(x, idx, 0), "y": jnp.take(y, idx, 0)})
                m = jax.tree_util.tree_map(
                    lambda m, g: (momentum * m + g).astype(dtype), m, g)
                p = jax.tree_util.tree_map(lambda p, m: p - lr * m, p, m)
                return p, m, tot + loss

            p, m, tot = jax.lax.fori_loop(0, steps, step,
                                          (p, m, jnp.float32(0.0)))
            return (p, m), tot / steps.astype(jnp.float32)

        m0 = jax.tree_util.tree_map(jnp.zeros_like, p0)
        (p, _), losses = jax.lax.scan(epoch, (p0, m0),
                                      jax.random.split(key, epochs))
        upd = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0)
        return upd, jnp.mean(losses)


@partial(jax.jit, static_argnames=("net", "dtype"))
def test_loss(params, x, y, *, net, dtype):
    with jax.default_matmul_precision(_precision(dtype)):
        p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        return net.loss_fn(p, {"x": x.astype(dtype), "y": y})


class ModelRef:
    """Client trainings and the eq.-(4) sum on the device, one client at
    a time, each client's rows uploaded once and reused."""

    def __init__(self, cfg: dict, clients, dtype=jnp.float32):
        self.cfg = cfg
        self.net = models.of(cfg).Task.of(cfg)
        self.clients = clients
        self.dtype = dtype
        bs = cfg["batch_size"]
        longest = max(max(len(y) for _, y in clients), bs)
        self.length = -(-longest // bs) * bs
        self._dev: Dict[int, tuple] = {}

    def _rows(self, c: int):
        if c not in self._dev:
            x, y = self.clients[c]
            idx = np.arange(self.length) % len(y)
            self._dev[c] = (jnp.asarray(x[idx]), jnp.asarray(y[idx]),
                            jnp.int32(len(y)))
        return self._dev[c]

    def round(self, params, selected: Sequence[int], coeffs, lr, keys):
        """eq. (4) over the selected clients: (new params, mean loss)."""
        total = jax.tree_util.tree_map(
            lambda a: np.zeros(a.shape, np.float64), params)
        losses = []
        for c, cf, key in zip(selected, coeffs, keys):
            x, y, n = self._rows(int(c))
            upd, loss = local_sgd(
                params, x, y, n, jnp.float32(lr), key, net=self.net,
                epochs=self.cfg["local_epochs"],
                batch=self.cfg["batch_size"],
                momentum=self.cfg["momentum"], dtype=self.dtype)
            total = jax.tree_util.tree_map(
                lambda t, u: t + float(cf) * np.asarray(u, np.float64),
                total, upd)
            losses.append(float(loss))
        new = jax.tree_util.tree_map(
            lambda p, t: jnp.asarray(np.asarray(p, np.float64) + t,
                                     jnp.float32), params, total)
        return new, float(np.mean(losses))

    def test_loss(self, params, x, y) -> float:
        return float(test_loss(params, jnp.asarray(x), jnp.asarray(y),
                               net=self.net, dtype=self.dtype))


# -- control plane (float64) --------------------------------------------------

EPS = 1e-12


class System:
    """The paper's per-device time and energy model (eqs. 5-17)."""

    def __init__(self, cfg: dict, sizes: np.ndarray, energy_scale=1.0):
        s = cfg["system"]
        self.n = cfg["num_devices"]
        self.k = float(cfg["sample_count"])
        self.epochs = cfg["local_epochs"]
        self.bandwidth = s["bandwidth_hz"]
        self.noise = s["noise_power"]
        self.bits = s["param_bits"] * s["model_params"]
        self.cycles = np.full(self.n, s["cycles_per_sample"])
        self.sizes = np.asarray(sizes, np.float64)
        self.w = self.sizes / self.sizes.sum()
        self.alpha = np.full(self.n, s["capacitance"])
        self.budget = np.full(self.n, s["energy_budget"] * energy_scale)
        self.f_min, self.f_max = s["f_min"], s["f_max"]
        self.p_min, self.p_max = s["p_min"], s["p_max"]

    def sel(self, q):
        return 1.0 - (1.0 - q) ** self.k

    def round_time(self, h, p, f):
        rate = self.bandwidth / self.k * np.log2(1.0 + h * p / self.noise)
        return self.epochs * self.cycles * self.sizes / f + self.bits / rate

    def round_energy(self, h, p, f):
        rate = self.bandwidth / self.k * np.log2(1.0 + h * p / self.noise)
        return (0.5 * self.alpha * self.epochs * self.cycles * self.sizes
                * f ** 2 + p * self.bits / rate)

    # Theorem 2: f = clip(cbrt(V q / (Q sel(q) alpha)))
    def solve_f(self, q, queues, V):
        denom = queues * self.sel(q) * self.alpha
        f = np.cbrt(V * q / np.maximum(denom, EPS))
        f = np.where(denom <= EPS, self.f_max, f)
        return np.clip(f, self.f_min, self.f_max)

    # Theorem 3: (1+x) ln(1+x) - x = V q h / (Q sel(q) N0), x = h p / N0
    def solve_p(self, q, queues, h, V):
        denom = queues * self.sel(q) * self.noise
        a1 = V * q * h / np.maximum(denom, EPS)
        phi = lambda x: (1.0 + x) * np.log1p(x) - x
        hi = np.maximum(h * self.p_max / self.noise, 1.0)
        for _ in range(60):
            hi = np.where(phi(hi) < a1, 2.0 * hi, hi)
        lo = np.zeros_like(hi)
        for _ in range(200):
            mid = 0.5 * (lo + hi)
            below = phi(mid) < a1
            lo, hi = np.where(below, mid, lo), np.where(below, hi, mid)
        p = 0.5 * (lo + hi) * self.noise / np.maximum(h, EPS)
        p = np.where(denom <= EPS, self.p_max, p)
        return np.clip(p, self.p_min, self.p_max)

    def static_f(self, h, p):
        """Uni-S energy balance: E_cmp(f) + E_com = Ebar / sel(1/N)."""
        sel = 1.0 - (1.0 - 1.0 / self.n) ** self.k
        rate = self.bandwidth / self.k * np.log2(1.0 + h * p / self.noise)
        e_com = p * self.bits / rate
        cyc = self.epochs * self.alpha * self.cycles * self.sizes
        f = np.sqrt(np.maximum(2.0 * (self.budget / sel - e_com)
                               / np.maximum(cyc, 1e-30), 0.0))
        return np.clip(f, self.f_min, self.f_max)


def _waterfill(b, a3, q_floor):
    """argmin sum b q + a3 / q over the simplex, q in [q_floor, 1]."""
    a3 = np.maximum(a3, EPS)
    q_of = lambda nu: np.clip(np.sqrt(a3 / np.maximum(b + nu, EPS)),
                              q_floor, 1.0)
    lo = -np.min(b) + EPS
    hi = max(np.max(a3 * len(b) ** 2 - b) + 1.0, lo + 1.0)
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if q_of(mid).sum() > 1.0:
            lo = mid
        else:
            hi = mid
    q = q_of(0.5 * (lo + hi))
    return q / q.sum()


def decide(name: str, sysm: System, h, queues, V, lam, solver: dict):
    """(f, p, q) of decision rule ``name`` for one round."""
    n = sysm.n
    uniform = np.full(n, 1.0 / n)
    mid_p = np.full(n, 0.5 * (sysm.p_min + sysm.p_max))
    if name == "lroa":
        f = np.full(n, 0.5 * (sysm.f_min + sysm.f_max))
        p, q = mid_p.copy(), uniform.copy()
        scale = lambda f, p, q: np.concatenate(
            [f / sysm.f_max, p / sysm.p_max, q])
        prev = scale(f + sysm.f_max, p, q)
        for _ in range(solver["outer_iters"]):
            if np.linalg.norm(scale(f, p, q) - prev) <= solver["outer_tol"]:
                break
            prev = scale(f, p, q)
            f = sysm.solve_f(q, queues, V)
            p = sysm.solve_p(q, queues, h, V)
            t = sysm.round_time(h, p, f)
            e = sysm.round_energy(h, p, f)
            a2, a3 = V * t, V * lam * sysm.w ** 2
            qi = q / q.sum()
            qprev = qi + 1.0
            for _ in range(solver["sum_iters"]):
                if np.linalg.norm(qi - qprev) <= solver["sum_tol"]:
                    break
                grad = queues * e * sysm.k * (1.0 - qi) ** (sysm.k - 1)
                qi, qprev = _waterfill(a2 + grad, a3, solver["q_floor"]), qi
            q = qi
        return f, p, q
    if name in ("uni_d", "round_robin"):
        q = uniform
        return sysm.solve_f(q, queues, V), sysm.solve_p(q, queues, h, V), q
    if name == "channel_aware":
        ranks = np.argsort(np.argsort(-h, kind="stable"), kind="stable")
        q = (ranks < sysm.k).astype(np.float64)
        q /= q.sum()
        return sysm.solve_f(q, queues, V), sysm.solve_p(q, queues, h, V), q
    if name in ("uni_s", "divfl"):
        return sysm.static_f(h, mid_p), mid_p, uniform
    if name == "cost_effective":
        f = sysm.static_f(h, mid_p)
        score = sysm.w / np.sqrt(np.maximum(sysm.round_time(h, mid_p, f),
                                            EPS))
        q = np.maximum(score / score.sum(), solver["q_floor"])
        return f, mid_p, q / q.sum()
    raise ValueError(f"unknown decision rule {name!r}")


def queue_step(sysm: System, queues, h, f, p, q):
    """Q <- max(Q + sel(q) E - Ebar, 0)."""
    inc = sysm.sel(q) * sysm.round_energy(h, p, f) - sysm.budget
    return np.maximum(queues + inc, 0.0)


def cdf_gap(q, u, chosen: int, side: str) -> float:
    """How far ``u`` lies outside the interval of CDF(q) that the chosen
    index owns (0 when inside): the test of a with-replacement draw.
    ``side='left'`` for searchsorted-left draws (u in (cdf[j-1], cdf[j]]),
    ``'right'`` for searchsorted-right ones."""
    cdf = np.cumsum(q) / np.sum(q)
    lo = cdf[chosen - 1] if chosen > 0 else 0.0
    hi = cdf[chosen]
    return float(max(lo - u, u - hi, 0.0))


def divfl_gaps(sysm: System, h, chosen: Sequence[int]) -> float:
    """Facility-location greedy over the normalised ``[w, h]`` features:
    at each step, how far the chosen client's coverage gain lies below
    the best gain given the earlier picks (relative)."""
    feats = np.stack([sysm.w, np.asarray(h, np.float64)], 1)
    unit = feats / np.maximum(np.linalg.norm(feats, axis=1,
                                             keepdims=True), EPS)
    sim = unit @ unit.T
    best = np.full(sysm.n, -np.inf)
    taken = np.zeros(sysm.n, bool)
    worst = 0.0
    for j in chosen:
        gains = np.maximum(best[:, None], sim).sum(0)
        gains[taken] = -np.inf
        top = np.max(gains)
        g = gains[j] if not taken[j] else -np.inf
        worst = max(worst, float((top - g) / max(abs(top), EPS)))
        best = np.maximum(best, sim[:, j])
        taken[j] = True
    return worst
