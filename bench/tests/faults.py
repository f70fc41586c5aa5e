"""Faults planted under the timed path, for the check to catch.

Each is a context manager that patches the program's modules; enter it
before the run builds its executables.

* ``unchanged``  — every round returns the model it was given;
* ``half_batch`` — half of the round's K clients are left out of eq. (4)
  and the other half's coefficients doubled (the mean over the rest);
* ``altered``    — the selection is altered where it is produced: each
  chosen client index moves to the next client, in every selection mode
  (sampled draws, the round-robin schedule, DivFL's greedy picks) and in
  the trainer's host-side draw.

A fourth fault of the contract, the exchange between chips left out, has
no place in a one-chip cell.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp


@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def unchanged():
    from repro.fl.round_engine import RoundEngine
    core = RoundEngine._round_core

    def round_core(self, params, *args, **kw):
        _, losses = core(self, params, *args, **kw)
        return params, losses

    with _patch(RoundEngine, "_round_core", round_core):
        yield


@contextlib.contextmanager
def half_batch():
    from repro.fl.round_engine import RoundEngine
    core = RoundEngine._round_core

    def round_core(self, params, xs, ys, coeffs, *args, **kw):
        k = coeffs.shape[0]
        keep = (jnp.arange(k) < k // 2).astype(coeffs.dtype)
        return core(self, params, xs, ys, coeffs * keep * 2.0, *args, **kw)

    with _patch(RoundEngine, "_round_core", round_core):
        yield


@contextlib.contextmanager
def altered():
    import numpy as np
    from repro.core import policy
    from repro.fl import server
    draw = server.sample_clients

    def shifted(select):
        def fn(params, *args):
            return (select(params, *args) + 1) % params.num_devices
        return fn

    def sample_clients(rng, q, k):
        return (np.asarray(draw(rng, q, k)) + 1) % len(q)

    fns = tuple(shifted(f) for f in policy.SELECT_FNS)
    with _patch(policy, "SELECT_FNS", fns), \
            _patch(server, "sample_clients", sample_clients):
        yield


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered": altered}
