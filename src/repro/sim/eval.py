"""EvalBank — the device-resident evaluation data plane for the arena.

The accuracy half of the paper's Sec.-VII trade-off curves used to run
host-side: after an ``Arena.run`` the driver looped over the S lanes,
sliced each lane's params out of the stacked pytree (one device gather
per leaf per lane), and dispatched one ``task.metrics`` call per lane —
S tiny dispatch chains whose wall-clock dominates pilot-length sweeps.
The EvalBank inverts that exactly like the ClientBank inverted the
training data plane: the test set is uploaded ONCE at construction
(blocking, never-aliasing, never-donated copy), and evaluation is one
``jax.vmap``ped ``task.metrics`` pass over the whole ``[S, ...]`` params
stack — one dispatch for the entire grid.

Two consumers:

* :meth:`evaluate_stacked` — host-facing batched evaluation of a stacked
  params pytree (``Arena.run`` calls it on the final params, landing
  ``test_*`` columns in ``RolloutReport.final_metrics``).
* :meth:`eval_fn` + :meth:`device_args` — the in-scan plane: the arena
  threads ``device_args()`` into the rollout executable as traced inputs
  and the scan body calls ``eval_fn`` every ``eval_every`` rounds behind
  an unbatched ``lax.cond`` (see ``RoundEngine._build_scan``), emitting
  ``test_<metric>`` per-round columns.  Passing the buffers as arguments
  (not closures) keeps the test set out of the executable's constant
  pool and lets one compiled program serve any same-shape test set.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import scopes

PyTree = Any


class EvalBank:
    """Device-resident test set + batched ``task.metrics`` evaluation."""

    def __init__(self, task, x, y):
        self.task = task
        # jnp.array copy semantics: the device buffers can never alias
        # caller numpy memory, mirroring the ClientBank upload contract
        self.x = jnp.array(np.asarray(x))
        self.y = jnp.array(np.asarray(y))
        # block so the upload can't race callers mutating their arrays
        jax.block_until_ready((self.x, self.y))
        self.num_examples = int(self.x.shape[0])
        #: the pure per-model evaluation trace, built by
        #: :meth:`make_eval_fn` — closes over the TASK only, never the
        #: bank, so embedding it in a long-lived cached executable (the
        #: arena) cannot pin the test-set buffers
        self.eval_fn = self.make_eval_fn(task)
        # one jitted executable per bank (jax caches on callable
        # identity, so this must be built once here, not per call)
        self._stacked = jax.jit(jax.vmap(self.eval_fn, in_axes=(0, None)))

    @staticmethod
    def make_eval_fn(task):
        """``eval_fn(params, data) -> {metric: scalar}`` over a traced
        ``(x, y)`` test set — THE evaluation trace, shared by the in-scan
        path (``RoundEngine._build_scan``) and :meth:`evaluate_stacked`
        so the ``test_*`` columns and ``final_metrics`` cannot diverge."""
        def eval_fn(params: PyTree, data) -> Dict[str, jax.Array]:
            x, y = data
            with jax.named_scope(scopes.EVAL):
                return task.metrics(params, {"x": x, "y": y})
        return eval_fn

    def device_args(self):
        """(x, y) device buffers for threading into a jitted rollout."""
        return (self.x, self.y)

    def evaluate_stacked(self, params: PyTree) -> Dict[str, np.ndarray]:
        """Evaluate a stacked ``[S, ...]`` params pytree in ONE vmapped
        dispatch; returns ``{metric: [S] numpy array}``."""
        out = self._stacked(params, (self.x, self.y))
        return {name: np.asarray(v) for name, v in out.items()}

    def evaluate_one(self, params: PyTree) -> Dict[str, float]:
        """Single-model evaluation (host convenience / reference)."""
        out = self.eval_fn(params, (self.x, self.y))
        return {name: float(v) for name, v in out.items()}

    def carry_struct(self, params_example: PyTree, s: int
                     ) -> Dict[str, jax.ShapeDtypeStruct]:
        """Shape/dtype structs of the in-scan last-eval carry for an
        ``[s, ...]`` lane stack — ``{metric: ShapeDtypeStruct([s])}``,
        derived from the real evaluation trace via ``jax.eval_shape`` so
        it cannot drift from what ``_build_scan`` actually carries.  The
        streaming arena uses this to AOT-lower chunk-resume executables
        and the sweep service to rebuild a checkpointed carry's ``like``
        tree without executing an evaluation."""
        out = jax.eval_shape(self.eval_fn, params_example,
                             (self.x, self.y))
        return {name: jax.ShapeDtypeStruct((s,) + tuple(v.shape), v.dtype)
                for name, v in out.items()}

    def aot_warm(self, s: int, params_example: PyTree) -> None:
        """AOT-compile the stacked evaluator for an ``[s, ...]`` params
        stack from shape structs alone (no execution) — the EvalBank
        half of ``Arena.warmup``; the compile fills the jit call cache."""
        structs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((s,) + tuple(a.shape),
                                           a.dtype), params_example)
        self._stacked.lower(structs, (self.x, self.y)).compile()
