"""Names of the device program's layers — the ``jax.named_scope``s of
the FL round.

Scopes are HLO metadata only: each compiled op's ``op_name`` carries
the scope path it was traced under (a transform wraps a name, as in
``vmap(fl.local_sgd)``), and a device profile shows that path as the
op's ``tf_op``.  No number a program computes depends on them.

The seven layer scopes never nest inside one another, so they split a
round's device time; ``fl.tier.<rows>`` and the ``fl.sgd.*`` sub-scopes
split a layer's time further and are never added to it.
"""

DECIDE = "fl.decide"          # the controller's decision (p, f, q)
SELECT = "fl.select"          # selection draw, slot fill, eq.-(4) coeffs
GATHER = "fl.gather"          # bank take and int8 dequantization
LOCAL_SGD = "fl.local_sgd"    # the vmapped E-epoch local SGD
AGGREGATE = "fl.aggregate"    # eq. (4) and the tier update sums
QUEUES = "fl.queues"          # queue update, time/energy, round outputs
EVAL = "fl.eval"              # test-set evaluation

SGD_SHUFFLE = "fl.sgd.shuffle"  # per-epoch uniform keys and argsort
SGD_BATCH = "fl.sgd.batch"      # the in-step batch takes
SGD_GRAD = "fl.sgd.grad"        # value_and_grad and the clip
SGD_UPDATE = "fl.sgd.update"    # optimizer update and num_steps masks

#: ``fl.tier.<rows>``: one tier of the bucket ladder, by its bucket rows
TIER_PREFIX = "fl.tier."

LAYERS = (DECIDE, SELECT, GATHER, LOCAL_SGD, AGGREGATE, QUEUES, EVAL)
SCOPES = LAYERS + (SGD_SHUFFLE, SGD_BATCH, SGD_GRAD, SGD_UPDATE)


def tier(rows: int) -> str:
    """The scope of the tier whose bucket holds ``rows`` rows."""
    return f"{TIER_PREFIX}{int(rows)}"
