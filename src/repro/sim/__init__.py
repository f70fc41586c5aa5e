"""repro.sim — the ScenarioArena sweep engine: struct-of-arrays scenario
grids (controller-as-data via traced ``lax.switch`` ids), whole evaluation
grids vmapped over the fused rollout scan in one jitted program (optionally
scenario-sharded over a mesh ``data`` axis), shape-adaptive dispatch
planning (cost-model lane bucketing over the ``(K, tier-footprint)``
signatures — ``k_mode='auto'``), streaming chunked execution
(``Arena.run(chunk_size=...)`` — carry-donated scan segments, host
reduction overlapped with device dispatch) behind a long-lived
``SweepService`` (queued/coalesced submissions, crash-safe chunk
checkpoints), and structured RolloutReports with the paper's Sec. VII
trade-off reducers."""

from repro.sim.arena import (Arena, ScenarioGrid, derive_hyperparams,
                             scenario_keys)
from repro.sim.cost_model import CostModel
from repro.sim.dispatch import (DispatchBucket, DispatchPlan,
                                lane_footprints, plan_dispatch)
from repro.sim.eval import EvalBank
from repro.sim.report import RolloutReport, concat_chunk_metrics
from repro.sim.service import NpzChunkStore, SweepService
