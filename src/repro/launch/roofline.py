"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds, from the peaks of the
named device kind (``repro.launch.mesh.DEVICE_PEAKS``):

    compute    = HLO_FLOPs   / (chips * flops_bf16)
    memory     = HLO_bytes   / (chips * hbm_bw)
    collective = collective_bytes / (chips * ici_bw)

``cost_analysis`` of the SPMD-partitioned executable reports **per-device**
flops/bytes, so global = per_device * chips and the division by chips
cancels; we compute from the per-device numbers directly (equivalent to the
brief's formulas).  collective_bytes sums the *operand* sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
in the post-partitioning HLO, reconstructed from result shapes + replica
group sizes (operands are not typed inline in optimized HLO text).
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

from repro.launch.mesh import device_peaks

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1,
}

_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\()?\s*((?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?(?:,\s*)?)+)\)?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    group_size: int
    operand_bytes: int
    ici_traffic_bytes: int       # ring-algorithm per-chip traffic estimate


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        shapes_blob, kind = m.group(1), m.group(2)
        if "-done" in line:
            continue
        result_bytes = sum(_shape_bytes(d, s)
                           for d, s in _SHAPE_RE.findall(shapes_blob))
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            if gi:
                g = int(gi.group(2))
        if kind == "all-gather":
            operand = result_bytes // max(g, 1)
            traffic = result_bytes * (g - 1) // max(g, 1)
        elif kind == "reduce-scatter":
            operand = result_bytes * g
            traffic = result_bytes * (g - 1)
        elif kind == "all-reduce":
            operand = result_bytes
            traffic = 2 * result_bytes * (g - 1) // max(g, 1)
        elif kind == "all-to-all":
            operand = result_bytes
            traffic = result_bytes * (g - 1) // max(g, 1)
        else:                      # collective-permute
            operand = result_bytes
            traffic = result_bytes
        ops.append(CollectiveOp(kind, result_bytes, g, operand, traffic))
    return ops


def roofline_terms(hlo_analysis: Dict, xla_cost: Dict[str, float],
                   *, chips: int, device_kind: str,
                   model_flops: float = 0.0) -> Dict:
    """``hlo_analysis``: loop-aware per-device numbers from
    ``repro.launch.hlo_cost.analyze`` (XLA's own cost_analysis counts while
    bodies once — see that module); ``xla_cost`` kept for cross-checking.
    ``device_kind`` selects the peaks (unknown kinds raise)."""
    peaks = device_peaks(device_kind)
    flops = float(hlo_analysis["flops"])
    bytes_accessed = float(hlo_analysis["bytes"])
    coll_operand = float(hlo_analysis["collective_operand_bytes"])
    coll_traffic = float(hlo_analysis["collective_traffic_bytes"])

    compute_s = flops / peaks["flops_bf16"]
    memory_s = bytes_accessed / peaks["hbm_bw"]
    collective_s = coll_operand / peaks["ici_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s,
             "collective_traffic_s": coll_traffic / peaks["ici_bw"],
             "hlo_flops_per_device": flops,
             "hlo_bytes_per_device": bytes_accessed,
             "collective_operand_bytes": coll_operand,
             "collective_traffic_bytes": coll_traffic,
             "collective_counts": hlo_analysis.get("collective_counts", {}),
             "collective_bytes_by_kind":
                 hlo_analysis.get("collective_bytes_by_kind", {}),
             "xla_raw_flops": float(xla_cost.get("flops", 0.0)),
             "xla_raw_bytes": float(xla_cost.get("bytes accessed", 0.0))}
    dominant = max(("compute_s", "memory_s", "collective_s"),
                   key=lambda k: terms[k])
    terms["dominant"] = dominant
    if model_flops:
        terms["model_flops"] = model_flops
        global_hlo = flops * chips
        terms["model_flops_ratio"] = (model_flops / global_hlo
                                      if global_hlo else 0.0)
    return terms


def _breakdown(collectives: List[CollectiveOp]) -> Dict[str, Dict]:
    out: Dict[str, Dict] = {}
    for op in collectives:
        d = out.setdefault(op.kind, {"count": 0, "operand_bytes": 0,
                                     "traffic_bytes": 0})
        d["count"] += 1
        d["operand_bytes"] += op.operand_bytes
        d["traffic_bytes"] += op.ici_traffic_bytes
    return out


def train_model_flops(param_count: int, active_param_count: int,
                      tokens: int) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE)."""
    return 6.0 * active_param_count * tokens


def decode_model_flops(active_param_count: int, batch: int) -> float:
    """One decode step: 2 N_active per token."""
    return 2.0 * active_param_count * batch


def format_table(rows: List[Dict]) -> str:
    hdr = (f"{'arch':24s} {'shape':12s} {'mesh':10s} {'compute_s':>10s} "
           f"{'memory_s':>10s} {'coll_s':>10s} {'dominant':>12s} "
           f"{'useful%':>8s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        t = r["terms"]
        useful = t.get("model_flops_ratio", 0.0) * 100
        lines.append(
            f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:10s} "
            f"{t['compute_s']:10.4f} {t['memory_s']:10.4f} "
            f"{t['collective_s']:10.4f} {t['dominant']:>12s} {useful:8.1f}")
    return "\n".join(lines)
