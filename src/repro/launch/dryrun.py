import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh with 512 placeholder host devices, print
memory_analysis / cost_analysis, and dump roofline JSON.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json

The XLA_FLAGS line above MUST stay the first statement — jax locks the
device count on first initialisation.
"""

import argparse
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_spec
from repro.configs.shapes import SHAPES, covered_shapes
from repro.dist import sharding as shd
from repro.launch import hlo_cost
from repro.launch import roofline as rf
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh
from repro.optim import SGD

# microbatch counts for the train_4k shape (memory fit on 16 GB v5e;
# measured in EXPERIMENTS.md §Perf — the terms are flat in microbatch count
# while activation temp memory scales ~1/mb)
TRAIN_MICROBATCH = {
    "grok-1-314b": 8,
    "granite-20b": 4,
    "gemma2-27b": 4,
    "yi-9b": 4,
    "qwen2-vl-7b": 4,
    "granite-moe-3b-a800m": 4,
    "gemma-2b": 2,
    "recurrentgemma-2b": 2,
    "whisper-tiny": 2,
}


def _mesh_tag(multi_pod: bool) -> str:
    return "2x16x16" if multi_pod else "16x16"


def lower_one(arch: str, shape_name: str, *, multi_pod: bool,
              microbatch: Optional[int] = None,
              ablate: tuple = (),
              verbose: bool = True) -> Dict:
    """``ablate`` re-enables pre-optimization behaviour for §Perf baselines:
    "moe_sort" (GShard capacity dispatch), "ring_cache" (full-length local
    KV), "act_constraints" (no activation sharding annotations)."""
    import dataclasses as _dc
    spec = get_spec(arch)
    shape = SHAPES[shape_name]
    cfg = steps_lib.dryrun_config(spec.config, multi_pod=multi_pod)
    if "quantized_kv" in ablate:      # opt-IN feature, not an ablation
        cfg = _dc.replace(cfg, quantized_kv=True)
    if "moe_sort" in ablate:
        cfg = _dc.replace(cfg, moe_dispatch="capacity")
    if "ring_cache" in ablate:
        cfg = _dc.replace(cfg, local_ring_cache=False)
    if "act_constraints" in ablate:
        cfg = _dc.replace(cfg, batch_axes=())
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 1
    for n in mesh.axis_names:
        chips *= mesh.shape[n]

    t0 = time.time()
    param_shapes = steps_lib.param_specs(cfg)
    param_shardings = shd.params_shardings(param_shapes, mesh)
    data_specs = steps_lib.input_specs(arch, shape, cfg)

    def batch_shardings(specs):
        out = {}
        for k, v in specs.items():
            if v.ndim == 0:
                out[k] = shd.replicated(mesh)
            else:
                out[k] = shd.batch_sharding(v.shape[0], mesh, v.ndim - 1)
        return out

    with mesh:
        if shape.kind == "train":
            mb = microbatch if microbatch is not None else \
                TRAIN_MICROBATCH.get(arch, 1)
            step = steps_lib.make_train_step(cfg, remat=True, microbatch=mb)
            opt_shapes = jax.eval_shape(SGD(momentum=0.9).init, param_shapes)
            opt_shardings = shd.params_shardings(opt_shapes, mesh)
            jitted = jax.jit(step, in_shardings=(
                param_shardings, opt_shardings, batch_shardings(data_specs)),
                out_shardings=(param_shardings, opt_shardings, None))
            lowered = jitted.lower(param_shapes, opt_shapes, data_specs)
            tokens = shape.global_batch * shape.seq_len
        elif shape.kind == "prefill":
            step = steps_lib.make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(
                param_shardings, batch_shardings(data_specs)))
            lowered = jitted.lower(param_shapes, data_specs)
            tokens = shape.global_batch * shape.seq_len
        else:  # decode
            step = steps_lib.make_serve_step(cfg)
            cache_shapes = steps_lib.cache_specs(arch, shape, cfg)
            cache_shardings = shd.cache_shardings(cache_shapes, mesh)
            jitted = jax.jit(step, in_shardings=(
                param_shardings, cache_shardings, batch_shardings(data_specs)),
                out_shardings=(None, cache_shardings),
                donate_argnums=(1,))        # in-place cache update
            lowered = jitted.lower(param_shapes, cache_shapes, data_specs)
            tokens = shape.global_batch
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    mem = None
    try:
        mem = compiled.memory_analysis()
    except Exception:
        pass
    hlo = compiled.as_text()
    analysis = hlo_cost.analyze(hlo)

    if shape.kind == "train":
        model_flops = rf.train_model_flops(cfg.param_count(),
                                           cfg.active_param_count(), tokens)
    elif shape.kind == "prefill":
        model_flops = 2.0 * cfg.active_param_count() * tokens
    else:
        model_flops = rf.decode_model_flops(cfg.active_param_count(), tokens)

    # the production mesh models a v5e pod
    terms = rf.roofline_terms(analysis, cost, chips=chips,
                              device_kind="TPU v5 lite",
                              model_flops=model_flops)
    result = {
        "arch": arch, "shape": shape_name, "mesh": _mesh_tag(multi_pod),
        "kind": shape.kind, "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "terms": terms,
        "memory_analysis": _mem_dict(mem),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {_mesh_tag(multi_pod)}] "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
        if mem is not None:
            print(f"  memory_analysis: {_mem_dict(mem)}")
        print(f"  cost_analysis: flops={terms['hlo_flops_per_device']:.3e} "
              f"bytes={terms['hlo_bytes_per_device']:.3e} (per device)")
        print(f"  roofline: compute {terms['compute_s']:.4f}s | memory "
              f"{terms['memory_s']:.4f}s | collective "
              f"{terms['collective_s']:.4f}s -> dominant {terms['dominant']}"
              f" | useful-flops ratio "
              f"{terms.get('model_flops_ratio', 0):.3f}")
    return result


def _mem_dict(mem) -> Optional[Dict]:
    if mem is None:
        return None
    out = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    return out or {"repr": str(mem)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run single-pod AND multi-pod")
    ap.add_argument("--all", action="store_true",
                    help="every covered (arch x shape)")
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--ablate", default="",
                    help="comma list: moe_sort,ring_cache,act_constraints")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args(argv)

    combos = []
    if args.all:
        for arch, spec in ARCHS.items():
            for shape in covered_shapes(spec):
                combos.append((arch, shape.name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        combos = [(args.arch, args.shape)]

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    results, failures = [], []
    for arch, shape in combos:
        for mp in meshes:
            try:
                results.append(lower_one(
                    arch, shape, multi_pod=mp, microbatch=args.microbatch,
                    ablate=tuple(filter(None, args.ablate.split(",")))))
            except Exception as e:  # noqa: BLE001 — report, keep going
                traceback.print_exc()
                failures.append({"arch": arch, "shape": shape,
                                 "mesh": _mesh_tag(mp), "error": repr(e)})

    if results:
        print()
        print(rf.format_table(results))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"results": results, "failures": failures}, f, indent=1)
        print(f"\nwrote {args.out}")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f_ in failures:
            print(f"  {f_['arch']} x {f_['shape']} x {f_['mesh']}: "
                  f"{f_['error']}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
