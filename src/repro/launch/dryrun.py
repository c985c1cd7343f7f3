import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and record memory / cost / roofline data.

  single-pod mesh: (data=16, model=16)        = 256 chips
  multi-pod mesh:  (pod=2, data=16, model=16) = 512 chips

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape decode_32k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out experiments/dryrun]

Each cell writes <out>/<arch>__<shape>__<mesh>.json incrementally, so the
sweep is resumable. Shape->step mapping: train_4k -> train_step,
prefill_32k -> prefill_step (INT8 path), decode_*/long_* -> serve decode
step (EVA VQ path). long_500k runs only for sub-quadratic archs
(DESIGN.md §4).
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as steps_mod
from repro.models.api import Model, SHAPES, build_model
from repro.core.plan import PlanPolicy
from repro.models.common import RunConfig
from repro.roofline.analysis import analyze_compiled, model_flops
from repro.core.vq import VQWeight


def fc_param_counts(model: Model) -> Dict[str, float]:
    """Analytic FC-parameter counts (total and decode-active) from specs."""
    specs = model.param_specs()
    cfg = model.cfg
    total = 0.0
    active = 0.0

    def walk(node, path):
        nonlocal total, active
        if isinstance(node, dict):
            if "w" in node and hasattr(node["w"], "ndim") and node["w"].ndim >= 2:
                sz = float(np.prod(node["w"].shape))
                total += sz
                if "experts" in path and cfg.num_experts:
                    active += sz * cfg.top_k / cfg.num_experts
                else:
                    active += sz
                return
            for k, v in node.items():
                walk(v, path + (k,))

    walk(specs, ())
    return {"total_fc": total, "active_fc": active}


def run_cell(arch: str, shape: str, mesh_kind: str, out_dir: str,
             *, vq_mode: str = "eva", tag: str = "",
             rc_overrides: Optional[Dict[str, Any]] = None,
             serve_step: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    model = build_model(cfg)
    mesh_name = "pod2" if mesh_kind == "multi" else "pod1"
    suffix = f"__{tag}" if tag else ""
    out_path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}{suffix}.json")
    if os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)

    result: Dict[str, Any] = {"arch": arch, "shape": shape, "mesh": mesh_name,
                              "tag": tag, "status": "pending"}
    if not model.supports_shape(shape):
        result["status"] = "skipped"
        result["reason"] = ("long_500k requires sub-quadratic attention; "
                            "skipped per DESIGN.md §4")
        _write(out_path, result)
        return result

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        ov = dict(rc_overrides or {})
        q_lm_head = ov.pop("quantize_lm_head", False)
        kind, specs = model.input_specs(
            shape, kv_int8=ov.get("kv_cache_int8", False),
            kv_int4=ov.get("kv_cache_int4", False))

        # execution knobs live inside PlanPolicy; any policy-level
        # override in rc_overrides is split out of the flat RunConfig kw
        pol_kw = {f: ov.pop(f) for f in
                  ("vq_mode", "impl", "epilogue", "block_v", "int8_prefill",
                   "interpret") if f in ov}
        if kind == "train":
            rc = RunConfig(mode="train", remat=True, attn_chunk=2048,
                           plan_policy=PlanPolicy(**pol_kw), **ov)
            lowered = steps_mod.lower_train_step(model, mesh, specs, rc)
        elif kind == "prefill":
            pol_kw.setdefault("int8_prefill", True)
            rc = RunConfig(mode="prefill", remat=False, attn_chunk=2048,
                           plan_policy=PlanPolicy(**pol_kw), **ov)
            lowered = steps_mod.lower_prefill_step(model, mesh, specs, rc,
                                                   quantized=True)
        else:
            pol_kw.setdefault("vq_mode", vq_mode)
            rc = RunConfig(mode="decode", remat=False,
                           plan_policy=PlanPolicy(**pol_kw), **ov)
            if serve_step:
                # the FULL serving decode step (in-jit sampling/stopping,
                # host reads back only (next_tok, done)) — what the
                # request-level engine actually lowers in production
                result["serve_step"] = True
                lowered = steps_mod.lower_serve_decode_step(
                    model, mesh, specs, rc, quantized=True, vq_mode=vq_mode,
                    quantize_lm_head=q_lm_head)
            else:
                lowered = steps_mod.lower_decode_step(
                    model, mesh, specs, rc, quantized=True, vq_mode=vq_mode,
                    quantize_lm_head=q_lm_head)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        seq, gb, _ = SHAPES[shape]
        counts = fc_param_counts(model)
        mf = model_flops(cfg, kind, seq, gb, counts["total_fc"],
                         counts["active_fc"])
        cache_bytes_dev = 0.0
        if kind == "decode":
            cache_bytes_dev = sum(
                float(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(specs["caches"])
            ) / chips
        report = analyze_compiled(
            compiled, arch=arch, shape=shape, mesh_name=mesh_name,
            chips=chips, model_flops=mf, step_kind=kind,
            cache_bytes_per_device=cache_bytes_dev,
        )
        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):  # one entry per program
            ca = ca[0] if ca else {}
        result.update({
            "status": "ok",
            "chips": chips,
            "step_kind": kind,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory_analysis": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "peak_bytes_estimate": mem.argument_size_in_bytes
                + mem.output_size_in_bytes + mem.temp_size_in_bytes,
            },
            "cost_analysis_flops_single_visit": float(ca.get("flops", -1.0)),
            "roofline": report.to_dict(),
            "fc_params": counts,
        })
    except Exception as e:
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    result["wall_s"] = round(time.time() - t0, 2)
    _write(out_path, result)
    return result


def _write(path: str, obj: Dict[str, Any]):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1, default=float)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--vq-mode", default="eva", choices=["eva", "dequant"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--serve-step", action="store_true",
                    help="lower decode cells as the full serving step "
                         "(in-jit sampling/stopping; serve/api.py)")
    args = ap.parse_args()
    if args.serve_step and not args.tag:
        args.tag = "servestep"  # keep plain-decode cells resumable

    archs = [a for a in ARCH_IDS if a != "llama2_7b"] if args.all or not args.arch \
        else [args.arch.replace("-", "_").replace(".", "_")]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    any_fail = False
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                r = run_cell(arch, shape, mk, args.out,
                             vq_mode=args.vq_mode, tag=args.tag,
                             serve_step=args.serve_step)
                line = (f"{arch:24s} {shape:12s} {r['mesh']:5s} "
                        f"{r['status']:8s}")
                if r["status"] == "ok":
                    rl = r["roofline"]
                    line += (f" wall={r['wall_s']:7.1f}s "
                             f"t_comp={rl['t_compute']*1e3:8.3f}ms "
                             f"t_mem={rl['t_memory']*1e3:8.3f}ms "
                             f"t_coll={rl['t_collective']*1e3:8.3f}ms "
                             f"bound={rl['bottleneck']}")
                elif r["status"] == "error":
                    line += f" {r['error'][:120]}"
                    any_fail = True
                print(line, flush=True)
    sys.exit(1 if any_fail else 0)


if __name__ == "__main__":
    main()
