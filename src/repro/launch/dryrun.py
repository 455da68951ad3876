import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape x
mesh) combination with ShapeDtypeStruct stand-ins (no allocation), then
extract the three-term roofline from the compiled per-device HLO.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
        --shape decode_32k [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import time
import traceback

import jax

from jax import set_mesh
from repro.configs import ASSIGNED
from repro.core.analysis import (HloCensus, cpu_upcast_artifact_bytes,
                                 memory_from_compiled)
from repro.core.hardware import TPU_V5E
from repro.core.roofline import roofline_report
from repro.launch.input_specs import SHAPES, SkipCase, build_case
from repro.launch.mesh import make_production_mesh, mesh_chips


def run_case(arch: str, shape: str, multi_pod: bool, out_dir: str,
             verbose: bool = True, variant: str = None) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "status": "?",
           "variant": variant or "baseline"}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        # arctic's 468B params can't host fp32 AdamW moments at these chip
        # counts (3.7TB): bf16 moments (documented trade-off in DESIGN.md)
        moment = "bfloat16" if arch == "arctic-480b" else "float32"
        case = build_case(arch, shape, mesh, moment_dtype=moment,
                          variant=variant)
        with set_mesh(mesh):
            jitted = jax.jit(case.fn, in_shardings=case.in_shardings,
                             out_shardings=case.out_shardings,
                             donate_argnums=case.donate)
            lowered = jitted.lower(*case.args_sds)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        mem = memory_from_compiled(compiled)
        hlo_text = compiled.as_text()
        artifact = cpu_upcast_artifact_bytes(hlo_text)
        mem["cpu_upcast_artifact_bytes"] = artifact
        mem["peak_bytes_tpu_adjusted"] = mem["peak_bytes"] - artifact
        census = HloCensus(hlo_text).census()
        rep = roofline_report(
            census, TPU_V5E, arch=arch, shape=shape, mesh=mesh_name,
            chips=mesh_chips(mesh), model_flops=case.model_flops,
            memory_bytes_per_chip=mem["peak_bytes"])
        rec.update(
            status="ok", lower_s=round(t1 - t0, 1),
            compile_s=round(t2 - t1, 1),
            memory=mem,
            flops_per_chip=census.flops, bytes_per_chip=census.bytes,
            coll_bytes_per_chip=census.coll_bytes,
            per_collective=census.per_collective,
            compute_s=rep.compute_s, memory_s=rep.memory_s,
            collective_s=rep.collective_s, dominant=rep.dominant,
            model_flops=rep.model_flops, useful_ratio=rep.useful_ratio,
            per_class_ai=rep.per_class_ai,
            per_class_terms=rep.per_class_terms,
            moment_dtype=moment,
            fits_hbm=mem["peak_bytes_tpu_adjusted"] <= TPU_V5E.hbm_bytes,
            fits_hbm_raw=mem["peak_bytes"] <= TPU_V5E.hbm_bytes,
        )
        if verbose:
            print(f"[{arch} x {shape} x {mesh_name}] OK "
                  f"compile={rec['compile_s']}s "
                  f"mem/chip={mem['peak_bytes']/1e9:.2f}GB "
                  f"(tpu-adj {mem['peak_bytes_tpu_adjusted']/1e9:.2f}GB) "
                  f"terms(ms): C={rep.compute_s*1e3:.2f} "
                  f"M={rep.memory_s*1e3:.2f} X={rep.collective_s*1e3:.2f} "
                  f"-> {rep.dominant}", flush=True)
    except SkipCase as e:
        rec.update(status="skip", reason=str(e))
        if verbose:
            print(f"[{arch} x {shape} x {mesh_name}] SKIP: {e}", flush=True)
    except Exception as e:  # noqa
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-3000:])
        if verbose:
            print(f"[{arch} x {shape} x {mesh_name}] ERROR: "
                  f"{type(e).__name__}: {str(e)[:300]}", flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{variant}" if variant else ""
        fn = os.path.join(out_dir,
                          f"{arch}__{shape}__{mesh_name}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1, default=float)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="§Perf hillclimb variant (see input_specs.VARIANTS)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        archs = list(ASSIGNED)
        shapes = list(SHAPES)
        meshes = [False, True]
    else:
        archs = [args.arch]
        shapes = [args.shape] if args.shape else list(SHAPES)
        meshes = [args.multi_pod] if not args.both_meshes else [False, True]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_case(arch, shape, mp, args.out,
                               variant=args.variant)
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"] == "skip"
                n_err += rec["status"] == "error"
    print(f"done: {n_ok} ok, {n_skip} skip, {n_err} error", flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
