"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combination
on 512 placeholder host devices, record memory/cost/collective analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape decode_32k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi --out results.json
"""
# The VERY FIRST lines, before ANY other import: jax locks the device count
# on first init.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_DRYRUN_XLA_FLAGS") or
                           "--xla_force_host_platform_device_count=512")

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.config import (ARCH_IDS, SHAPES, get_arch, get_shape,  # noqa: E402
                          model_for_shape)
from repro.launch import steps as steps_mod                        # noqa: E402
from repro.launch.hlo_analysis import analyze_compiled             # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_axes      # noqa: E402
from repro.models import dist                                      # noqa: E402


def run_one(arch: str, shape_name: str, multi_pod: bool,
            parallelism: str = "sequence_parallel",
            algorithm: str = "shvs", verbose: bool = True) -> dict:
    """Lower + compile one combination; return the roofline record."""
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    batch_axes, model_axes = mesh_axes(mesh)
    from repro.launch.sharding import batch_axes_for
    eff_batch = batch_axes_for(shape, mesh)

    t0 = time.perf_counter()
    with dist.use_mesh(mesh, batch_axes=eff_batch, model_axes=model_axes):
        make = steps_mod.program_for(shape.kind)
        if shape.kind == "decode":
            fn, a_in, in_sh, out_sh, _ = make(cfg, shape, mesh,
                                              parallelism=parallelism,
                                              algorithm=algorithm)
        elif shape.kind == "prefill":
            fn, a_in, in_sh, out_sh, _ = make(cfg, shape, mesh,
                                              parallelism=parallelism)
        else:
            fn, a_in, in_sh, out_sh, _ = make(cfg, shape, mesh)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*a_in)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    name = f"{arch}|{shape_name}|{'2x16x16' if multi_pod else '16x16'}|{parallelism}|{algorithm}"
    hlo = compiled.as_text()
    roof = analyze_compiled(name, compiled, hlo, mesh.size,
                            model_for_shape(cfg, shape), shape)
    rec = roof.row()
    rec.update({
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "parallelism": parallelism, "algorithm": algorithm,
        "lower_s": t_lower, "compile_s": t_compile,
        "collective_counts": roof.collectives.count_by_kind,
        "collective_bytes_by_kind": roof.collectives.bytes_by_kind,
        "status": "ok",
    })
    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: float(getattr(ma, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(ma, k)}
    except Exception as e:  # CPU backend may not implement it
        rec["memory_analysis"] = {"unavailable": str(e)}
    if verbose:
        print(f"[ok] {name}: compute={rec['compute_s']:.3e}s "
              f"memory={rec['memory_s']:.3e}s coll={rec['collective_s']:.3e}s "
              f"bottleneck={rec['bottleneck']} "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s)", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--parallelism", default="sequence_parallel",
                    choices=("sequence_parallel", "vocab_gather",
                             "hierarchical"))
    ap.add_argument("--algorithm", default="shvs",
                    choices=("shvs", "reference"))
    ap.add_argument("--all", action="store_true",
                    help="every (arch × shape) combination")
    ap.add_argument("--out", default=None, help="append JSON records here")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    records = []
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = run_one(arch, shape, mp, args.parallelism,
                                  args.algorithm)
                except Exception as e:
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": repr(e)}
                    print(f"[FAIL] {arch}|{shape}|{rec['mesh']}: {e}",
                          flush=True)
                    traceback.print_exc()
                records.append(rec)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    ok = sum(1 for r in records if r.get("status") == "ok")
    print(f"\ndry-run complete: {ok}/{len(records)} ok, {failures} failures")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
