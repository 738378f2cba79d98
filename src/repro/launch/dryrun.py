import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: `.lower().compile()` must succeed on the single-pod 16x16 mesh
and the 2-pod (2,16,16) mesh for every assigned architecture x input
shape, plus the SBV GP runtime cells. For each cell we record
``memory_analysis()`` (fits-in-HBM evidence) and ``cost_analysis()`` +
parsed collective bytes (the §Roofline inputs) into a JSON results file.

Usage:
    python -m repro.launch.dryrun                       # all cells, both meshes
    python -m repro.launch.dryrun --arch gemma2-9b      # one arch
    python -m repro.launch.dryrun --shape train_4k --mesh pod
    python -m repro.launch.dryrun --out results.json --resume
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.analysis.hlo_analysis import analyze_compiled, model_flops, roofline
from repro.configs import ARCHS, SHAPES, applicable, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SBV_GP_SHAPES, build_cell

MESHES = {"pod": False, "multipod": True}


def run_cell(arch: str, shape_name: str, mesh_name: str, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=MESHES[mesh_name])
    step, args, in_sh, out_sh, donate = build_cell(arch, shape_name, mesh)

    t0 = time.time()
    jitted = jax.jit(
        step, in_shardings=in_sh, out_shardings=out_sh,
        donate_argnums=donate or None,
    )
    with jax.set_mesh(mesh):  # activates activation-sharding constraints
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    if arch == "sbv-gp":
        spec = SBV_GP_SHAPES[shape_name]
        # per-block flops: 2 chol (m^3/3, bs^3/3) + trsm (m^2 bs) + gemm (m bs^2)
        m, bs = spec["m"], spec["bs"]
        bc = spec["n"] / bs
        mflops = bc * (m**3 / 3 + bs**3 / 3 + m * m * bs + m * bs * bs) * 2.0  # fwd+bwd ~2x
    else:
        mflops = model_flops(get_config(arch), SHAPES[shape_name])

    rep = analyze_compiled(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name,
        n_devices=mesh.size, mflops=mflops,
    )
    rep.extra = {"t_lower_s": t_lower, "t_compile_s": t_compile}
    if verbose:
        ma_line = (f"peak {rep.peak_memory/2**30:.2f} GiB/dev "
                   f"(args {rep.arg_bytes/2**30:.2f} + temp {rep.temp_bytes/2**30:.2f})")
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
              f"(lower {t_lower:.1f}s compile {t_compile:.1f}s) {ma_line}")
        print("         " + roofline(rep))
    return rep.to_dict()


def all_cells(archs=None, shapes=None, meshes=None):
    archs = archs or (list(ARCHS) + ["sbv-gp"])
    meshes = meshes or list(MESHES)
    for arch in archs:
        if arch == "sbv-gp":
            snames = shapes or list(SBV_GP_SHAPES)
            snames = [s for s in snames if s in SBV_GP_SHAPES]
        else:
            snames = shapes or list(SHAPES)
            snames = [s for s in snames if s in SHAPES]
        for sname in snames:
            if arch != "sbv-gp":
                ok, why = applicable(get_config(arch), sname)
                if not ok:
                    yield (arch, sname, None, {"skipped": why})
                    continue
            for mname in meshes:
                yield (arch, sname, mname, None)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--mesh", action="append", default=None, choices=list(MESHES))
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    results = {}
    if args.resume and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    failures = []
    for arch, sname, mname, skip in all_cells(args.arch, args.shape, args.mesh):
        if skip is not None:
            key = f"{arch}|{sname}|-"
            results[key] = {"arch": arch, "shape": sname, **skip}
            print(f"[dryrun] {arch} x {sname}: SKIP ({skip['skipped'][:60]}...)")
            continue
        key = f"{arch}|{sname}|{mname}"
        if args.resume and key in results and "error" not in results[key]:
            continue
        try:
            results[key] = run_cell(arch, sname, mname)
        except Exception as e:
            traceback.print_exc()
            results[key] = {"arch": arch, "shape": sname, "mesh": mname,
                            "error": f"{type(e).__name__}: {e}"}
            failures.append(key)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)

    n_ok = sum(1 for v in results.values() if "error" not in v and "skipped" not in v)
    n_skip = sum(1 for v in results.values() if "skipped" in v)
    print(f"\n[dryrun] {n_ok} cells OK, {n_skip} skipped, {len(failures)} FAILED -> {args.out}")
    if failures:
        print("FAILED:", failures)
        sys.exit(1)


if __name__ == "__main__":
    main()
