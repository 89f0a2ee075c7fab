import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on the
production meshes, record memory/cost analysis + roofline terms.

Usage:
  python -m repro.launch.dryrun --arch llama3_8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--jobs 4] [--mesh both]
  python -m repro.launch.dryrun --dvnr --mesh both        # the paper's own cells

Results land in results/dryrun/<mesh>/<arch>__<shape>.json; EXPERIMENTS.md
sections are generated from these by benchmarks/roofline.py.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run_cell(arch: str, shape: str, mesh_name: str, moe_dispatch: str = "scatter",
             out_dir: Path = RESULTS) -> dict:
    import jax
    from repro.configs import cell_is_applicable
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_production_mesh
    from repro.utils.hlo import analyze_hlo
    from repro.utils import hw

    ok, reason = cell_is_applicable(arch, shape)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "moe_dispatch": moe_dispatch}
    if not ok:
        rec.update(status="skipped", reason=reason)
        _save(rec, out_dir, mesh_name, arch, shape)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, moe_dispatch=moe_dispatch)
    with mesh:
        jitted = jax.jit(cell.fn,
                         in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate_argnums)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    an = analyze_hlo(hlo, mesh.size)

    n = mesh.size
    # the dry run models the v5e it is sized for, whatever device it runs on
    chip = hw.peaks(hw.V5E)
    terms = {
        "compute_s": an.flops / chip.bf16_flops,
        "memory_s": an.hbm_bytes / chip.hbm_bytes_per_s,
        "collective_s": an.collective_wire_bytes / chip.ici_link_bytes_per_s,
    }
    dominant = max(terms, key=terms.get)
    model_flops_per_dev = cell.meta["model_flops_global"] / n
    rec.update(
        status="ok",
        devices=n,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory_analysis={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            "alias_bytes": mem.alias_size_in_bytes,
        } if mem is not None else None,
        cost_analysis={"flops": cost.get("flops"),
                       "bytes_accessed": cost.get("bytes accessed")} if cost else None,
        hlo_flops_per_device=an.flops,
        hlo_bytes_per_device=an.hbm_bytes,
        collective_wire_bytes_per_device=an.collective_wire_bytes,
        collective_breakdown=an.collective_summary(),
        roofline=dict(terms, dominant=dominant,
                      step_time_s=max(terms.values()),
                      roofline_fraction=(
                          model_flops_per_dev / chip.bf16_flops / max(max(terms.values()), 1e-30))),
        model_flops_global=cell.meta["model_flops_global"],
        model_flops_per_device=model_flops_per_dev,
        useful_flops_ratio=model_flops_per_dev / max(an.flops, 1.0),
        params=cell.meta["params"],
        active_params=cell.meta["active_params"],
    )
    _save(rec, out_dir, mesh_name, arch, shape)
    return rec


def _save(rec: dict, out_dir: Path, mesh_name: str, arch: str, shape: str):
    d = out_dir / mesh_name
    d.mkdir(parents=True, exist_ok=True)
    (d / f"{arch}__{shape}.json").write_text(json.dumps(rec, indent=1))


def _run_all(meshes, jobs: int, archs, shapes, moe_dispatch):
    """Spawn one subprocess per cell (isolation against per-cell OOM/failures)."""
    cells = [(a, s, m) for m in meshes for a in archs for s in shapes]
    procs: list = []
    failures = []
    done = 0

    def launch(a, s, m):
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", a, "--shape", s, "--mesh", m,
               "--moe-dispatch", moe_dispatch]
        return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True), (a, s, m)

    pending = list(cells)
    while pending or procs:
        while pending and len(procs) < jobs:
            procs.append(launch(*pending.pop(0)))
        for i, (p, key) in enumerate(procs):
            if p.poll() is not None:
                out = p.stdout.read()
                done += 1
                status = "ok" if p.returncode == 0 else "FAIL"
                print(f"[{done}/{len(cells)}] {key} -> {status}", flush=True)
                if p.returncode != 0:
                    failures.append((key, out[-2500:]))
                procs.pop(i)
                break
        else:
            time.sleep(0.5)
    for key, out in failures:
        print(f"\n=== FAILURE {key} ===\n{out}")
    return len(failures)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--dvnr", action="store_true", help="run the DVNR (paper) cells")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--moe-dispatch", default="scatter",
                    choices=["scatter", "a2a", "scatter_global", "scatter_gspmd"])
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.dvnr:
        from repro.core.dryrun_cells import run_dvnr_cell
        for m in meshes:
            for kind in ("train", "render"):
                rec = run_dvnr_cell(kind, m, RESULTS)
                print(json.dumps({k: rec[k] for k in ("arch", "shape", "mesh", "status")}))
        return

    if args.all:
        from repro.configs import ARCH_IDS, SHAPES
        rc = _run_all(meshes, args.jobs, list(ARCH_IDS), list(SHAPES), args.moe_dispatch)
        sys.exit(1 if rc else 0)

    assert args.arch and args.shape, "--arch and --shape required (or --all)"
    for m in meshes:
        rec = run_cell(args.arch, args.shape, m, args.moe_dispatch)
        print(json.dumps({k: v for k, v in rec.items()
                          if k in ("arch", "shape", "mesh", "status", "compile_s",
                                   "roofline", "reason")}, indent=1))


if __name__ == "__main__":
    main()
