"""Multi-pod dry-run: lower and compile every (arch, shape, mesh) cell on
virtual CPU devices.  The device count locks at JAX's first init, so the
shell that starts it provides them:

  XLA_FLAGS=--xla_force_host_platform_device_count=512 JAX_PLATFORMS=cpu \
      PYTHONPATH=src python -m repro.launch.dryrun
"""
import argparse
import json
import os
import time
import traceback

import jax
import zstandard

from repro.configs import ARCH_IDS, get_config
from repro.launch.hlo_cost import analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import model_flops, roofline_terms
from repro.launch.steps import SHAPES, applicable_shapes, input_specs, rules_for, step_for

_DEVICES_NEEDED = 512   # the (2, 16, 16) multi-pod mesh


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str, *, overrides=None, tag=""):
    cfg = get_config(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    out_path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(out_path):
        print(f"[skip] {cell_id} (cached)", flush=True)
        return json.load(open(out_path))

    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.devices.size
        rules = rules_for(cfg, mesh, shape["kind"])
        step, donate = step_for(cfg, shape_name, rules)
        args = input_specs(cfg, shape_name, mesh, rules)

        with mesh:
            lowered = jax.jit(step, donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        # persist compressed HLO so terms can be re-derived without recompiling
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, cell_id + ".hlo.zst"), "wb") as f:
            f.write(zstandard.ZstdCompressor(level=3).compress(hlo.encode()))
        # trip-count-aware accounting (XLA cost_analysis visits while bodies
        # once; see launch/hlo_cost.py)
        acc = analyze(hlo)
        terms = roofline_terms(acc)
        mf = model_flops(cfg, shape)
        flops_global = terms["flops_per_dev"] * chips
        rec.update(
            status="ok",
            chips=chips,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory={
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(mem, k)
            },
            terms=terms,
            model_flops_global=mf,
            hlo_flops_global=flops_global,
            useful_flops_ratio=(mf / flops_global) if flops_global else 0.0,
            raw_cost_analysis={
                "flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
            },
            hlo_bytes=len(hlo),
        )
        print(
            f"[ok] {cell_id}: compile={t_compile:.0f}s dominant={terms['dominant']} "
            f"bound={terms['bound_s']*1e3:.2f}ms useful={rec['useful_flops_ratio']:.2f}",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[FAIL] {cell_id}: {type(e).__name__}: {e}", flush=True)

    os.makedirs(out_dir, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser(description="Multi-pod dry-run: lower+compile every cell")
    ap.add_argument("--arch", default=None, help="single arch id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES), help="single shape")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    if jax.device_count() < _DEVICES_NEEDED:
        raise SystemExit(
            f"dry-run needs {_DEVICES_NEEDED} devices, found "
            f"{jax.device_count()}: start it with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={_DEVICES_NEEDED}")

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = [args.shape] if args.shape else applicable_shapes(cfg)
        for shape_name in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape_name, multi, args.out)
                if rec["status"] == "ok":
                    n_ok += 1
                else:
                    n_fail += 1
    print(f"done: {n_ok} ok, {n_fail} failed", flush=True)
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
