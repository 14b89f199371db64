import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede any jax import: jax locks the device count on first init.
# Smoke tests and benches do NOT get this (they see 1 device); only the
# dry-run builds the 256/512-chip production meshes.

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import ALL_ARCHS, SHAPES, get_config, runnable_cells  # noqa: E402
from repro.launch import hlo_cost  # noqa: E402
from repro.launch.mesh import dp_axes, make_production_mesh  # noqa: E402
from repro.launch.specs import cell_inputs, step_fn_for  # noqa: E402

from repro.planner.cost import MACHINES  # noqa: E402

_CHIP = MACHINES["TPU v5 lite"]   # the production mesh's chip
PEAK_FLOPS = _CHIP.peak_flops
HBM_BW = _CHIP.hbm_bw
LINK_BW = _CHIP.link_bw


def run_cell(arch: str, shape: str, mesh_kind: str, *,
             causal_mode: str = "masked_full", out_dir: Path,
             tag: str = "baseline") -> dict:
    cfg = get_config(arch)
    cell = SHAPES[shape]
    skip = runnable_cells(cfg)[shape]
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind, "tag": tag,
           "causal_mode": causal_mode}
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    t0 = time.time()
    with mesh:
        kind, args = cell_inputs(cfg, cell, mesh)
        fn = step_fn_for(cfg, kind, mesh, causal_mode=causal_mode)
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        xla_cost = hlo_cost.normalize_cost_analysis(compiled.cost_analysis())
        cost = hlo_cost.analyze(compiled.as_text())

    tokens = cell.global_batch * (cell.seq_len if kind == "train" else
                                  cell.seq_len if kind == "prefill" else 1)
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    mult = 6 if kind == "train" else 2
    model_flops = mult * n_active * tokens
    per_dev = {
        "flops": cost.flops,
        "bytes": cost.bytes,
        "collective_bytes": cost.coll_bytes,
        "collectives": dict(cost.coll_detail),
    }
    terms = {
        "compute_s": cost.flops / PEAK_FLOPS,
        "memory_s": cost.bytes / HBM_BW,
        "collective_s": cost.coll_bytes / LINK_BW,
    }
    dom = max(terms, key=terms.get)
    rec.update({
        "status": "ok",
        "kind": kind,
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "total_per_device_bytes": (mem.argument_size_in_bytes +
                                       mem.temp_size_in_bytes),
        },
        "per_device": per_dev,
        "xla_cost_analysis_flops": xla_cost.get("flops"),
        "roofline": {
            **terms,
            "dominant": dom,
            "bound_s": max(terms.values()),
            "model_flops_total": model_flops,
            "model_flops_per_device": model_flops / chips,
            "useful_flops_ratio": (model_flops / chips) / max(cost.flops, 1),
            "roofline_fraction": (model_flops / chips / PEAK_FLOPS) /
            max(max(terms.values()), 1e-30),
        },
        "params": n_params,
        "active_params": n_active,
    })
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--causal-mode", default="masked_full")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = ALL_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else \
        [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                fname = out_dir / f"{args.tag}_{arch}_{shape}_{mesh_kind}.json"
                if fname.exists():
                    print(f"[dryrun] SKIP(existing) {fname.name}", flush=True)
                    continue
                print(f"[dryrun] {arch} x {shape} x {mesh_kind} ...",
                      flush=True)
                try:
                    rec = run_cell(arch, shape, mesh_kind,
                                   causal_mode=args.causal_mode,
                                   out_dir=out_dir, tag=args.tag)
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "tag": args.tag, "status": "error",
                           "error": repr(e),
                           "traceback": traceback.format_exc()[-3000:]}
                fname.write_text(json.dumps(rec, indent=1))
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_fail += st == "error"
                if st == "ok":
                    r = rec["roofline"]
                    print(f"  ok compile={rec['compile_s']}s "
                          f"mem/dev={rec['memory']['total_per_device_bytes']/2**30:.2f}GiB "
                          f"dominant={r['dominant']} "
                          f"roofline_frac={r['roofline_fraction']:.3f}",
                          flush=True)
                else:
                    print(f"  {st}: {rec.get('reason', rec.get('error'))}"[:300],
                          flush=True)
    print(f"[dryrun] done ok={n_ok} skipped={n_skip} failed={n_fail}",
          flush=True)
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
