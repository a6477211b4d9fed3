import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_FORCE_DEVICES", "512")
    + " " + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/collective analysis.

The two lines ABOVE the docstring must run before any jax import — jax locks
the device count on first init.  Smoke tests and benches do NOT import this
module, so they see the single real CPU device.

Usage:
    python -m repro.launch.dryrun --arch mistral-nemo-12b --shape train_4k
    python -m repro.launch.dryrun --all                  # single-pod 16x16
    python -m repro.launch.dryrun --all --multi-pod      # 2x16x16
Records land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch import compile_cache
from repro.launch.cells import analyze, lower_cell
from repro.launch.mesh import make_production_mesh
from repro.parallel.sharding import make_context

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    rec_path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}.json")
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "skipped", "reason": reason}
        _write(rec_path, rec)
        print(f"[skip] {arch} x {shape_name} ({mesh_tag}): {reason}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = make_context(mesh)
    chips = mesh.devices.size
    print(f"[cell] {arch} x {shape_name} on {mesh_tag} ({chips} chips)")
    try:
        with mesh:
            lowered, meta = lower_cell(cfg, shape, ctx)
            t0 = time.time()
            compiled = lowered.compile()
            meta["compile_s"] = round(time.time() - t0, 2)
            print(compiled.memory_analysis())   # proves it fits
            cost = compiled.cost_analysis()     # FLOPs/bytes for the roofline
            print({k: cost[k] for k in ("flops", "bytes accessed")
                   if k in cost})
            rec = analyze(lowered, compiled, cfg, shape, chips)
            rec.update({"mesh": mesh_tag, "status": "ok", **meta})
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
        print(f"[FAIL] {arch} x {shape_name}: {e}")
    _write(rec_path, rec)
    return rec


def _write(path: str, rec: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    compile_cache.enable()
    pods = [args.multi_pod] if not args.both_meshes else [False, True]
    cells_ = (
        [(a, s) for a in ARCH_IDS for s in SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    failures = 0
    for mp in pods:
        for arch, shape_name in cells_:
            tag = "2x16x16" if mp else "16x16"
            path = os.path.join(args.out, f"{arch}__{shape_name}__{tag}.json")
            if args.skip_existing and os.path.exists(path):
                rec = json.load(open(path))
                if rec.get("status") in ("ok", "skipped"):
                    continue
            rec = run_cell(arch, shape_name, mp, args.out)
            failures += rec.get("status") == "error"
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
