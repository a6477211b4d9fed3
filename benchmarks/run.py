"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--seeds N] [--fast] [--out-dir D]

Prints ``name,us_per_call,derived`` CSV lines per benchmark, and records
each benchmark's returned result object to ``BENCH_<name>.json`` under
``--out-dir`` (default: the working directory) — the machine-readable perf
trajectory CI archives per commit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _jsonable(obj):
    """Best-effort conversion to JSON-serialisable structures (tuple-keyed
    dicts become "a|b|c" keys; numpy scalars become floats)."""
    if isinstance(obj, dict):
        return {
            "|".join(str(p) for p in k) if isinstance(k, tuple) else str(k):
                _jsonable(v)
            for k, v in obj.items()
        }
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "item") and not isinstance(obj, (str, bytes)):
        try:
            return obj.item()
        except (TypeError, ValueError):
            pass
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return str(obj)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--fast", action="store_true", help="seeds=1, smaller cells")
    ap.add_argument(
        "--only", default="", help="comma-separated benchmark names"
    )
    ap.add_argument(
        "--out-dir", default=".",
        help="directory for the BENCH_<name>.json result records",
    )
    args = ap.parse_args()
    seeds = 1 if args.fast else args.seeds

    from . import (
        elastic,
        fig4_radius,
        fig5_tasks,
        hierarchy,
        limplock,
        netfault,
        open_arrival,
        placement_ablation,
        policy_matrix,
        sched_micro,
        slo_trace,
        table3_lw,
        table4_ctws,
        topology,
        weighted,
    )
    # (benchmarks/common.py is the only unregistered module — shared
    # helpers, not a benchmark.)

    benches = {
        "fig4": lambda: fig4_radius.run(seeds=seeds),
        "table3": lambda: table3_lw.run(seeds=seeds),
        "table4": lambda: table4_ctws.run(seeds=seeds),
        "fig5": lambda: fig5_tasks.run(),
        "placement": lambda: placement_ablation.run(seeds=seeds),
        "sched_micro": lambda: sched_micro.run(),
        "open_arrival": lambda: open_arrival.run(seeds=seeds),
        "policy_matrix": lambda: policy_matrix.run(seeds=seeds, fast=args.fast),
        "elastic": lambda: elastic.run(seeds=seeds, fast=args.fast),
        "weighted": lambda: weighted.run(seeds=seeds, fast=args.fast),
        "limplock": lambda: limplock.run(seeds=seeds, fast=args.fast),
        "netfault": lambda: netfault.run(seeds=seeds, fast=args.fast),
        "slo_trace": lambda: slo_trace.run(seeds=1, fast=args.fast),
        "hierarchy": lambda: hierarchy.run(seeds=seeds, fast=args.fast),
        "topology": lambda: topology.run(seeds=seeds, fast=args.fast),
    }
    only = set(args.only.split(",")) if args.only else None
    os.makedirs(args.out_dir, exist_ok=True)
    t0 = time.time()
    for name, fn in benches.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", flush=True)
        try:
            result = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name}_FAILED,0,{type(e).__name__}: {e}", file=sys.stderr)
            raise
        if result is not None:
            path = os.path.join(args.out_dir, f"BENCH_{name}.json")
            with open(path, "w") as fh:
                json.dump(_jsonable(result), fh, indent=2, sort_keys=True)
            print(f"# wrote {path}", flush=True)
    print(f"# done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
