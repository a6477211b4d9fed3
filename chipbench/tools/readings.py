"""Readings that set a cell's limits: the program's compared number and
the control's on the same sample, for many seeds in one process (set-up is
paid once per seed, compilation once).

    python3 chipbench/tools/readings.py --workload survey-uniform \\
        --seeds 101,102,103 --seconds 5 [--control 1]

One JSON line per seed on stdout: the seed, every check's value (the
control's as ``<check>.control``), attempted and failed.  Needs the chip,
like the benchmark.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=1)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from chipbench import harness
    from chipbench.peaks import peaks_for

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = harness.load_benchmark(ROOT)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = json.loads((harness.HERE / "configs" / f"{cell['config']}.json").read_text())
    mix = json.loads((harness.HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    driver = harness.load_module(harness.HERE / "drivers" / f"{config['driver']}.py")
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        raise SystemExit("needs the chips the cell asks for")
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell=cell, config=config, mix=mix, seed=seed,
                          seconds=args.seconds, trace=False, devices=devices,
                          peaks=peaks_for(devices[0].device_kind),
                          t_start=time.perf_counter(),
                          work_dir=ROOT / ".chipbench_run",
                          control=bool(args.control))
        out = driver.run(run)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "attempted": out.attempted, "failed": out.failed,
                          "setup_s": out.setup_s, "e2e": out.e2e,
                          **{c.name: c.value for c in out.checks}}), flush=True)


if __name__ == "__main__":
    main()
