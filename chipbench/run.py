"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up (``setup_s``), measures for ``--seconds``, checks what
the timed path produced against the plain reference, and prints one JSON
object as the last line of standard output.  With ``--trace 1`` the
metrics are the cell's per-layer ones, read from a profiler trace of part
of the window and from the scheduler's records.  There is no CPU path:
without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
WORK_DIR = ROOT / ".chipbench_run"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def fail(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def compose(bench: dict, cell: dict, out, devices, peaks: dict, trace: bool) -> dict:
    """The result line of a finished run; prints each check on stderr."""
    from chipbench import harness, trace_reduce

    kind = devices[0].device_kind
    result = {"correct": all(c.ok for c in out.checks) and out.failed == 0,
              "attempted": out.attempted, "failed": out.failed}
    metrics = {}
    if trace:
        summary = out.trace
        if isinstance(summary, (str, Path)):
            summary = trace_reduce.reduce_file(trace_reduce.find_xplane(str(summary)))
        print(f"[chipbench] traced programs {summary.module_s} counts "
              f"{summary.module_n}", file=sys.stderr, flush=True)
        ctx = dict(out.layer_ctx, trace=summary, peaks=peaks,
                   chips=cell["chips"])
        for m in harness.per_layer_for(bench, cell["name"]):
            reader = harness.load_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = dict(out.e2e, setup_s=out.setup_s)
        for m in harness.end_to_end_for(bench, cell["name"]):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = {"platform": devices[0].platform, "kind": kind,
                        "count": len(devices),
                        "memory_peak_bytes": out.memory_peak_bytes}
    if trace:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    for c in out.checks:
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> None:
    args = parse(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # The persistent compile cache lives at a fixed path inside the
    # checkout; the program's own cache helper takes it from here.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)

    from chipbench import harness
    from chipbench.peaks import peaks_for

    try:
        bench = harness.load_benchmark(ROOT)
    except FileNotFoundError:
        fail("no BENCHMARK.json at the checkout root")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        fail(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
    cell = cells[args.workload]
    cfg_path = HERE / "configs" / f"{cell['config']}.json"
    mix_path = HERE / "traffic" / f"{cell['traffic']}.json"
    config = json.loads(cfg_path.read_text())
    mix = json.loads(mix_path.read_text())
    driver = harness.load_module(HERE / "drivers" / f"{config['driver']}.py")

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    kind = devices[0].device_kind
    print(f"[chipbench] device platform={devices[0].platform} kind={kind} "
          f"count={len(devices)} jax={jax.__version__}", file=sys.stderr,
          flush=True)
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform!r}; there is no CPU path")
    if len(devices) < cell["chips"]:
        fail(f"cell {cell['name']} needs {cell['chips']} chips, found {len(devices)}")
    peaks = peaks_for(kind)

    WORK_DIR.mkdir(exist_ok=True)
    run = harness.Run(cell=cell, config=config, mix=mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, peaks=peaks, t_start=T_START,
                      work_dir=WORK_DIR)
    out = driver.run(run)

    result = compose(bench, cell, out, devices, peaks, bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
