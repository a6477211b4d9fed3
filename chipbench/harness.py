"""What every cell shares: the benchmark file, the device, the files found
by name, the traced window, the per-layer readers and the result line.

A cell names a configuration and a traffic mix.  The configuration is
``configs/<config>.json`` (sizes, the driver that runs it, the limits of
its comparison) with its plain reference ``configs/<config>_ref.py``
beside it; the mix is ``traffic/<traffic>.json``; each per-layer metric
is ``metrics/<metric>.py`` with a ``read(ctx)`` that returns a number or
None.  The driver ``drivers/<driver>.py`` runs the cell.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

__all__ = ["HERE", "ROOT", "Check", "Outcome", "Run", "load_module", "span",
           "load_benchmark", "per_layer_for", "end_to_end_for"]


def load_module(path: Path, name: str | None = None):
    """Import a file by path (file names may hold '-' and '.')."""
    name = name or "chipbench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def end_to_end_for(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell)]


def per_layer_for(bench: dict, cell: str) -> list[dict]:
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if _applies(m, cell) and m["moves"] in e2e]


@dataclass
class Check:
    """One number compared with its limit: passes when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back to the harness."""

    setup_s: float
    e2e: dict[str, float]
    attempted: int
    failed: int
    checks: list[Check]
    memory_peak_bytes: int
    layer_ctx: dict = field(default_factory=dict)
    trace: object | None = None  # trace_reduce.TraceSummary


@dataclass
class Run:
    """Everything a driver needs about the run it makes."""

    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    t_start: float  # perf_counter at process start
    work_dir: Path
    # Also read the control (the reference one precision down) on the
    # same sample, as extra checks named "<check>.control".  Only the
    # readings tool sets it; the benchmark's runs never do.
    control: bool = False

    def reference(self):
        return load_module(HERE / "configs" / f"{self.cell['config']}_ref.py")

    @property
    def chips(self) -> int:
        return self.cell["chips"]

    def log(self, msg: str) -> None:
        print(f"[chipbench] {msg}", file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def traced(self):
        """Profile the block when this is a ``--trace 1`` run; yields a
        one-item list that holds the TraceSummary once the block ends."""
        import jax

        box: list = [None]
        if not self.trace:
            yield box
            return
        tdir = self.work_dir / "trace"
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("chipbench.window"):
                yield box
        finally:
            jax.profiler.stop_trace()
        box[0] = tdir

    def memory_peak(self) -> int:
        peaks = []
        for d in self.devices[: self.chips]:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)


def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(f"chipbench.{name}")
