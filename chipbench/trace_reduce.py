"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time per
program and per kernel, and the longest idle gaps by what the host did.

Everything is read with ``jax.profiler.ProfileData`` and works in the
trace's own nanosecond clock, on which the host spans the benchmark
records (``jax.profiler.TraceAnnotation`` named ``chipbench.*``) and the
device's events lie together.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

__all__ = ["TraceSummary", "find_xplane", "merge", "reduce_trace", "reduce_file"]

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
# Ops that hold other ops (a loop and its body): left out of the breakdown,
# where their time would be counted twice.
CONTAINER = re.compile(r"^(while|conditional|call)[.\d]*$")


@dataclass
class TraceSummary:
    window_s: float  # length of the traced window
    busy_s: float  # union of device-op intervals, averaged over devices
    devices: int
    module_s: dict[str, float] = field(default_factory=dict)  # per program
    module_n: dict[str, float] = field(default_factory=dict)  # cut ones pro rata
    op_s: dict[str, float] = field(default_factory=dict)  # per op name
    op_n: dict[str, int] = field(default_factory=dict)
    gaps: list[tuple[str, float]] = field(default_factory=list)  # longest

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def time_of(self, table: str, needle: str) -> tuple[float, float]:
        """Seconds and count of the programs (``table="module"``) or ops
        (``"op"``) whose name contains ``needle``."""
        s = getattr(self, f"{table}_s")
        n = getattr(self, f"{table}_n")
        keys = [k for k in s if needle in k]
        return sum(s[k] for k in keys), sum(n[k] for k in keys)

    def time_of_program(self, name: str, most: int) -> tuple[float, float]:
        """Seconds and count of the compiled programs whose module name is
        exactly ``name`` (a program's key is ``name(fingerprint)``).  Raises
        when there is none, or more than ``most`` distinct ones: the name no
        longer finds the program it stands for, or finds others too."""
        keys = [k for k in self.module_s if k.split("(", 1)[0] == name]
        if not keys or len(keys) > most:
            raise ValueError(f"{len(keys)} programs named {name!r} in the trace, "
                             f"expected 1 to {most}; programs: {sorted(self.module_s)}")
        return sum(self.module_s[k] for k in keys), sum(self.module_n[k] for k in keys)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(((k, v) for k, v in self.op_s.items() if not CONTAINER.match(k)),
                     key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def short_name(name: str) -> str:
    """An op's event name is its whole HLO instruction; keep the name
    (``%fusion.79 = bf16[...] fusion(...)`` gives ``fusion.79``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def merge(intervals):
    """Union of [start, end) intervals, as a sorted disjoint list."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _host_spans(planes):
    spans = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    return spans


def _attribute(gap, spans) -> str:
    """The host span (other than the window itself) that covers most of
    the gap, or ``"host: untraced"``."""
    best, best_ov = "host: untraced", 0.0
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce_trace(pd) -> TraceSummary:
    """Reduce a loaded ``ProfileData``.  The window is the host span
    ``chipbench.window``; without one, the span of all device events."""
    planes = list(pd.planes)
    spans = _host_spans(planes)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    devs = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    if not devs:
        raise ValueError("trace has no TPU device plane")
    per_dev_ops = []
    module_s: dict[str, float] = {}
    module_n: dict[str, float] = {}
    op_s: dict[str, float] = {}
    op_n: dict[str, int] = {}
    for p in devs:
        ops, mods = [], []
        for line in p.lines:
            evs = [(short_name(ev.name), ev.start_ns, ev.end_ns) for ev in line.events]
            if line.name == "XLA Ops":
                ops += evs
            elif line.name == "XLA Modules":
                mods += evs
        per_dev_ops.append((ops or mods, mods))
    if win:
        lo, hi = win[0]
    else:
        starts = [s for ops, _ in per_dev_ops for _, s, _ in ops]
        ends = [e for ops, _ in per_dev_ops for _, _, e in ops]
        lo, hi = min(starts), max(ends)
    busy_ns = 0.0
    gaps: list[tuple[str, float]] = []
    for ops, mods in per_dev_ops:
        for name, s, e in mods:
            if e > lo and s < hi:  # a cut event counts as its share
                inside = min(e, hi) - max(s, lo)
                module_s[name] = module_s.get(name, 0.0) + inside * 1e-9
                module_n[name] = module_n.get(name, 0.0) + inside / max(e - s, 1)
        for name, s, e in ops:
            if e > lo and s < hi:
                op_s[name] = op_s.get(name, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
                op_n[name] = op_n.get(name, 0) + 1
        busy = merge(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_attribute((a, b), spans), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / len(devs),
        devices=len(devs), module_s=module_s, module_n=module_n,
        op_s=op_s, op_n=op_n, gaps=gaps)


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_trace(ProfileData.from_file(path))
