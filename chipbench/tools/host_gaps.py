"""Name the device-idle gaps of a traced window by the innermost host span
over them, the program's own spans (``repro.*``) beside the benchmark's
(``chipbench.*``), and total each span's time in the window.

    python3 chipbench/tools/host_gaps.py .chipbench_run/trace [--top 10]

Reads the newest ``.xplane.pb`` under the directory (a ``--trace 1`` run
leaves its trace in ``.chipbench_run/trace``) and prints one JSON object:

- ``gaps``: the longest gaps, each with the span that names it, its
  seconds, the seconds of it under each span (at every instant the
  innermost, i.e. shortest, span there, so a long span never hides a finer
  one inside it; the gap is named by the span with the most) and, per host
  thread, the innermost span at the gap's middle;
- ``host_s``/``host_n``: per span name (the part before any ``#``), its
  seconds clipped to the window and its count.

Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import trace_reduce  # noqa: E402

PREFIXES = ("repro.", trace_reduce.HOST_PREFIX)
UNTRACED = "host: untraced"


def host_spans(planes) -> list[tuple[str, int, int, str]]:
    """(name, start ns, end ns, thread) of every program or benchmark span."""
    out = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append((ev.name.split("#", 1)[0], ev.start_ns, ev.end_ns,
                                f"{line.name}/{k}"))
    return out


def composition(gap, spans) -> dict[str, float]:
    """Seconds of ``gap`` under each span (not the window), giving every
    instant to the shortest span over it, on any thread."""
    inside = [(s, e, name) for name, s, e, *_ in spans
              if name != trace_reduce.WINDOW_SPAN and e > gap[0] and s < gap[1]]
    edges = sorted({gap[0], gap[1]} | {x for s, e, _ in inside for x in (s, e)
                                       if gap[0] < x < gap[1]})
    out: dict[str, float] = {}
    for a, b in zip(edges, edges[1:]):
        over = [(e - s, name) for s, e, name in inside if s <= a and e >= b]
        if over:
            name = min(over)[1]
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def attribute(gap, spans) -> str:
    """The span with the most of ``gap`` by ``composition``; ``"host:
    untraced"`` when no span overlaps it."""
    parts = composition(gap, spans)
    return max(parts, key=parts.get) if parts else UNTRACED


def _innermost(t, spans) -> dict[str, str]:
    """Per thread, the shortest span (not the window) around time ``t``."""
    inner: dict[str, tuple[int, str]] = {}
    for name, s, e, thread in spans:
        if name != trace_reduce.WINDOW_SPAN and s <= t < e:
            if thread not in inner or e - s < inner[thread][0]:
                inner[thread] = (e - s, name)
    return {th: name for th, (_, name) in sorted(inner.items())}


def reduce(pd, top: int = 10) -> dict:
    planes = list(pd.planes)
    spans = host_spans(planes)
    win = [(s, e) for n, s, e, _ in spans if n == trace_reduce.WINDOW_SPAN]
    devs = [p for p in planes if p.name.startswith(trace_reduce.DEVICE_PREFIX)]
    per_dev = []
    for p in devs:
        by_line = {line.name: [(ev.start_ns, ev.end_ns) for ev in line.events]
                   for line in p.lines}
        per_dev.append(by_line.get("XLA Ops") or by_line.get("XLA Modules", []))
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for ops in per_dev for s, _ in ops)
        hi = max(e for ops in per_dev for _, e in ops)
    gaps = []
    for ops in per_dev:
        busy = trace_reduce.merge(trace_reduce._clip(ops, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    host_s: dict[str, float] = {}
    host_n: dict[str, int] = {}
    for name, s, e, _ in spans:
        if e > lo and s < hi:
            host_s[name] = host_s.get(name, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
            host_n[name] = host_n.get(name, 0) + 1
    return {
        "window_s": (hi - lo) * 1e-9,
        "gaps": [[attribute(g, spans), (g[1] - g[0]) * 1e-9, composition(g, spans),
                  _innermost((g[0] + g[1]) // 2, spans)] for g in gaps[:top]],
        "host_s": host_s, "host_n": host_n,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    path = trace_reduce.find_xplane(args.trace_dir)
    print(json.dumps(reduce(ProfileData.from_file(path), args.top)))


if __name__ == "__main__":
    main()
