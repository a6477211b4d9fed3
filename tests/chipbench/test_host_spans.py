"""Gap naming by program and benchmark spans (chipbench/tools/host_gaps.py)
and the scheduler's counter reader (chipbench/metrics/boundary_host_us.py),
on the CPU.

    PYTHONPATH=src python -m pytest tests/chipbench -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

from chipbench import harness, trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
host_gaps = harness.load_module(harness.HERE / "tools" / "host_gaps.py")
boundary_host_us = harness.load_module(harness.HERE / "metrics" / "boundary_host_us.py")


def test_attribute_names_the_innermost_span():
    gap = (100, 110)
    window = ("chipbench.window", 0, 1000, "main")
    task = ("repro.a2ws.task", 50, 500, "worker")
    copy = ("chipbench.seis_copy", 90, 120, "worker")
    # nested spans over the whole gap: the innermost names it, whatever
    # the order they come in
    assert host_gaps.attribute(gap, [window, task, copy]) == "chipbench.seis_copy"
    assert host_gaps.attribute(gap, [copy, task, window]) == "chipbench.seis_copy"
    # equal overlap on two threads: the shorter span
    other = ("repro.serve.step", 95, 115, "replica")
    assert host_gaps.attribute(gap, [task, copy, other]) == "repro.serve.step"
    # more overlap beats a shorter span that covers part of the gap
    part = ("repro.a2ws.boundary", 108, 109, "worker")
    assert host_gaps.attribute(gap, [task, part]) == "repro.a2ws.task"
    # the window alone, or nothing over the gap, leaves it untraced
    assert host_gaps.attribute(gap, [window]) == "host: untraced"
    assert host_gaps.attribute(gap, [("repro.a2ws.wait", 0, 100, "w")]) == "host: untraced"


def test_composition_gives_each_instant_to_the_innermost_span():
    gap = (100, 200)
    spans = [("chipbench.window", 0, 1000, "main"),
             ("repro.a2ws.task", 0, 170, "worker"),  # a shot and its copy
             ("chipbench.seis_copy", 50, 160, "worker"),
             ("repro.a2ws.boundary", 170, 185, "worker"),  # between shots
             ("repro.a2ws.task", 185, 400, "worker"),  # the next shot
             ("chipbench.shot_dispatch", 190, 260, "worker")]
    parts = host_gaps.composition(gap, spans)
    assert parts == pytest.approx({"chipbench.seis_copy": 60e-9, "repro.a2ws.task": 15e-9,
                                   "repro.a2ws.boundary": 15e-9,
                                   "chipbench.shot_dispatch": 10e-9})
    # the copy has most of the gap, though the task around it overlaps more
    assert host_gaps.attribute(gap, spans) == "chipbench.seis_copy"
    assert host_gaps.composition((500, 600), spans) == {}


def test_host_gaps_on_the_recorded_tpu_trace():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / "trace_small.xplane.pb"))
    out = host_gaps.reduce(pd, top=10)
    ref = trace_reduce.reduce_trace(pd)
    # the same gaps as the benchmark's reducer, longest first
    assert [g[1] for g in out["gaps"]] == pytest.approx([s for _, s in ref.gaps[:10]])
    assert out["window_s"] == pytest.approx(ref.window_s)
    # the 50 ms host sleep is named, and so is what the thread was in
    name, secs, parts, threads = out["gaps"][0]
    assert name == "chipbench.idle_probe"
    assert "chipbench.idle_probe" in threads.values()
    assert sum(parts.values()) <= secs * (1 + 1e-9)
    assert out["host_n"]["chipbench.shot_dispatch"] == 3
    assert out["host_n"]["chipbench.window"] == 1
    assert out["host_s"]["chipbench.window"] == pytest.approx(ref.window_s)
    sleep = json.loads((DATA / "trace_small.json").read_text())["sleep_s"]
    assert sleep <= out["host_s"]["chipbench.idle_probe"] < min(secs, sleep + 0.01)


def test_boundary_host_us_reads_the_program_counters(monkeypatch):
    from repro.core import spans

    ctx = {"surveys": [{"start": 0.0, "end": 1.0, "workers": 1, "steals": 0}],
           "records": []}
    fresh = spans.Counters()
    monkeypatch.setattr(spans, "COUNTERS", fresh)
    assert boundary_host_us.read(ctx) is None  # no task has run
    fresh.add("a2ws.tasks", 8)
    fresh.add("a2ws.boundary_ns", 8 * 37_500)
    assert boundary_host_us.read(ctx) == pytest.approx(37.5)
    assert boundary_host_us.read({}) is None  # not a survey cell


def test_boundary_host_us_reads_a_survey_window():
    from repro.core.spans import COUNTERS

    drv = tiny.driver("survey")
    before = COUNTERS.snapshot()
    out = drv.run(tiny.cpu_run("survey-overthrust", tiny.SURVEY, tiny.SURVEY_MIX,
                               seconds=0.3))
    after = COUNTERS.snapshot()
    # the pool runs in the window alone: one task per shot recorded there
    assert after["a2ws.tasks"] - before.get("a2ws.tasks", 0) == len(out.layer_ctx["records"])
    value = boundary_host_us.read(out.layer_ctx)
    assert value == after["a2ws.boundary_ns"] / after["a2ws.tasks"] / 1e3 > 0


def test_boundary_host_us_is_silent_without_the_program_counters(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)  # import fails
    assert boundary_host_us.read({"surveys": [{}], "records": []}) is None
