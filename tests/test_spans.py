"""Host spans and counters (repro.core.spans) and their call sites: the
scheduler's task boundary (core/a2ws.py) and the decode launch
(launch/serve.py::generate)."""

from __future__ import annotations

import glob
import itertools
import os
import sys
import threading
import time

import pytest

from repro.core import spans
from repro.core.a2ws import WorkerPool
from repro.core.spans import COUNTERS, Counters, span, tagged


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation with a trace running."""

    seen: list = []  # (thread, span name, stats)

    @staticmethod
    def is_enabled() -> bool:
        return True

    def __init__(self, name, **args):
        self.args = args
        self.seen.append((threading.current_thread().name, name, args))

    def set_metadata(self, **more):
        self.args.update(more)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def test_span_with_profiler_off_is_a_noop_that_nests_and_reraises():
    import jax  # noqa: F401  (spans look for a profiler only once jax is in)

    assert not jax.profiler.TraceAnnotation.is_enabled()
    with span("outer", a=1) as outer:
        with span("inner") as inner:
            inner.set_metadata(got=3)
        assert not isinstance(outer, jax.profiler.TraceAnnotation)
    with pytest.raises(KeyError, match="lost"):
        with span("outer"):
            with span("inner"):
                raise KeyError("lost")


def test_tagged_arguments_reach_nested_spans_on_the_same_thread_only(monkeypatch):
    monkeypatch.setattr(spans, "_annotation", _Recorder)
    monkeypatch.setattr(spans, "_enabled", _Recorder.is_enabled)
    monkeypatch.setattr(_Recorder, "seen", [])
    me = threading.current_thread().name

    def elsewhere():
        with span("other", pos=0):
            pass

    with tagged(request=3, replica=1):
        with span("step", pos=7):
            pass
        with tagged(replica=2):  # inner tags override outer ones
            with span("step", pos=8, phase="token"):
                pass
        t = threading.Thread(target=elsewhere, name="other-thread")
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with span("step", request=9):  # a span's own argument wins
            pass
    with span("after"):
        pass
    assert _Recorder.seen == [
        (me, "repro.step", {"request": 3, "replica": 1, "pos": 7}),
        (me, "repro.step", {"request": 3, "replica": 2, "pos": 8, "phase": "token"}),
        ("other-thread", "repro.other", {"pos": 0}),
        (me, "repro.step", {"request": 9, "replica": 1}),
        (me, "repro.after", {}),
    ]


def test_counters_sum_exactly_across_threads():
    c = Counters()
    n_threads, n_adds = 8, 10_000

    def hammer():
        for _ in range(n_adds):
            c.add("x", 3)
            c.add("n")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert c.snapshot() == {"x": 3 * n_threads * n_adds, "n": n_threads * n_adds}
    snap = c.snapshot()
    snap["x"] = 0  # a snapshot is a copy
    assert c.snapshot()["x"] == 3 * n_threads * n_adds


def test_pool_counts_tasks_and_boundary_time_on_a_virtual_clock():
    ticks = itertools.count()
    ran = []
    pool = WorkerPool(list(range(40)), 4, lambda w, t: ran.append(t), seed=3,
                      clock=lambda: next(ticks) * 1e-3)
    before = COUNTERS.snapshot()
    stats = pool.run()
    d = _delta(before, COUNTERS.snapshot())
    assert sorted(ran) == list(range(40)) and len(stats.records) == 40
    assert d["a2ws.tasks"] == 40
    assert d["a2ws.boundary_ns"] > 0


def test_steal_span_carries_thief_victim_and_loot(monkeypatch):
    monkeypatch.setattr(spans, "_annotation", _Recorder)
    monkeypatch.setattr(spans, "_enabled", _Recorder.is_enabled)
    monkeypatch.setattr(_Recorder, "seen", [])
    # all the work starts on worker 0, so worker 1 has to steal it
    pool = WorkerPool([], 2, lambda w, t: time.sleep(0.002), seed=0,
                      open_arrival=True)
    pool.start()
    time.sleep(0.05)  # both workers idle first, in repro.a2ws.wait
    pool.submit_many(list(range(12)), worker=0)
    pool.drain()
    stats = pool.join()
    assert stats.steals, "the idle worker never stole"
    steals = [a for _, n, a in _Recorder.seen if n == "repro.a2ws.steal"]
    assert all(set(a) == {"thief", "victim", "got"} for a in steals)
    landed = [a for a in steals if a["got"] > 0]
    assert sorted((a["thief"], a["victim"], a["got"]) for a in landed) == sorted(
        s[1:] for s in stats.steals)
    names = {n for _, n, _ in _Recorder.seen}
    assert {"repro.a2ws.task", "repro.a2ws.boundary", "repro.a2ws.wait"} <= names


def test_cpu_trace_of_a_pool_run_holds_its_spans_with_their_arguments(tmp_path):
    import jax
    from jax.profiler import ProfileData

    pool = WorkerPool(list(range(6)), 2, lambda w, t: time.sleep(0.001), seed=0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        pool.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    found: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    assert len(found["repro.a2ws.task"]) == 6
    assert {s["worker"] for s in found["repro.a2ws.task"]} <= {0, 1}
    assert found["repro.a2ws.boundary"]


@pytest.fixture(scope="module")
def smoke_lm():
    import jax.numpy as jnp  # noqa: F401

    from repro.configs.base import get_smoke
    from repro.launch import serve

    # a config of its own, so this module's decode step is not shared
    cfg = get_smoke("phi4-mini-3.8b").with_(norm_eps=3.25e-6)
    return cfg, serve.init_params(cfg, 0)


def test_generate_counts_one_launch_per_token(smoke_lm):
    import jax.numpy as jnp

    from repro.launch.serve import generate

    cfg, params = smoke_lm
    s, n = 5, 4
    before = COUNTERS.snapshot()
    out = generate(cfg, params, jnp.ones((1, s), jnp.int32), n)
    d = _delta(before, COUNTERS.snapshot())
    assert out.shape == (1, n)
    assert d["serve.launches"] == s + n - 1
    assert d["serve.host_cpu_ns"] > 0


def test_decode_program_is_named_and_one_per_cache_length(smoke_lm):
    import jax.numpy as jnp

    from repro.launch.serve import generate, make_decode
    from repro.models import lm

    cfg, params = smoke_lm
    decode = make_decode(cfg)
    caches = lm.init_caches(cfg, 1, 7)
    text = decode.lower(params, jnp.zeros((1, 1), jnp.int32), caches,
                        jnp.int32(0)).as_text()
    assert text.startswith("module @jit_decode_step")
    before = decode._cache_size()
    generate(cfg, params, jnp.ones((1, 4), jnp.int32), 3)  # cache length 7
    generate(cfg, params, jnp.ones((1, 2), jnp.int32), 5)  # cache length 7
    assert decode._cache_size() == before + 1
    generate(cfg, params, jnp.ones((1, 2), jnp.int32), 6)  # cache length 8
    assert decode._cache_size() == before + 2


def test_serve_pool_tags_each_replica_span_with_its_request(monkeypatch):
    from repro.serve.engine import Replica, ServePool

    monkeypatch.setattr(spans, "_annotation", _Recorder)
    monkeypatch.setattr(spans, "_enabled", _Recorder.is_enabled)
    monkeypatch.setattr(_Recorder, "seen", [])

    def gen(request):
        with span("serve.step", pos=0):
            return {"echo": request["x"]}

    pool = ServePool([Replica("r0", gen), Replica("r1", gen)], seed=1)
    futs = [pool.submit({"x": k}) for k in range(5)]
    assert [f.result(timeout=30)["echo"] for f in futs] == list(range(5))
    pool.shutdown()
    assert [f.id for f in futs] == list(range(5))
    steps = sorted((a["request"], a["replica"]) for _, n, a in _Recorder.seen
                   if n == "repro.serve.step")
    assert steps == sorted((f.id, f.worker) for f in futs)
