"""Open-arrival scheduling (DESIGN.md §Open-arrival): dynamic task injection
into the live A2WS runtime, quiescence termination, mid-flight steals of
injected tasks, the continuous-batching ServePool, and the simulator's
Poisson/trace arrival modes with latency-percentile reporting."""

import threading
import time

import numpy as np
import pytest

from repro.core.a2ws import A2WSRuntime, PoolCollapsed, WorkerPool
from repro.core.policy import SchedPolicy
from repro.core.simulator import SimConfig, simulate, table2_speeds
from repro.core.steal import tail_steal_amount
from repro.serve.engine import Replica, ServePool


# ------------------------------------------------------------ threaded runtime
def test_open_arrival_quiescence_no_deadlock():
    """Queues go transiently empty between submit waves; the run must only
    terminate after drain(), and must terminate promptly then."""
    done = []
    lock = threading.Lock()

    def task_fn(wid, task):
        with lock:
            done.append(task)

    rt = A2WSRuntime([], 3, task_fn, open_arrival=True, seed=0)
    rt.start()
    rt.submit_many(range(10))
    deadline = time.time() + 5.0
    while rt.pending() and time.time() < deadline:
        time.sleep(0.001)
    assert rt.pending() == 0  # wave 1 fully executed...
    assert not rt._finished()  # ...but NOT finished: more work may arrive
    time.sleep(0.01)  # workers idle on empty deques — must not exit
    rt.submit_many(range(10, 25))
    rt.drain()
    stats = rt.join()  # must not deadlock
    assert sorted(done) == list(range(25))
    assert sum(stats.per_worker_tasks) == 25


def test_open_arrival_empty_drain():
    """drain() with zero submitted tasks terminates immediately."""
    rt = A2WSRuntime([], 2, lambda w, t: None, open_arrival=True)
    rt.start()
    rt.drain()
    stats = rt.join()
    assert sum(stats.per_worker_tasks) == 0


def test_submit_requires_open_mode_and_predrain():
    rt = A2WSRuntime([1, 2], 2, lambda w, t: None)
    with pytest.raises(RuntimeError):
        rt.submit(3)
    rt2 = A2WSRuntime([], 2, lambda w, t: None, open_arrival=True)
    rt2.drain()
    with pytest.raises(RuntimeError):
        rt2.submit(3)
    rt2.start()
    rt2.join()


def test_midflight_steal_of_injected_task():
    """Tasks injected onto a busy worker's deque AFTER the run started must
    be stolen and executed by another worker.

    Deterministic setup: both workers block on a "blocker" task, 8 requests
    are injected onto worker 1's deque while it is still blocked, then only
    worker 0 is released — everything it serves was stolen mid-flight
    (worker 1 cannot even publish its queue depth while blocked; the probe
    steal path is what discovers the backlog)."""
    releases = [threading.Event(), threading.Event()]
    served_by = {}
    lock = threading.Lock()

    def task_fn(wid, task):
        if isinstance(task, str) and task.startswith("blocker"):
            releases[wid].wait(10.0)
            return
        with lock:
            served_by[task] = wid

    rt = A2WSRuntime([], 2, task_fn, open_arrival=True, seed=1)
    rt.start()
    rt.submit("blocker0", worker=0)
    rt.submit("blocker1", worker=1)
    deadline = time.time() + 5.0
    while time.time() < deadline and (
        len(rt.workers[0].deque) or len(rt.workers[1].deque)
    ):
        time.sleep(0.001)  # both blockers picked up -> both workers stuck
    rt.submit_many(list(range(8)), worker=1)
    releases[0].set()  # only worker 0 wakes; worker 1 still holds blocker1
    deadline = time.time() + 10.0
    while rt.pending() > 1 and time.time() < deadline:
        time.sleep(0.001)
    releases[1].set()
    rt.drain()
    stats = rt.join()
    assert len(served_by) == 8
    stolen_and_served = [t for t, w in served_by.items() if w == 0]
    assert len(stolen_and_served) == 8, served_by
    assert [s for s in stats.steals if s[1] == 0], "no recorded steal by w0"


def test_submit_invalid_worker_rejected_before_counting():
    """An out-of-range pin must raise ValueError WITHOUT bumping the
    quiescence counter — otherwise join() hangs forever."""
    rt = A2WSRuntime([], 2, lambda w, t: None, open_arrival=True)
    rt.start()
    with pytest.raises(ValueError):
        rt.submit("x", worker=5)
    assert rt.pending() == 0
    rt.drain()
    rt.join()  # must terminate promptly


def test_duplicate_payload_objects_keep_latency_stats_consistent():
    """Submitting the same (interned) object N times must yield N stamped
    records with non-negative latencies (arrival stamps are a per-id stack,
    not a single slot)."""
    rt = A2WSRuntime([], 2, lambda w, t: time.sleep(0.001),
                     open_arrival=True, seed=0)
    rt.start()
    rt.submit_many(["retry"] * 6)  # one interned str, six submissions
    rt.drain()
    stats = rt.join()
    assert len(stats.latencies) == 6
    assert all(x >= 0.0 for x in stats.latencies)


def test_open_arrival_latency_stats():
    """Records carry arrival stamps; percentiles are monotone."""
    rt = A2WSRuntime([], 2, lambda w, t: time.sleep(0.001),
                     open_arrival=True, seed=0)
    rt.start()
    rt.submit_many(range(12))
    rt.drain()
    stats = rt.join()
    lat = stats.latencies
    assert len(lat) == 12
    assert all(x >= 0.0 for x in lat)
    pct = stats.latency_percentiles((50.0, 95.0, 99.0))
    assert pct[50.0] <= pct[95.0] <= pct[99.0]


def test_closed_mode_has_no_latency_stats():
    rt = A2WSRuntime(list(range(8)), 2, lambda w, t: None)
    stats = rt.run()
    assert stats.latency_percentiles() == {}


class _IdleGatePolicy(SchedPolicy):
    """Worker 1's post-get_task idle boundaries sleep ``hold`` seconds so the
    test can land a submit() inside the window between its empty-deque check
    and its backoff wait; nobody ever steals."""

    name = "idle-gate"

    def __init__(self, hold: float = 0.15) -> None:
        self.hold = hold
        self.calls = 0
        self.in_idle_boundary = threading.Event()

    def on_boundary(self, view):
        if view.worker == 1 and view.idle:
            self.calls += 1
            if self.calls % 2 == 0:  # the idle-branch call AFTER get_task
                self.in_idle_boundary.set()
                time.sleep(self.hold)
                self.in_idle_boundary.clear()
        return None


def test_submit_wakes_backoff_sleeper_promptly():
    """Bugfix regression (lost submit wakeup): with ONE shared wake event, a
    busy worker's event-clear at its loop top could erase a submit()'s set()
    aimed at an idle sleeper that had already checked its deque — costing a
    full idle_backoff_max of tail latency.  With per-worker events the
    submitted task must complete far sooner than the 0.5 s backoff cap."""
    pol = _IdleGatePolicy(hold=0.15)
    exec_t = {}

    def task_fn(wid, task):
        if task == "probe":
            exec_t["probe"] = time.perf_counter()
        else:
            time.sleep(0.001)

    pool = WorkerPool([], 2, task_fn, policy=pol, open_arrival=True,
                      idle_backoff=0.5, idle_backoff_max=0.5)
    pool.start()
    # 300 ms of backlog pinned to worker 0: it cycles its loop top (where
    # the shared event used to be cleared) every millisecond with NO further
    # submits to re-set the event.
    pool.submit_many(["w0"] * 300, worker=0)
    assert pol.in_idle_boundary.wait(5.0), "worker 1 never reached idle gate"
    t0 = time.perf_counter()
    pool.submit("probe", worker=1)  # lands AFTER worker 1's deque check
    deadline = time.time() + 5.0
    while "probe" not in exec_t and time.time() < deadline:
        time.sleep(0.005)
    pool.drain()
    pool.join()
    assert "probe" in exec_t, "probe task never executed"
    latency = exec_t["probe"] - t0
    assert latency < 0.35, (
        f"sleeper woke after {latency:.3f}s — submit wakeup was lost "
        f"(idle backoff cap is 0.5s)"
    )


def test_submit_into_collapsed_pool_raises():
    """Bugfix regression (submit-vs-collapse race): once every worker has
    died, submit() must raise PoolCollapsed instead of round-robining onto a
    dead deque nobody will ever drain (the silent strand of the old code)."""

    def die(wid, task):
        raise RuntimeError("boom")

    pool = WorkerPool([], 2, die, policy="random", open_arrival=True)
    pool.start()
    pool.submit_many(["a", "b"])  # both workers pick one up and die
    deadline = time.time() + 5.0
    while pool.alive.load() > 0 and time.time() < deadline:
        time.sleep(0.001)
    assert pool.alive.load() == 0
    with pytest.raises(PoolCollapsed):
        pool.submit("stranded")
    pool.drain()
    pool.join()  # must return promptly, nothing hangs


def test_servepool_kill_all_replicas_while_submitting():
    """Bugfix regression: hammer submits while every replica dies — each
    future must resolve (with an error), whether it was accepted before the
    collapse, swept by the collapse hook, or rejected after it."""

    def bad(req):
        raise RuntimeError("replica crashed")

    pool = ServePool([Replica("b0", bad), Replica("b1", bad)])
    pool.start()
    futs = [pool.submit({"x": k}) for k in range(40)]
    for f in futs:
        with pytest.raises(RuntimeError):
            f.result(timeout=10)
    stats = pool.shutdown()
    assert sum(stats.per_worker_tasks) == 0


# ------------------------------------------------------------------ tail rule
def test_tail_steal_open_arrival_accepts_tie():
    """Closed: equal-speed single-task tie -> no steal.  Open: the idle
    thief takes it (the victim is busy with an in-flight task; leaving the
    queued task behind it is a pure latency loss)."""
    assert tail_steal_amount(0, 1.0, 1, 1.0) == 0
    assert tail_steal_amount(0, 1.0, 1, 1.0, open_arrival=True) == 1
    # but a strictly-worsening move is still refused even when open
    assert tail_steal_amount(0, 60.0, 1, 1.0, open_arrival=True) == 0
    # and a busy thief gets no tie-break exemption
    assert tail_steal_amount(3, 1.0, 1, 1.0, open_arrival=True) == 0


# ------------------------------------------------------------------ ServePool
def test_servepool_streams_across_waves_without_teardown():
    def gen(req):
        time.sleep(0.001)
        return {"y": req["x"] * 2}

    pool = ServePool(
        [Replica("fast", gen), Replica("slow", gen, slow_factor=10.0)],
        seed=3,
    )
    pool.start()
    runtime = pool._runtime
    # wave 1: everything pinned to the SLOW replica post-start; the fast
    # replica can only serve via mid-flight steals.
    futs = pool.submit_wave([{"x": k} for k in range(16)], replica=1)
    resp = [f.result(timeout=30) for f in futs]
    assert [r["y"] for r in resp] == [2 * k for k in range(16)]
    served_by_fast = sum(1 for f in futs if f.worker == 0)
    assert served_by_fast > 0, "no injected request was stolen cross-replica"
    s1 = pool.stats()
    assert len(s1.steals) > 0

    # wave 2 reuses the same runtime: no teardown/re-partition between waves
    resp2, s2 = pool.submit_all([{"x": 100 + k} for k in range(8)])
    assert pool._runtime is runtime
    assert [r["y"] for r in resp2] == [2 * (100 + k) for k in range(8)]
    assert sum(s2.per_worker_tasks) == 24

    final = pool.shutdown()
    assert sum(final.per_worker_tasks) == 24
    assert len(final.latencies) == 24


def test_servepool_total_collapse_fails_futures_instead_of_hanging():
    """When EVERY replica dies, queued requests can never be served — their
    futures must fail promptly (collapse hook) rather than hang forever."""

    def bad(req):
        raise RuntimeError("boom")

    pool = ServePool([Replica("b0", bad), Replica("b1", bad)])
    pool.start()
    futs = pool.submit_wave([{"x": k} for k in range(6)])
    for f in futs:
        with pytest.raises(RuntimeError):
            f.result(timeout=10)
    stats = pool.shutdown()
    assert sum(stats.per_worker_tasks) == 0
    # collapse means every replica died, each logged with its exception
    assert sorted(w for w, _, _ in pool.errors) == [0, 1]
    assert all(str(e) == "boom" for _, _, e in pool.errors)


def test_submit_drain_race_never_strands_tasks():
    """Hammer submit() against drain() from another thread: every submit
    must either raise (after drain) or have its task executed."""
    done = []
    lock = threading.Lock()

    def task_fn(wid, task):
        with lock:
            done.append(task)

    for trial in range(5):
        rt = A2WSRuntime([], 2, task_fn, open_arrival=True, seed=trial)
        rt.start()
        accepted = []

        def submitter():
            for k in range(200):
                try:
                    rt.submit(("t", trial, k))
                except RuntimeError:
                    return
                accepted.append(k)

        th = threading.Thread(target=submitter)
        th.start()
        time.sleep(0.002)
        rt.drain()
        th.join()
        rt.join()
        ran = [t for t in done if t[1] == trial]
        assert len(ran) == len(accepted), (trial, len(ran), len(accepted))


def test_servepool_replica_failure_transparent_retry():
    calls = []

    def bad_gen(req):
        raise RuntimeError("replica crashed")

    def good_gen(req):
        calls.append(req["x"])
        return {"ok": req["x"]}

    pool = ServePool([Replica("good", good_gen), Replica("bad", bad_gen)])
    pool.start()
    futs = [pool.submit({"x": k}, replica=1) for k in range(4)]
    resp = [f.result(timeout=30) for f in futs]
    assert sorted(r["ok"] for r in resp) == [0, 1, 2, 3]
    assert all(f.worker == 0 for f in futs)  # survivor served everything
    pool.shutdown()


# ------------------------------------------------------------------ simulator
def test_sim_poisson_latency_reporting():
    speeds = table2_speeds("C1")
    capacity = float(speeds.sum()) / 60.0
    cfg = SimConfig(speeds=speeds, num_tasks=300, seed=0,
                    arrival="poisson", arrival_rate=0.6 * capacity)
    res = simulate("a2ws", cfg)
    assert sum(res.per_node_tasks) == 300
    assert len(res.latencies) == 300
    pct = res.latency_percentiles((50.0, 95.0, 99.0))
    assert 0.0 < pct[50.0] <= pct[95.0] <= pct[99.0]
    assert res.makespan > 0


def test_sim_trace_arrivals():
    speeds = table2_speeds("C1")
    trace = tuple(np.linspace(0.0, 50.0, 40))
    cfg = SimConfig(speeds=speeds, num_tasks=0, seed=1,
                    arrival="trace", arrival_trace=trace)
    res = simulate("a2ws", cfg)
    assert sum(res.per_node_tasks) == 40
    assert len(res.latencies) == 40


def test_sim_open_stealing_beats_static_routing_tail():
    """Round-robin arrivals overload slow nodes; adaptive stealing must
    rescue the tail (radius=0 disables stealing entirely)."""
    speeds = table2_speeds("C1")
    capacity = float(speeds.sum()) / 60.0
    base = dict(speeds=speeds, num_tasks=400, seed=0,
                arrival="poisson", arrival_rate=0.7 * capacity)
    steal = simulate("a2ws", SimConfig(**base))
    nosteal = simulate("a2ws", SimConfig(**base, radius=0))
    assert steal.steals > 0 and nosteal.steals == 0
    p99_s = steal.latency_percentiles((99.0,))[99.0]
    p99_n = nosteal.latency_percentiles((99.0,))[99.0]
    assert p99_s < 0.5 * p99_n
    assert steal.makespan < nosteal.makespan


@pytest.mark.parametrize("policy", ["ctws", "lw", "random"])
def test_sim_open_arrival_baseline_parity(policy):
    """PR 2 (policy layer): open-arrival simulation is no longer A2WS-only —
    every policy runs on the same event loop and reports latencies."""
    speeds = table2_speeds("C1")
    cfg = SimConfig(speeds=speeds, num_tasks=60, seed=3,
                    arrival="poisson", arrival_rate=0.5 * float(speeds.sum()) / 60.0)
    res = simulate(policy, cfg)
    assert sum(res.per_node_tasks) == 60
    assert len(res.latencies) == 60
    assert res.latency_percentiles()[99.0] > 0.0
