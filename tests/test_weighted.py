"""Work-weighted stealing (DESIGN.md §Work-weighted stealing): the class
pricing math, the degenerate single-class guarantee, the NaN-boot guards,
work-greedy loot, churn regressions in both planes, cross-plane conformance
and the acceptance makespan ratio on the clustered bimodal scenario."""

import threading
import time

import numpy as np
import pytest

from _hypo import given, settings, st

from repro.core.a2ws import WorkerPool
from repro.core.deque import TaskDeque
from repro.core.info_ring import RingInfo
from repro.core.simulator import SimConfig, simulate
from repro.core.steal import (
    class_relatives,
    ideal_runtime,
    plan_steal,
    queue_units,
    steal_rate_radius,
    tail_steal_amount,
)
from repro.serve.engine import (
    Replica,
    ServePool,
    request_size,
    shape_cost_classifier,
)


# ---------------------------------------------------------- class pricing math
def test_class_relatives_from_own_worker_ratios():
    # Within one worker the speed cancels: every worker reports 8x between
    # its classes, so rel must be [1, 8] regardless of absolute speeds.
    tc = np.array([[1.0, 8.0], [4.0, 32.0], [np.nan, np.nan]])
    assert np.allclose(class_relatives(tc), [1.0, 8.0])


def test_class_relatives_unreported_class_prices_at_one():
    tc = np.array([[2.0, np.nan], [3.0, np.nan]])
    assert np.allclose(class_relatives(tc), [1.0, 1.0])
    # nobody reported anything: all ones (count-based degenerate values)
    assert np.allclose(class_relatives(np.full((3, 2), np.nan)), [1.0, 1.0])


def test_class_relatives_pool_mean_fallback():
    # No worker reported BOTH classes: fall back to the ratio of pool means.
    tc = np.array([[2.0, np.nan], [np.nan, 10.0]])
    assert np.allclose(class_relatives(tc), [1.0, 5.0])


def test_queue_units_mean_work_per_task():
    nc = np.array([[4.0, 0.0], [0.0, 2.0], [2.0, 2.0], [0.0, 0.0]])
    units = queue_units(nc, np.array([1.0, 8.0]))
    assert np.allclose(units, [1.0, 8.0, 4.5, 1.0])


# ----------------------------------------------------- NaN-boot guards (Eq. 2/5)
def test_ideal_runtime_unreported_neighbour_is_nan_not_garbage():
    assert np.isnan(ideal_runtime([3.0, 2.0], [1.0, float("nan")]))
    assert ideal_runtime([3.0, 3.0], [1.0, 1.0]) == pytest.approx(3.0)


def test_steal_rate_radius_nan_window():
    t = np.array([1.0, np.nan, 1.0, 1.0])
    assert np.isnan(steal_rate_radius(0, np.ones(4), t, radius=1))
    # NaN outside the window must NOT poison the subsystem computation
    t2 = np.array([1.0, 1.0, np.nan, 1.0])
    assert np.isfinite(steal_rate_radius(0, np.ones(4), t2, radius=1))


def test_tail_steal_amount_nonfinite_inputs_mean_no_steal():
    assert tail_steal_amount(0.0, float("nan"), 5.0, 1.0) == 0
    assert tail_steal_amount(0.0, 1.0, 5.0, float("inf")) == 0


def test_plan_steal_all_unreported_boot_returns_no_plan():
    """Regression (fails pre-fix): at open-arrival boot every in-window t̂
    is NaN while depths are already positive.  The old code propagated NaN
    into the victim weights and ``rng.choice`` raised ``ValueError``; the
    fix must translate "no information" into "no steal"."""
    rng = np.random.default_rng(0)
    n = np.array([0.0, 5.0, 4.0, 3.0, 2.0, 1.0])
    t = np.full(6, np.nan)
    plan = plan_steal(
        rng, 0, n, t, n.copy(), radius=2, idle=True, open_arrival=True
    )
    assert plan is None


def test_plan_steal_partial_reports_only_targets_reported_victims():
    rng = np.random.default_rng(1)
    n = np.array([0.0, 6.0, 6.0, 0.0])
    t = np.array([1.0, np.nan, 1.0, 1.0])
    for _ in range(20):
        plan = plan_steal(
            rng, 0, n, t, n.copy(), radius=2, idle=True, open_arrival=True
        )
        assert plan is None or plan.victim == 2  # never the NaN victim


# --------------------------------------------- degenerate single-class guarantee
def test_single_class_weighted_plan_equals_count_plan():
    """The work-weighted identities (unit ≡ 1, qtasks ≡ queued) must leave
    the count-based plan untouched bit-for-bit, rng stream included."""
    for seed in range(40):
        rng = np.random.default_rng(seed)
        p = int(rng.integers(2, 9))
        n = rng.integers(0, 30, p).astype(float)
        t = rng.uniform(0.5, 4.0, p)
        queued = np.minimum(rng.integers(0, 20, p).astype(float), n)
        i = int(rng.integers(0, p))
        radius = int(rng.integers(1, max(p // 2, 2)))
        idle = bool(rng.integers(0, 2))
        open_arr = bool(rng.integers(0, 2))
        a = plan_steal(
            np.random.default_rng(seed), i, n, t, queued, radius,
            idle=idle, open_arrival=open_arr,
        )
        b = plan_steal(
            np.random.default_rng(seed), i, n, t, queued, radius,
            idle=idle, open_arrival=open_arr,
            unit=np.ones(p), qtasks=queued,
        )
        if a is None:
            assert b is None
        else:
            assert b is not None
            assert (a.victim, a.amount, a.criterion) == (
                b.victim, b.amount, b.criterion
            )


@settings(max_examples=60, deadline=None)
@given(
    st.integers(min_value=0, max_value=10_000),
)
def test_single_class_plan_property(seed):
    """Hypothesis-driven variant of the bit-for-bit degenerate guarantee."""
    rng = np.random.default_rng(seed)
    p = int(rng.integers(2, 12))
    n = rng.integers(0, 40, p).astype(float)
    t = rng.uniform(0.1, 8.0, p)
    queued = np.minimum(rng.integers(0, 25, p).astype(float), n)
    i = int(rng.integers(0, p))
    radius = int(rng.integers(1, max(p // 2, 2)))
    a = plan_steal(np.random.default_rng(seed), i, n, t, queued, radius)
    b = plan_steal(
        np.random.default_rng(seed), i, n, t, queued, radius,
        unit=np.ones(p), qtasks=queued,
    )
    assert (a is None) == (b is None)
    if a is not None:
        assert (a.victim, a.amount) == (b.victim, b.amount)


def test_sim_single_class_weighted_equals_count_exactly():
    """One cost class through the whole simulator: the weighted info plane
    must reproduce the count-based run bit-for-bit (same rng stream, same
    makespan, same steal telemetry)."""
    cfg = SimConfig(
        speeds=np.array([4.0, 2.0, 1.0, 1.0]), num_tasks=80, seed=5,
        class_cost=(3.0,),
    )
    rw = simulate("a2ws", cfg)
    rc = simulate("a2ws", cfg.with_(weighted=False))
    assert rw.makespan == rc.makespan
    assert (rw.steals, rw.failed_steals, rw.moved_tasks) == (
        rc.steals, rc.failed_steals, rc.moved_tasks
    )
    assert rw.per_node_tasks == rc.per_node_tasks


# ------------------------------------------------------------ work-greedy loot
def test_steal_by_work_homogeneous_takes_exact_count():
    dq = TaskDeque(list(range(10)))
    r = dq.steal_by_work(3.0, lambda _t: 1.0, max_tasks=8)
    assert len(r.tasks) == 3
    # synthesized pre-image: observed span - got == what is left behind
    assert r.observed_tail - r.observed_head - len(r.tasks) == len(dq)


def test_steal_by_work_refuses_overshooting_heavy_task():
    """A thief planning one light-task's worth must never ingest a heavy
    task 8x its target — the count-based failure mode under tail skew."""
    dq = TaskDeque(["heavy"])
    r = dq.steal_by_work(1.0, lambda _t: 8.0, max_tasks=4)
    assert not r and len(dq) == 1  # refused, nothing claimed


def test_steal_by_work_nearest_to_target():
    dq = TaskDeque(["l", "l", "h"])  # thief side = tail: h first
    work = {"l": 1.0, "h": 4.0}
    # target 5: take h (cum 4), then l (cum 5 == target), stop
    r = dq.steal_by_work(5.0, lambda t: work[t], max_tasks=8)
    assert sorted(r.tasks) == ["h", "l"]
    # target 4.4: after h (cum 4), +l overshoots by 0.6 > deficit 0.4: stop
    dq2 = TaskDeque(["l", "l", "h"])
    r2 = dq2.steal_by_work(4.4, lambda t: work[t], max_tasks=8)
    assert r2.tasks == ["h"]


def test_peek_tail_and_snapshot_tasks():
    dq = TaskDeque([1, 2, 3])
    assert dq.peek_tail() == 3
    assert dq.snapshot_tasks() == [1, 2, 3]
    assert len(dq) == 3  # both are pure reads
    assert TaskDeque([]).peek_tail() is None


# ------------------------------------------------------- info-ring class plane
def test_ring_info_class_payload_roundtrip_and_versioning():
    ri = RingInfo(3, radius=1, num_classes=2)
    ri.update_local(0, 4.0, 1.0, nc_i=np.array([3.0, 1.0]),
                    tc_i=np.array([1.0, 8.0]))
    v0 = ri.version[0, 0]
    # class-profile-only change must dirty the cell (scalars unchanged)
    ri.update_local(0, 4.0, 1.0, nc_i=np.array([2.0, 2.0]),
                    tc_i=np.array([1.0, 8.0]))
    assert ri.version[0, 0] == v0 + 1
    ri.communicate(0)
    ri.communicate(1)
    *_, nc, tc, _limp = ri.view_window_all(1)
    assert np.allclose(nc[0], [2.0, 2.0]) and np.allclose(tc[0], [1.0, 8.0])


def test_ring_info_grow_preserves_class_cells():
    ri = RingInfo(2, radius=1, num_classes=2)
    ri.update_local(1, 2.0, 0.5, nc_i=np.array([0.0, 2.0]),
                    tc_i=np.array([np.nan, 4.0]))
    ri.grow(4)
    assert np.allclose(ri.nc[1, 1], [0.0, 2.0])
    assert np.isnan(ri.tc[1, 1, 0]) and ri.tc[1, 1, 1] == 4.0
    assert np.all(ri.nc[:, 2:, :] == 0.0) and np.all(np.isnan(ri.tc[:, 2:, :]))


# ---------------------------------------------------------- threaded substrate
def _busy(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_threaded_weighted_pool_runs_and_observes_classes():
    n = 40
    rng = np.random.default_rng(2)
    tasks = [int(c) for c in (rng.random(n) < 0.2)]
    done, lock = [], threading.Lock()

    def task_fn(wid, task):
        time.sleep(0.004 * (4.0 if task else 1.0))
        with lock:
            done.append(task)

    pool = WorkerPool(
        tasks, 3, task_fn, policy="a2ws", seed=0,
        cost_class_fn=lambda t: t, num_classes=2,
    )
    stats = pool.run()
    assert sorted(done) == sorted(tasks)
    assert sum(stats.per_worker_tasks) == n
    # every worker that ran a task has a finite EWMA for some class
    for w, st_ in zip(pool.workers, stats.per_worker_tasks):
        if st_ > 0:
            assert np.isfinite(w.class_t).any()


def test_threaded_closed_reconciliation_keeps_board_counts():
    """Regression (code review): in weighted CLOSED mode the Fig. 3b
    reconciliation must derive its executed estimate from the pre-overlay
    COUNT vectors — the work-repriced ``n_view - queued`` is executed work
    in reference units, and writing it into the board's count-denominated
    ``n`` double-scales on the next view (a victim with heavy history got
    its n inflated ~rel[c]-fold, attracting oversized plans forever).

    Crafted state, driven on the main thread: worker 1 executed 10 heavy
    tasks (8 ms each) and still queues 3; worker 0 ran 3 light (1 ms) and
    is idle.  rel resolves to ~8, so the pre-fix code recorded worker 1's
    n as ~50 work units instead of <= 13 tasks."""
    tasks = [0, 0, 0] + [1, 1, 1]  # block split: w0 light, w1 heavy queue
    pool = WorkerPool(
        tasks, 2, lambda wid, t: None, policy="a2ws", seed=0,
        cost_class_fn=lambda t: t, num_classes=2,
    )
    w0, w1 = pool.workers
    while w0.deque.get_task() is not None:
        pass  # w0 idle: its boundary must plan a steal
    now = pool.clock()
    w0.executed, w0.runtime_sum, w0.ran_any = 3, 3e-3, True
    w0.class_t[0] = 1e-3
    w0.start_time = now - 0.05
    w1.executed, w1.runtime_sum, w1.ran_any = 10, 8e-2, True
    w1.class_t[1] = 8e-3
    w1.start_time = now - 0.05
    pool._update_info(0)
    pool._update_info(1)
    pool.info.communicate(1)  # w1's self-cell reaches w0's vector
    assert pool._policy_boundary(0), "crafted state must trigger a steal"
    # Closed-mode n is a TASK count (executed + queued): worker 1's cell
    # can never exceed its 10 executed + 3 queued.
    assert float(pool.info.n[0, 1]) <= 13.5, pool.info.n[0, 1]


def test_threaded_raising_classifier_never_kills_a_worker():
    def bad_classifier(_task):
        raise RuntimeError("shape probe exploded")

    pool = WorkerPool(
        list(range(20)), 2, lambda wid, t: time.sleep(0.001),
        policy="a2ws", cost_class_fn=bad_classifier, num_classes=3,
    )
    stats = pool.run()
    assert sum(stats.per_worker_tasks) == 20  # all classified to class 0


# ------------------------------------------------- churn regression (both planes)
def test_threaded_weighted_churn_probes_skip_retired_members():
    """Join/retire churn under open arrivals with the weighted info plane:
    a retired member's stale ring row (depth > 0 at tombstone time) must
    not attract probe steals forever, and every submitted task is served."""
    done, lock = [], threading.Lock()

    def task_fn(wid, task):
        time.sleep(0.002 * (3.0 if task % 7 == 0 else 1.0))
        with lock:
            done.append(task)

    pool = WorkerPool(
        [], 3, task_fn, policy="a2ws", open_arrival=True, seed=0,
        cost_class_fn=lambda t: int(t % 7 == 0), num_classes=2,
    )
    pool.start()
    pool.submit_many(range(30), worker=1)  # backlog on the future retiree
    time.sleep(0.01)
    pool.retire_worker(1, drain=True)  # ring rows elsewhere still show depth
    wid = pool.add_worker()
    pool.submit_many(range(30, 60))
    pool.drain()
    stats = pool.join()
    assert sorted(done) == list(range(60))
    assert sum(stats.per_worker_tasks) == 60
    # no SUCCESSFUL steal may have a tombstoned victim after its retirement
    retire_t = [t for t, k, w in pool.membership_log if k == "retire"][0]
    for t, _thief, victim, got in stats.steals:
        if victim == 1 and t > retire_t:
            assert got == 0
    assert pool.dead[1] and not pool.dead[wid]


def test_sim_weighted_churn_conserves_tasks():
    speeds = np.array([4.0, 2.0, 1.0, 1.0])
    cfg = SimConfig(
        speeds=speeds, num_tasks=120, seed=3,
        arrival="poisson", arrival_rate=0.6 * float(speeds.sum()) / 60.0,
        class_cost=(1.0, 6.0), class_probs=(0.85, 0.15),
        retires=((90.0, 2),), joins=((90.0, 4.0),),
    )
    res = simulate("a2ws", cfg)
    assert sum(res.per_node_tasks) == 120
    assert len(res.latencies) == 120
    assert res.per_node_tasks[4] > 0  # the joiner pulled work


# --------------------------------------------------- cross-plane conformance
_SPEEDS = [4.0, 1.0, 1.0, 1.0]
_N, _BASE, _MULT = 48, 0.012, 4.0


def _bimodal_classes(seed: int = 7) -> list[int]:
    rng = np.random.default_rng(seed)
    return [int(c) for c in (rng.random(_N) < 0.15)]


def _threaded_weighted(seed: int):
    cls = _bimodal_classes()

    def task_fn(wid, task):
        _busy(_BASE * (_MULT if task else 1.0) / _SPEEDS[wid])

    pool = WorkerPool(
        cls, len(_SPEEDS), task_fn, policy="a2ws", seed=seed,
        cost_class_fn=lambda t: t, num_classes=2,
    )
    return pool.run()


def test_cross_plane_conformance_weighted_a2ws():
    """Weighted A2WS through BOTH planes on the same seeded bimodal
    workload: the fast worker dominates everywhere and steal volumes agree
    within the (generous) cross-plane band of tests/test_policy.py."""
    cls = _bimodal_classes()
    cfg = SimConfig(
        speeds=np.asarray(_SPEEDS), num_tasks=_N, task_cost=_BASE, noise=0.0,
        seed=0, hop_latency=1e-4, info_poll=1e-3, comm_cell_cost=0.0,
        steal_latency=5e-4, steal_per_task=1e-5, retry_interval=1e-3,
        class_cost=(1.0, _MULT), class_trace=tuple(cls),
    )
    sim = simulate("a2ws", cfg)
    assert sum(sim.per_node_tasks) == _N
    assert int(np.argmax(sim.per_node_tasks)) == 0
    assert sim.steals > 0

    runs = [_threaded_weighted(seed) for seed in range(3)]
    for st_ in runs:
        assert sum(st_.per_worker_tasks) == _N
    med_w0 = float(np.median([st_.per_worker_tasks[0] for st_ in runs]))
    others = float(np.median([max(st_.per_worker_tasks[1:]) for st_ in runs]))
    assert med_w0 > others, "fast worker must dominate in the threaded plane"
    med_moved = float(
        np.median([sum(s[3] for s in st_.steals) for st_ in runs])
    )
    assert med_moved > 0, "threaded plane never stole"
    hi = max(med_moved, float(sim.moved_tasks))
    assert abs(med_moved - sim.moved_tasks) <= max(8.0, 0.8 * hi), (
        f"steal volume diverged across planes: threaded~{med_moved} "
        f"vs simulated {sim.moved_tasks}"
    )


# ------------------------------------------------------- acceptance criterion
def test_acceptance_weighted_beats_count_on_clustered_bimodal():
    """The PR's acceptance scenario (mirrored in benchmarks/weighted.py):
    heavy shots at every partition block's tail, 16x cost, moderate speed
    spread.  Deterministic virtual time: the median work-weighted makespan
    over six seeds must be ≤ 0.9x the count-based one, and weighted must
    never lose by more than the modelling noise on any seed."""
    speeds = np.asarray((4.0, 2.0, 2.0, 1.0, 1.0, 1.0, 1.0, 1.0))
    n, blk, heavy = 240, 30, 6
    cls: list[int] = []
    for _ in range(len(speeds)):
        cls += [0] * (blk - heavy) + [1] * heavy
    ratios = []
    for seed in range(6):
        cfg = SimConfig(
            speeds=speeds, num_tasks=n, seed=seed, task_cost=6.0,
            class_cost=(1.0, 16.0), class_trace=tuple(cls),
        )
        rw = simulate("a2ws", cfg)
        rc = simulate("a2ws", cfg.with_(weighted=False))
        assert sum(rw.per_node_tasks) == n and sum(rc.per_node_tasks) == n
        ratios.append(rw.makespan / rc.makespan)
    assert float(np.median(ratios)) <= 0.9, f"ratios={ratios}"
    assert max(ratios) <= 1.05, f"weighted lost a seed outright: {ratios}"


# ------------------------------------------------------------- serving layer
def test_request_size_shape_inference():
    assert request_size({"nt": 480}) == 480.0
    assert request_size({"max_new_tokens": 64}) == 64.0
    assert request_size({"prompt": "abcd"}) == 4.0
    assert request_size({"tokens": list(range(7))}) == 7.0
    assert request_size({"mystery": object()}) == 1.0  # lowest class, no error
    clf = shape_cost_classifier((100.0,))
    assert clf({"nt": 60}) == 0 and clf({"nt": 480}) == 1
    clf3 = shape_cost_classifier((10.0, 100.0))
    assert clf3({"nt": 5}) == 0 and clf3({"nt": 50}) == 1 and clf3({"nt": 500}) == 2


def test_servepool_rejects_conflicting_classifier_config():
    with pytest.raises(ValueError):
        ServePool([], cost_class_bounds=(1.0,), cost_class_fn=lambda r: 0)
    with pytest.raises(ValueError):
        ServePool([], cost_class_fn=lambda r: 0)  # num_classes missing


def test_servepool_infers_classes_from_request_shape():
    def gen(request):
        _busy(0.001 * (4.0 if request["nt"] > 100 else 1.0))
        return {"ok": request["nt"]}

    pool = ServePool(
        [Replica("a", gen), Replica("b", gen), Replica("c", gen)],
        seed=0, cost_class_bounds=(100.0,),
    )
    pool.start()
    assert pool._runtime is not None and pool._runtime.weighted
    assert pool._runtime.num_classes == 2
    rng = np.random.default_rng(0)
    futs = []
    for k in range(24):
        time.sleep(float(rng.exponential(1.0 / 500.0)))
        nt = 480 if k % 6 == 0 else 60
        futs.append(pool.submit({"nt": nt}))
    for f in futs:
        assert "ok" in f.result(timeout=30.0)
    stats = pool.shutdown()
    assert sum(stats.per_worker_tasks) == 24
