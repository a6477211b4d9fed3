"""Topology plane (DESIGN.md §Topology plane): the network-cost model, the
``topology=None`` / zero-cost conformance property in both planes, the
pricing behaviours (distance-penalized victims, net-negative refusal, the
hierarchical cross-cell gate, link contention), the serve-plane migration
fold, and the wedged-worker staleness regressions (LimpConfig.stale_after)."""

import threading
import time

import numpy as np
import pytest

from repro.core.a2ws import WorkerPool
from repro.core.limp import LimpConfig, SlowdownEvent
from repro.core.policy import HierarchicalA2WSPolicy
from repro.core.simulator import SimConfig, simulate, table2_speeds
from repro.core.steal import victim_weights
from repro.core.topology import Topology, parse_topology
from repro.serve.engine import Replica, ServePool


# ------------------------------------------------------------------ the model
def test_cost_zero_diagonal_and_uniform():
    topo = Topology.uniform(0.5, 0.1)
    assert topo.cost(3, 3, 100) == 0.0
    assert topo.cost(0, 1) == pytest.approx(0.6)
    assert topo.cost(0, 1, 5) == pytest.approx(0.5 + 5 * 0.1)
    # any worker id is valid (elastic growth)
    assert topo.cost(10_000, 3, 2) == pytest.approx(0.7)
    assert topo.cost(0, 1, -3) == pytest.approx(0.5)  # clamped, not negative


def test_two_level_tiers_from_callable_sequence_and_cellmap():
    for cells in (lambda g: g // 4, [0, 0, 0, 0, 1, 1, 1, 1],
                  HierarchicalA2WSPolicy(8, num_cells=2).cells):
        topo = Topology.two_level(
            cells, intra_latency=0.01, intra_per_task=0.001,
            cross_latency=0.1, cross_per_task=0.02,
        )
        assert topo.cost(0, 1, 1) == pytest.approx(0.011)
        assert topo.cost(0, 5, 1) == pytest.approx(0.12)
        # the acceptance skew: cross >= 10x intra on both terms
        assert topo.cost(0, 5, 1) >= 10 * topo.cost(0, 1, 1)
        # unknown workers (beyond the description) price as CROSS
        assert topo.cost(0, 9_999, 1) == pytest.approx(0.12)


def test_fat_tree_hop_tiers_and_modulo_wrap():
    topo = Topology.fat_tree(4, hop_latency=1.0, hop_per_task=0.5)
    # k=4: 16 hosts, edge groups of 2, pods of 4
    assert topo.cost(0, 1, 0) == 2.0  # same edge switch
    assert topo.cost(0, 2, 0) == 4.0  # same pod, via aggregation
    assert topo.cost(0, 8, 0) == 6.0  # across pods, via core
    assert topo.cost(0, 8, 2) == pytest.approx(6.0 + 2 * 6 * 0.5)
    # ids wrap modulo k^3/4 (elastic joiners reuse physical slots)
    assert topo.cost(16, 0, 0) == 0.0
    assert topo.cost(17, 0, 0) == 2.0
    with pytest.raises(ValueError):
        Topology.fat_tree(3)
    with pytest.raises(ValueError):
        Topology.fat_tree(0)


def test_from_matrix_and_out_of_range_prices_far():
    lat = [[0.0, 1.0], [2.0, 0.0]]
    per = [[0.0, 0.1], [0.2, 0.0]]
    topo = Topology.from_matrix(lat, per)
    assert topo.cost(0, 1, 1) == pytest.approx(1.1)
    assert topo.cost(1, 0, 2) == pytest.approx(2.4)
    # beyond the matrix: the matrix MAXIMUM (unmodelled joiner is far)
    assert topo.cost(0, 7, 1) == pytest.approx(2.0 + 0.2)
    with pytest.raises(ValueError):
        Topology.from_matrix([[0.0, 1.0]])  # not square
    with pytest.raises(ValueError):
        Topology.from_matrix(lat, [[0.0]])  # shape mismatch


def test_add_per_task_folds_migration_into_remote_links():
    topo = Topology.uniform(0.5, 0.1).add_per_task(0.05)
    assert topo.cost(0, 1, 2) == pytest.approx(0.5 + 2 * 0.15)
    assert topo.cost(1, 1, 2) == 0.0  # local stays free
    with pytest.raises(ValueError):
        Topology.uniform().add_per_task(-0.1)
    with pytest.raises(ValueError):
        Topology.uniform().add_per_task(float("nan"))


def test_contention_validation():
    with pytest.raises(ValueError):
        Topology.uniform(contention=-1.0)
    with pytest.raises(ValueError):
        Topology.uniform(contention=float("inf"))


def test_parse_topology_specs_and_errors():
    assert parse_topology(None, 8) is None
    assert parse_topology("none", 8) is None
    assert parse_topology("", 8) is None
    uni = parse_topology("uniform:0.5:0.1", 8)
    assert uni.cost(0, 1, 1) == pytest.approx(0.6)
    two = parse_topology("two-level:2:0.01:0.1", 8)
    assert two.cost(0, 1) == pytest.approx(0.01)  # same contiguous cell of 4
    assert two.cost(0, 4) == pytest.approx(0.1)
    # cross defaults to 10x intra
    assert parse_topology("two-level:2:0.01", 8).cost(0, 4) == pytest.approx(0.1)
    ft = parse_topology("fat-tree:4:0.5", 8)
    assert ft.cost(0, 8) == pytest.approx(3.0)
    with pytest.raises(ValueError):
        parse_topology("mesh:1", 8)
    with pytest.raises(ValueError):
        parse_topology("uniform:abc", 8)


# --------------------------------------- zero-cost conformance (plan level)
def test_victim_weights_zero_cost_hook_is_identity():
    n = [10.0, 2.0, 8.0, 1.0, 9.0]
    t = [0.1, 0.1, 0.2, 0.1, 0.15]
    queued = [8.0, 0.0, 6.0, 0.0, 7.0]
    base = victim_weights(1, n, t, queued, 2)
    hook = victim_weights(1, n, t, queued, 2, tcost=lambda j, k: 0.0)
    assert base[2] == hook[2]
    assert np.array_equal(base[0], hook[0])
    assert np.array_equal(base[1], hook[1])


def _crafted_plans(policy, p, seed, topology):
    """Deterministic boundary plans from a constructed (never started) pool
    with crafted imbalance (mirrors tests/test_hierarchy.py)."""
    pool = WorkerPool(
        list(range(p * 5)), p, lambda w, t: None, policy=policy, seed=seed,
        topology=topology,
    )
    for i in (0, p // 2):
        w = pool.workers[i]
        while w.deque.get_task() is not None:
            pass
    now = pool.clock()
    for i, w in enumerate(pool.workers):
        w.executed, w.runtime_sum, w.ran_any = 5, 5 * 0.05, True
        w.start_time = now - 1e-3
        pool._update_info(i)
    for i in range(p):
        pool.info.communicate(i)
    plans = []
    for i in range(p):
        plan = pool.policy.on_boundary(pool._make_view(i))
        plans.append(
            None if plan is None else
            (plan.victim, plan.amount, plan.criterion, plan.delay, plan.work)
        )
    return plans


@pytest.mark.parametrize("p,seed", [(2, 0), (5, 7), (11, 23), (24, 1234)])
def test_threaded_plans_bit_for_bit_under_zero_cost_topology(p, seed):
    """The conformance property, threaded plane: an all-zero topology model
    produces IDENTICAL boundary plans to topology=None — same victims,
    amounts, criteria, delays, work targets, same rng stream."""
    bare = _crafted_plans("a2ws", p, seed, None)
    zero = _crafted_plans("a2ws", p, seed, Topology.uniform())
    assert bare == zero


@pytest.mark.parametrize(
    "conf,seed,tasks",
    [("C1", 0, 80), ("C4", 3, 120), ("C4", 17, 160), ("C1", 42, 100)],
)
def test_sim_telemetry_bit_for_bit_under_zero_cost_topology(conf, seed, tasks):
    """The conformance property, sim plane, flat scheduler: whole-run
    virtual-time telemetry is bit-for-bit identical between topology=None
    and the all-zero uniform topology."""
    cfg = SimConfig(speeds=table2_speeds(conf), num_tasks=tasks, seed=seed)
    bare = simulate("a2ws", cfg)
    zero = simulate("a2ws", cfg.with_(topology=Topology.uniform()))
    assert zero.makespan == bare.makespan
    assert zero.per_node_tasks == bare.per_node_tasks
    assert zero.per_node_busy == bare.per_node_busy
    assert zero.records == bare.records
    assert (zero.steals, zero.failed_steals, zero.moved_tasks,
            zero.boundaries) == (bare.steals, bare.failed_steals,
                                 bare.moved_tasks, bare.boundaries)


@pytest.mark.parametrize("seed", [0, 11, 37])
def test_sim_telemetry_bit_for_bit_zero_cost_hierarchical(seed):
    """The conformance property for the hierarchical scheduler: the leader
    balancer's cross-cell gate must not perturb anything at zero cost."""
    p = 64
    cfg = SimConfig(speeds=table2_speeds("C4"), num_tasks=220, seed=seed)
    bare = simulate(HierarchicalA2WSPolicy(p), cfg)
    zero = simulate(
        HierarchicalA2WSPolicy(p),
        cfg.with_(topology=Topology.uniform()),
    )
    assert zero.makespan == bare.makespan
    assert zero.per_node_tasks == bare.per_node_tasks
    assert zero.records == bare.records
    assert (zero.steals, zero.moved_tasks) == (bare.steals, bare.moved_tasks)


# ------------------------------------------------------- pricing behaviours
def test_sim_expensive_uniform_topology_suppresses_stealing():
    """When every link costs more than the work it could move, the priced
    scheduler refuses steals the blind scheduler happily fires."""
    speeds = table2_speeds("C4")[:16]
    cfg = SimConfig(speeds=speeds, num_tasks=64, seed=0, task_cost=0.05)
    topo = Topology.uniform(50.0, 10.0)  # any steal costs >> total work
    free = simulate("a2ws", cfg)
    priced = simulate("a2ws", cfg.with_(topology=topo))
    blind = simulate("a2ws", cfg.with_(topology=topo, topology_aware=False))
    assert free.steals > 0
    assert blind.steals > 0  # blind plans as if the network were free
    assert priced.steals < blind.steals
    assert priced.moved_tasks < blind.moved_tasks


def test_hierarchical_balancer_refuses_net_negative_cross_cell_batches():
    p = 128
    speeds = tuple(np.tile(table2_speeds("C4", order="blocked"), p // 64))
    cfg = SimConfig(speeds=speeds, num_tasks=p * 4, seed=0, task_cost=2.0)
    pol = HierarchicalA2WSPolicy(p)
    topo = Topology.two_level(
        pol.cells, cross_latency=1e4, cross_per_task=1e3,
    )
    res = simulate(pol, cfg.with_(topology=topo))
    cell_of = pol.cells.cell_of
    xmoved = sum(t for _t, i, v, t in res.steal_log
                 if cell_of(i) != cell_of(v))
    assert pol.xcell_refused > 0, "balancer never priced a batch out"
    assert xmoved == 0, "net-negative cross-cell batches still moved loot"


def test_sim_link_contention_changes_transfer_timing():
    """contention=1 queues repeated transfers on one directed link behind
    each other.  Delayed arrivals feed back into scheduling decisions, so
    whole-run makespan is NOT monotone — the pinned property is that the
    knob is actually exercised (a directed link is reused) and that it
    perturbs the trajectory while conserving every task."""
    speeds = (4.0, 1.0, 1.0, 1.0)
    cfg = SimConfig(speeds=speeds, num_tasks=64, seed=0, task_cost=0.2)
    fluid = simulate("a2ws", cfg.with_(topology=Topology.uniform(0.05, 0.01)))
    jammed = simulate(
        "a2ws",
        cfg.with_(topology=Topology.uniform(0.05, 0.01, contention=1.0)),
    )
    links = [(v, i) for _t, i, v, _k in jammed.steal_log]
    assert len(links) > len(set(links)), "no directed link ever reused"
    assert jammed.steals > 0
    assert sum(jammed.per_node_tasks) == cfg.num_tasks
    assert (jammed.makespan, jammed.steal_log) != (
        fluid.makespan, fluid.steal_log
    ), "contention knob had no effect on the trajectory"


def test_steal_log_records_every_transfer():
    cfg = SimConfig(speeds=table2_speeds("C4")[:8], num_tasks=64, seed=0)
    res = simulate("a2ws", cfg)
    assert len(res.steal_log) == res.steals
    assert sum(take for *_x, take in res.steal_log) == res.moved_tasks
    for t, thief, victim, take in res.steal_log:
        assert 0.0 <= t <= res.makespan
        assert thief != victim
        assert take >= 1


# ------------------------------------------------------------- serve plane
def test_servepool_migration_cost_folds_into_topology():
    pool = ServePool(
        [Replica(f"r{i}", lambda req: {"ok": True}) for i in range(2)],
        seed=0, migration_cost=0.25,
    )
    assert pool.topology is not None
    assert pool.topology.cost(0, 1, 2) == pytest.approx(0.5)
    assert pool.topology.cost(0, 0, 2) == 0.0
    base = Topology.uniform(0.1, 0.05)
    pool2 = ServePool(
        [Replica(f"q{i}", lambda req: {"ok": True}) for i in range(2)],
        seed=0, topology=base, migration_cost=0.25,
    )
    assert pool2.topology.cost(0, 1, 1) == pytest.approx(0.1 + 0.3)
    with pytest.raises(ValueError):
        ServePool([Replica("z", lambda r: r)], migration_cost=-1.0)


def test_servepool_serves_with_priced_topology():
    def gen(req):
        time.sleep(0.002)
        return {"ok": True}

    pool = ServePool(
        [Replica(f"r{i}", gen) for i in range(2)],
        seed=0, topology=Topology.uniform(0.001, 0.0005),
        migration_cost=0.001,
    )
    pool.start()
    futs = [pool.submit({"x": i}) for i in range(16)]
    for f in futs:
        assert f.result(timeout=30)["ok"]
    stats = pool.shutdown()
    assert sum(stats.per_worker_tasks) == 16


# ------------------------------------- wedged-worker staleness (satellite 1)
def test_limp_config_stale_after_validation():
    assert not np.isfinite(LimpConfig().stale_after)  # default: disabled
    assert LimpConfig(stale_after=2.0).stale_after == 2.0
    with pytest.raises(ValueError):
        LimpConfig(stale_after=0.0)
    with pytest.raises(ValueError):
        LimpConfig(stale_after=-1.0)


def test_sim_wedge_staleness_closes_factor_inf_blind_spot():
    """The PR-5 detector only observes COMPLETED tasks, so a worker wedged
    inside one task — a 200x slowdown that outlives the whole healthy run,
    indistinguishable from factor-infinity while it lasts — never flags:
    the owner-side EWMA is silent until the stuck task itself completes,
    ~200 s too late.  stale_after closes the blind spot from the PEER
    side: the heartbeat goes stale, peers flag the wedge within seconds,
    hold the flag for the whole silence, and hand the verdict back to the
    owner EWMA once the heartbeat returns."""
    base = SimConfig(
        speeds=np.ones(4), num_tasks=600, task_cost=1.0, seed=0,
        arrival="poisson", arrival_rate=2.0,
        slowdowns=(SlowdownEvent(1, 20.0, 200.0, duration=200.0),),
    )
    blind = simulate("a2ws", base.with_(limp=LimpConfig()))
    wedge = simulate("a2ws", base.with_(limp=LimpConfig(stale_after=2.0)))
    # The owner-side EWMA alone is silent for the whole wedge — its first
    # chance to flag is the stuck task's own completion, ~200 s too late.
    early_blind = [t for t, w, f in blind.limp_events if w == 1 and f]
    assert not early_blind or early_blind[0] > 200.0
    # The peer-side heartbeat check fires within seconds of the wedge...
    flags = [t for t, w, f in wedge.limp_events if w == 1 and f]
    assert flags and 20.0 < flags[0] < 30.0
    # ...holds the flag for the entire silence (no flapping mid-wedge)...
    unflags = [t for t, w, f in wedge.limp_events if w == 1 and not f]
    assert all(t > 200.0 for t in unflags)
    # ...and releases it once the heartbeat returns (the wedged task
    # completes at ~220 s), handing the verdict back to the owner EWMA.
    assert unflags and unflags[0] < base.num_tasks  # well before drain-end
    # Healthy workers are never dragged in by the staleness check: an idle
    # poll IS a heartbeat, so only the worker stuck INSIDE a task flags.
    assert not [t for t, w, f in wedge.limp_events if w != 1 and f]
    # Both legs still run every task to completion.
    assert sum(blind.per_node_tasks) == base.num_tasks
    assert sum(wedge.per_node_tasks) == base.num_tasks
    # The wedge-aware grid stays bounded for the 95% of tasks that peers
    # can rescue (the in-flight victim itself is unsaveable in both legs).
    assert wedge.latency_percentiles((95.0,))[95.0] < 60.0


def test_threaded_wedge_staleness_flags_blocked_worker():
    """Real threads: a worker wedged inside a task (never reaching a
    boundary, so its own ring version stands still) is flagged by peers via
    the heartbeat check, and the pool drains cleanly after release.  The
    wedge is submitted to worker 1, but an idle peer may steal it before
    worker 1 pops it: the worker that runs it is the one that must be
    flagged."""
    gate = threading.Event()
    wedged: list[int] = []

    def task_fn(wid, task):
        if task == "wedge":
            wedged.append(wid)
            gate.wait(timeout=30.0)
        else:
            time.sleep(0.002)

    pool = WorkerPool(
        [], 3, task_fn, policy="a2ws", open_arrival=True, seed=0,
        limp=LimpConfig(stale_after=0.3),
    )
    pool.start()
    pool.submit_many(["t%d" % i for i in range(30)])
    deadline = time.time() + 5.0
    while pool.pending() and time.time() < deadline:
        time.sleep(0.002)
    pool.submit("wedge", worker=1)
    deadline = time.time() + 10.0
    while not (wedged and pool.limping(wedged[0])) and time.time() < deadline:
        pool.submit_many(["u%d" % i for i in range(4)])
        time.sleep(0.05)
    assert wedged, "the wedge task never started"
    assert pool.limping(wedged[0]), "wedged worker never flagged by peers"
    assert any(w == wedged[0] and f for _t, w, f in pool.limp_log)
    gate.set()
    pool.drain()
    stats = pool.join()
    assert sum(stats.per_worker_tasks) == pool.done_counter.load()
