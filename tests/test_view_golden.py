"""Golden record of the A2WS information model in both planes.

Each case pins what a plane concluded on a fixed input: the simulator's
``SimResult`` summary for a config, and the threaded ``WorkerPool``'s
``PolicyView`` on an unstarted pool whose boards are set by hand under a
constant clock.  The expected values were recorded from the code before
the model moved behind ``repro.core.view``; a refactor of either plane's
reader must reproduce them.  Integers and flags compare exactly, floats to
a relative 1e-12.
"""

from __future__ import annotations

import math

import numpy as np
import pytest

from repro.core.a2ws import WorkerPool
from repro.core.limp import LimpConfig, SlowdownEvent, SlowdownSchedule
from repro.core.netfault import LinkFault, NetFaultSchedule, PartitionEvent
from repro.core.policy import HierarchicalA2WSPolicy
from repro.core.simulator import SimConfig, simulate, table2_speeds
from repro.core.topology import Topology

NOW = 100.0  # the threaded cases' constant clock


# --------------------------------------------------------------- simulator
def _sim_configs() -> dict:
    c1 = table2_speeds("C1")
    c2 = table2_speeds("C2")
    return {
        "flat_c1_closed": ("a2ws", SimConfig(speeds=c1, num_tasks=240, seed=1)),
        "poisson_open": ("a2ws", SimConfig(
            speeds=c1, num_tasks=300, seed=2, arrival="poisson",
            arrival_rate=1.5,
        )),
        "weighted_bimodal": ("a2ws", SimConfig(
            speeds=c1, num_tasks=300, seed=3, class_cost=(1.0, 8.0),
            class_probs=(0.75, 0.25),
        )),
        "limp_stale_after": ("a2ws", SimConfig(
            speeds=c1, num_tasks=320, seed=4,
            slowdowns=SlowdownSchedule((
                SlowdownEvent(worker=0, start=40.0, factor=100.0),
                SlowdownEvent(worker=2, start=0.0, factor=12.0),
            )),
            limp=LimpConfig(stale_after=90.0),
        )),
        "hier_two_cells": (
            HierarchicalA2WSPolicy(len(c2), num_cells=2),
            SimConfig(speeds=c2, num_tasks=480, seed=5),
        ),
        "netfault_partition": ("a2ws", SimConfig(
            speeds=c1, num_tasks=320, seed=6,
            netfaults=NetFaultSchedule(
                faults=(LinkFault(src=0, dst=None, drop_prob=0.3),),
                partitions=(PartitionEvent(side=(0, 1, 2), start=20.0,
                                           duration=60.0),),
                stale_after=5.0,
            ),
            limp=LimpConfig(),
        )),
        # radius 2 on a 4-ring: both ends of the window land on one slot
        "small_ring_wedge": ("a2ws", SimConfig(
            speeds=(4.0, 1.0, 2.0, 8.0), num_tasks=120, seed=7, radius=2,
            slowdowns=SlowdownSchedule((
                SlowdownEvent(worker=2, start=30.0, factor=12.0),
            )),
            limp=LimpConfig(stale_after=60.0),
        )),
        # two peers wedged at once: the flags are raised in window order
        "odd_ring_two_wedges": ("a2ws", SimConfig(
            speeds=(2.0,) * 5, num_tasks=60, seed=9, radius=2, noise=0.0,
            slowdowns=SlowdownSchedule((
                SlowdownEvent(worker=1, start=0.0, factor=20.0),
                SlowdownEvent(worker=4, start=0.0, factor=20.0),
            )),
            limp=LimpConfig(stale_after=50.0),
        )),
        "weighted_churn_topology": ("a2ws", SimConfig(
            speeds=c1, num_tasks=300, seed=8, class_cost=(1.0, 3.0, 9.0),
            joins=((30.0, 4.0), (60.0, 1.0)), retires=((90.0, 3),),
            topology=Topology.uniform(0.5, 0.05),
        )),
    }


def _sim_summary(res) -> dict:
    return {
        "makespan": float(res.makespan),
        "steals": res.steals,
        "failed_steals": res.failed_steals,
        "moved_tasks": res.moved_tasks,
        "per_node_tasks": list(res.per_node_tasks),
        "boundaries": res.boundaries,
        "limp_events": [[float(t), int(g), bool(f)] for t, g, f in res.limp_events],
        "net_failed": res.net_failed,
        "lease_expired": res.lease_expired,
        "lost_tasks": res.lost_tasks,
        "latency_sum": float(sum(res.latencies)),
    }


# ----------------------------------------------------------------- threaded
def _pool(p: int = 6, tasks: int = 30, **kw) -> WorkerPool:
    kw.setdefault("policy", "a2ws")
    return WorkerPool(
        list(range(tasks)), p, lambda w, t: None, seed=3,
        clock=lambda: NOW, **kw,
    )


def _report(pool: WorkerPool, silent=(), rounds: int = 2) -> None:
    """Hand-set run state, publish every non-silent worker, gossip."""
    for i, w in enumerate(pool.workers):
        w.start_time = 90.0
        w.executed = 2 + i
        w.runtime_sum = (2 + i) * (0.5 + 0.25 * i)
        w.ran_any = True
    for i in range(pool.num_workers):
        if i not in silent:
            pool._update_info(i)
    for _ in range(rounds):
        for i in range(pool.num_workers):
            if i not in silent:
                pool.info.communicate(i)


def _arr(a):
    return None if a is None else np.asarray(a).tolist()


def _view_record(pool: WorkerPool, i: int) -> dict:
    v = pool._make_view(i)
    m = v.num_workers
    rec = {
        "worker": v.worker, "now": v.now, "idle": v.idle,
        "ran_any": v.ran_any, "open_arrival": v.open_arrival,
        "radius": v.radius, "num_workers": m, "window": list(v.window),
        "depth": [int(v.depth(j)) for j in range(m)],
        "alive": [bool(v.alive(j)) for j in range(m)],
        "pending": v.pending(),
    }
    for name in ("n_view", "t_view", "queued", "unit", "qtasks", "rel",
                 "ntasks", "limp", "members", "nc_view"):
        rec[name] = _arr(getattr(v, name))
    rec["transfer_cost"] = (
        None if v.transfer_cost is None
        else [v.transfer_cost(j, 2) for j in range(m)]
    )
    rec["link_health"] = (
        None if v.link_health is None
        else [v.link_health(j) for j in range(m)]
    )
    return rec


def _threaded_case(name: str) -> dict:
    out: dict = {}
    if name == "closed":
        pool = _pool(radius=2)
        _report(pool)
        out["views"] = [_view_record(pool, i) for i in (0, 3)]
    elif name == "open":
        pool = _pool(radius=2, open_arrival=True)
        _report(pool)
        pool.workers[2].deque.get_task()
        out["views"] = [_view_record(pool, i) for i in (0, 2)]
    elif name == "nan_t":
        pool = _pool(radius=2)
        _report(pool, silent=(4, 5))
        out["views"] = [_view_record(pool, i) for i in (0, 1)]
    elif name == "tombstone":
        pool = _pool(radius=2)
        _report(pool)
        pool.dead[1] = True
        pool.workers[1].deque.push([101, 102])
        out["views"] = [_view_record(pool, i) for i in (0, 2)]
    elif name == "hole":
        pol = HierarchicalA2WSPolicy(8, num_cells=2)
        pool = _pool(p=8, tasks=40, policy=pol)
        _report(pool)
        pool.info.migrate(2, 1)  # slot 2 of cell 0 becomes a hole
        pool._update_info(2)
        for i in range(8):
            pool.info.communicate(i)
        out["views"] = [_view_record(pool, i) for i in (0, 2, 5)]
    elif name == "wedge":
        pool = _pool(radius=2, limp=LimpConfig(stale_after=0.5))
        pool._limping[0] = True
        pool.workers[0].limp_state.recent = 7.5  # own re-pricing
        _report(pool)
        pool._hb_beat[1] = 99.0     # silent past stale_after: wedged
        pool._hb_beat[5] = 99.9     # fresh
        pool._stale_flagged[2] = True  # beat again: verdict handed back
        pool.workers[2].limp_state.limping = False
        pool._limping[2] = True
        out["views"] = [_view_record(pool, i) for i in (0, 3)]
        out["limping"] = list(pool._limping)
        out["stale_flagged"] = list(pool._stale_flagged)
        out["limp_log"] = [
            [float(t), int(g), bool(f)] for t, g, f in pool.limp_log
        ]
        out["published_t0"] = float(pool.info.t[0, 0])
    elif name == "partition":
        nf = NetFaultSchedule(
            partitions=(PartitionEvent(side=(4, 5), start=2.0),),
            stale_after=0.5,
        )
        pool = _pool(radius=2, netfaults=nf, limp=LimpConfig(),
                     topology=Topology.uniform(0.25, 0.125))
        _report(pool)
        pool._t0 = 90.0
        pool._hb_beat[1] = 99.75
        out["views"] = [_view_record(pool, i) for i in (0, 4)]
    elif name == "weighted":
        pool = _pool(radius=2, tasks=36, cost_class_fn=lambda t: t % 3,
                     num_classes=3)
        for i, w in enumerate(pool.workers):
            w.class_t[:] = [0.5 + 0.1 * i, 1.5 + 0.2 * i, np.nan]
        pool.workers[4].class_t[2] = 4.0 + 0.5 * 4
        _report(pool)
        pool.dead[5] = True
        pool.workers[5].deque.push([101, 104, 107])  # orphans of class 2
        out["views"] = [_view_record(pool, i) for i in (0, 3, 4)]
    else:  # pragma: no cover
        raise KeyError(name)
    return out


THREADED_CASES = (
    "closed", "open", "nan_t", "tombstone", "hole", "wedge", "partition",
    "weighted",
)


# ------------------------------------------------------------- comparison
def _same(got, want, path="") -> None:
    if isinstance(want, dict):
        assert isinstance(got, dict) and set(got) == set(want), path
        for k in want:
            _same(got[k], want[k], f"{path}.{k}")
    elif isinstance(want, (list, tuple)):
        assert isinstance(got, (list, tuple)) and len(got) == len(want), path
        for k, (g, w) in enumerate(zip(got, want)):
            _same(g, w, f"{path}[{k}]")
    elif isinstance(want, bool) or want is None:
        assert got is want or got == want, (path, got, want)
    elif isinstance(want, float):
        if math.isnan(want):
            assert isinstance(got, float) and math.isnan(got), (path, got)
        else:
            assert got == pytest.approx(want, rel=1e-12, abs=0.0), (
                path, got, want,
            )
    else:
        assert got == want, (path, got, want)


@pytest.mark.parametrize("name", sorted(_sim_configs()))
def test_simulate_summary_matches_golden(name):
    policy, cfg = _sim_configs()[name]
    _same(_sim_summary(simulate(policy, cfg)), GOLDEN_SIM[name], name)


@pytest.mark.parametrize("name", THREADED_CASES)
def test_threaded_view_matches_golden(name):
    _same(_threaded_case(name), GOLDEN_THREADED[name], name)


def record() -> tuple[dict, dict]:
    """Recompute both golden tables (run by hand when the model's rules
    change on purpose, then paste the output below)."""
    sims = {
        name: _sim_summary(simulate(pol, cfg))
        for name, (pol, cfg) in _sim_configs().items()
    }
    threaded = {name: _threaded_case(name) for name in THREADED_CASES}
    return sims, threaded


# fmt: off
GOLDEN_SIM: dict = {'flat_c1_closed': {'boundaries': 319,
                    'failed_steals': 0,
                    'latency_sum': 0.0,
                    'lease_expired': 0,
                    'limp_events': [],
                    'lost_tasks': 0,
                    'makespan': 183.31417457574176,
                    'moved_tasks': 197,
                    'net_failed': 0,
                    'per_node_tasks': [72, 47, 24, 12, 6, 3, 73, 3],
                    'steals': 48},
 'hier_two_cells': {'boundaries': 709,
                    'failed_steals': 3,
                    'latency_sum': 0.0,
                    'lease_expired': 0,
                    'limp_events': [],
                    'lost_tasks': 0,
                    'makespan': 184.01048134279938,
                    'moved_tasks': 385,
                    'net_failed': 0,
                    'per_node_tasks': [73, 48, 23, 12, 5, 3, 72, 48, 24, 12, 6,
                                       3, 72, 3, 73, 3],
                    'steals': 130},
 'limp_stale_after': {'boundaries': 857,
                      'failed_steals': 410,
                      'latency_sum': 0.0,
                      'lease_expired': 0,
                      'limp_events': [[15.300768625622743, 3, True],
                                      [29.9859860899951, 3, False],
                                      [60.26933409528115, 7, True],
                                      [93.6599587324346, 2, True],
                                      [133.03073159285304, 0, True],
                                      [179.82756889432744, 7, False],
                                      [277.60758520729377, 2, False]],
                      'lost_tasks': 0,
                      'makespan': 411.68141901601854,
                      'moved_tasks': 444,
                      'net_failed': 0,
                      'per_node_tasks': [18, 88, 3, 22, 13, 6, 164, 6],
                      'steals': 86},
 'netfault_partition': {'boundaries': 522,
                        'failed_steals': 13,
                        'latency_sum': 0.0,
                        'lease_expired': 6,
                        'limp_events': [[30.92756214188512, 4, True],
                                        [62.48509334082767, 5, True],
                                        [62.75744517744133, 7, True],
                                        [91.10775560244176, 4, False],
                                        [180.61842454331824, 7, False]],
                        'lost_tasks': 0,
                        'makespan': 244.05425128772606,
                        'moved_tasks': 1004,
                        'net_failed': 13,
                        'per_node_tasks': [98, 64, 32, 16, 7, 2, 97, 4],
                        'steals': 115},
 'odd_ring_two_wedges': {'boundaries': 2234,
                         'failed_steals': 0,
                         'latency_sum': 0.0,
                         'lease_expired': 0,
                         'limp_events': [[60.0024, 4, True],
                                         [60.0024, 1, True]],
                         'lost_tasks': 0,
                         'makespan': 1200.0024,
                         'moved_tasks': 22,
                         'net_failed': 0,
                         'per_node_tasks': [19, 2, 19, 19, 1],
                         'steals': 12},
 'poisson_open': {'boundaries': 547,
                  'failed_steals': 277,
                  'latency_sum': 6771.311775196337,
                  'lease_expired': 0,
                  'limp_events': [],
                  'lost_tasks': 0,
                  'makespan': 245.3167214275136,
                  'moved_tasks': 213,
                  'net_failed': 0,
                  'per_node_tasks': [89, 60, 30, 15, 8, 4, 90, 4],
                  'steals': 195},
 'small_ring_wedge': {'boundaries': 250,
                      'failed_steals': 0,
                      'latency_sum': 0.0,
                      'lease_expired': 0,
                      'limp_events': [[60.14545709432364, 1, True],
                                      [120.91207212230954, 2, True],
                                      [179.85509859762303, 1, False],
                                      [240.25816239642745, 1, True],
                                      [240.4943273995149, 1, False],
                                      [478.44376210565764, 1, True],
                                      [480.866242460092, 1, False]],
                      'lost_tasks': 0,
                      'makespan': 539.9863193594437,
                      'moved_tasks': 64,
                      'net_failed': 0,
                      'per_node_tasks': [36, 9, 3, 72],
                      'steals': 23},
 'weighted_bimodal': {'boundaries': 1294,
                      'failed_steals': 13,
                      'latency_sum': 0.0,
                      'lease_expired': 0,
                      'limp_events': [],
                      'lost_tasks': 0,
                      'makespan': 750.1777373830929,
                      'moved_tasks': 271,
                      'net_failed': 0,
                      'per_node_tasks': [100, 52, 43, 8, 6, 5, 82, 4],
                      'steals': 49},
 'weighted_churn_topology': {'boundaries': 2543,
                             'failed_steals': 0,
                             'latency_sum': 0.0,
                             'lease_expired': 0,
                             'limp_events': [],
                             'lost_tasks': 0,
                             'makespan': 1246.7596064585814,
                             'moved_tasks': 477,
                             'net_failed': 0,
                             'per_node_tasks': [88, 64, 31, 1, 6, 4, 86, 4, 12,
                                                4],
                             'steals': 48}}

GOLDEN_THREADED: dict = {'closed': {'views': [{'alive': [True, True, True, True, True, True],
                       'depth': [5, 5, 5, 5, 5, 5],
                       'idle': False,
                       'limp': None,
                       'link_health': None,
                       'members': None,
                       'n_view': [7.0, 8.0, 9.0, 0.0, 11.0, 12.0],
                       'nc_view': None,
                       'now': 100.0,
                       'ntasks': None,
                       'num_workers': 6,
                       'open_arrival': False,
                       'pending': 30,
                       'qtasks': None,
                       'queued': [5.0, 0.0, 0.0, 0.0, 4.333333333333333,
                                  6.285714285714286],
                       'radius': 2,
                       'ran_any': True,
                       'rel': None,
                       't_view': [0.5, 0.75, 1.0, 1.1, 1.5, 1.75],
                       'transfer_cost': None,
                       'unit': None,
                       'window': [4, 5, 0, 1, 2],
                       'worker': 0},
                      {'alive': [True, True, True, True, True, True],
                       'depth': [5, 5, 5, 5, 5, 5],
                       'idle': False,
                       'limp': None,
                       'link_health': None,
                       'members': None,
                       'n_view': [0.0, 8.0, 9.0, 10.0, 11.0, 12.0],
                       'nc_view': None,
                       'now': 100.0,
                       'ntasks': None,
                       'num_workers': 6,
                       'open_arrival': False,
                       'pending': 30,
                       'qtasks': None,
                       'queued': [0.0, 0.0, 0.0, 5.0, 4.333333333333333,
                                  6.285714285714286],
                       'radius': 2,
                       'ran_any': True,
                       'rel': None,
                       't_view': [1.25, 0.75, 1.0, 1.25, 1.5, 1.75],
                       'transfer_cost': None,
                       'unit': None,
                       'window': [1, 2, 3, 4, 5],
                       'worker': 3}]},
 'hole': {'views': [{'alive': [True, True, False, True],
                     'depth': [5, 5, 0, 5],
                     'idle': False,
                     'limp': None,
                     'link_health': None,
                     'members': [0, 1, -1, 3],
                     'n_view': [7.0, 8.0, 0.0, 10.0],
                     'nc_view': None,
                     'now': 100.0,
                     'ntasks': None,
                     'num_workers': 4,
                     'open_arrival': False,
                     'pending': 40,
                     'qtasks': None,
                     'queued': [5.0, 0.0, 0.0, 2.0],
                     'radius': 2,
                     'ran_any': True,
                     'rel': None,
                     't_view': [0.5, 0.75, 1000000000000.0, 1.25],
                     'transfer_cost': None,
                     'unit': None,
                     'window': [0, 1, 2, 3],
                     'worker': 0},
                    {'alive': [True, True, True, True, True],
                     'depth': [5, 5, 5, 5, 5],
                     'idle': False,
                     'limp': None,
                     'link_health': None,
                     'members': [4, 5, 6, 7, 2],
                     'n_view': [0.0, 0.0, 0.0, 0.0, 9.0],
                     'nc_view': None,
                     'now': 100.0,
                     'ntasks': None,
                     'num_workers': 5,
                     'open_arrival': False,
                     'pending': 40,
                     'qtasks': None,
                     'queued': [0.0, 0.0, 0.0, 0.0, 5.0],
                     'radius': 2,
                     'ran_any': True,
                     'rel': None,
                     't_view': [10.0, 10.0, 10.0, 10.0, 1.0],
                     'transfer_cost': None,
                     'unit': None,
                     'window': [0, 1, 2, 3, 4],
                     'worker': 4},
                    {'alive': [True, True, True, True, True],
                     'depth': [5, 5, 5, 5, 5],
                     'idle': False,
                     'limp': None,
                     'link_health': None,
                     'members': [4, 5, 6, 7, 2],
                     'n_view': [11.0, 12.0, 13.0, 14.0, 9.0],
                     'nc_view': None,
                     'now': 100.0,
                     'ntasks': None,
                     'num_workers': 5,
                     'open_arrival': False,
                     'pending': 40,
                     'qtasks': None,
                     'queued': [4.333333333333333, 5.0, 8.0, 9.555555555555555,
                                0.0],
                     'radius': 2,
                     'ran_any': True,
                     'rel': None,
                     't_view': [1.5, 1.75, 2.0, 2.25, 1.0],
                     'transfer_cost': None,
                     'unit': None,
                     'window': [0, 1, 2, 3, 4],
                     'worker': 1}]},
 'nan_t': {'views': [{'alive': [True, True, True, True, True, True],
                      'depth': [5, 5, 5, 5, 5, 5],
                      'idle': False,
                      'limp': None,
                      'link_health': None,
                      'members': None,
                      'n_view': [7.0, 8.0, 9.0, 0.0, 0.0, 0.0],
                      'nc_view': None,
                      'now': 100.0,
                      'ntasks': None,
                      'num_workers': 6,
                      'open_arrival': False,
                      'pending': 30,
                      'qtasks': None,
                      'queued': [5.0, 0.0, 0.0, 0.0, 0.0, 0.0],
                      'radius': 2,
                      'ran_any': True,
                      'rel': None,
                      't_view': [0.5, 0.75, 1.0, 0.75, 10.0, 10.0],
                      'transfer_cost': None,
                      'unit': None,
                      'window': [4, 5, 0, 1, 2],
                      'worker': 0},
                     {'alive': [True, True, True, True, True, True],
                      'depth': [5, 5, 5, 5, 5, 5],
                      'idle': False,
                      'limp': None,
                      'link_health': None,
                      'members': None,
                      'n_view': [7.0, 8.0, 9.0, 10.0, 0.0, 0.0],
                      'nc_view': None,
                      'now': 100.0,
                      'ntasks': None,
                      'num_workers': 6,
                      'open_arrival': False,
                      'pending': 30,
                      'qtasks': None,
                      'queued': [0.0, 5.0, 0.0, 2.0, 0.0, 0.0],
                      'radius': 2,
                      'ran_any': True,
                      'rel': None,
                      't_view': [0.5, 0.75, 1.0, 1.25, 0.875, 10.0],
                      'transfer_cost': None,
                      'unit': None,
                      'window': [5, 0, 1, 2, 3],
                      'worker': 1}]},
 'open': {'views': [{'alive': [True, True, True, True, True, True],
                     'depth': [5, 5, 4, 5, 5, 5],
                     'idle': False,
                     'limp': None,
                     'link_health': None,
                     'members': None,
                     'n_view': [5.0, 5.0, 5.0, 0.0, 5.0, 5.0],
                     'nc_view': None,
                     'now': 100.0,
                     'ntasks': None,
                     'num_workers': 6,
                     'open_arrival': True,
                     'pending': 30,
                     'qtasks': None,
                     'queued': [5.0, 5.0, 5.0, 0.0, 5.0, 5.0],
                     'radius': 2,
                     'ran_any': True,
                     'rel': None,
                     't_view': [0.5, 0.75, 1.0, 1.1, 1.5, 1.75],
                     'transfer_cost': None,
                     'unit': None,
                     'window': [4, 5, 0, 1, 2],
                     'worker': 0},
                    {'alive': [True, True, True, True, True, True],
                     'depth': [5, 5, 4, 5, 5, 5],
                     'idle': False,
                     'limp': None,
                     'link_health': None,
                     'members': None,
                     'n_view': [5.0, 5.0, 4.0, 5.0, 5.0, 0.0],
                     'nc_view': None,
                     'now': 100.0,
                     'ntasks': None,
                     'num_workers': 6,
                     'open_arrival': True,
                     'pending': 30,
                     'qtasks': None,
                     'queued': [5.0, 5.0, 4.0, 5.0, 5.0, 0.0],
                     'radius': 2,
                     'ran_any': True,
                     'rel': None,
                     't_view': [0.5, 0.75, 1.0, 1.25, 1.5, 1.0],
                     'transfer_cost': None,
                     'unit': None,
                     'window': [0, 1, 2, 3, 4],
                     'worker': 2}]},
 'partition': {'views': [{'alive': [True, True, True, True, True, True],
                          'depth': [5, 5, 5, 5, 5, 5],
                          'idle': False,
                          'limp': [False, False, False, False, True, True],
                          'link_health': [1.0, 1.0, 1.0, 1.0, 0.0, 0.0],
                          'members': None,
                          'n_view': [7.0, 8.0, 9.0, 0.0, 11.0, 12.0],
                          'nc_view': None,
                          'now': 100.0,
                          'ntasks': None,
                          'num_workers': 6,
                          'open_arrival': False,
                          'pending': 30,
                          'qtasks': None,
                          'queued': [5.0, 0.0, 0.0, 0.0, 9.75, 10.75],
                          'radius': 2,
                          'ran_any': True,
                          'rel': None,
                          't_view': [0.5, 0.75, 1.0, 1.1, 8.0, 8.0],
                          'transfer_cost': [0.0, 0.5, 0.5, 0.5, 0.5, 0.5],
                          'unit': None,
                          'window': [4, 5, 0, 1, 2],
                          'worker': 0},
                         {'alive': [True, True, True, True, True, True],
                          'depth': [5, 5, 5, 5, 5, 5],
                          'idle': False,
                          'limp': [True, False, True, True, False, False],
                          'link_health': [0.0, 0.0, 0.0, 0.0, 1.0, 1.0],
                          'members': None,
                          'n_view': [7.0, 0.0, 9.0, 10.0, 11.0, 12.0],
                          'nc_view': None,
                          'now': 100.0,
                          'ntasks': None,
                          'num_workers': 6,
                          'open_arrival': False,
                          'pending': 30,
                          'qtasks': None,
                          'queued': [5.75, 0.0, 7.75, 8.75, 5.0,
                                     6.285714285714286],
                          'radius': 2,
                          'ran_any': True,
                          'rel': None,
                          't_view': [8.0, 1.2, 8.0, 8.0, 1.5, 1.75],
                          'transfer_cost': [0.5, 0.5, 0.5, 0.5, 0.0, 0.5],
                          'unit': None,
                          'window': [2, 3, 4, 5, 0],
                          'worker': 4}]},
 'tombstone': {'views': [{'alive': [True, False, True, True, True, True],
                          'depth': [5, 7, 5, 5, 5, 5],
                          'idle': False,
                          'limp': None,
                          'link_health': None,
                          'members': None,
                          'n_view': [7.0, 10.0, 9.0, 0.0, 11.0, 12.0],
                          'nc_view': None,
                          'now': 100.0,
                          'ntasks': None,
                          'num_workers': 6,
                          'open_arrival': False,
                          'pending': 30,
                          'qtasks': None,
                          'queued': [5.0, 7.0, 0.0, 0.0, 4.333333333333333,
                                     6.285714285714286],
                          'radius': 2,
                          'ran_any': True,
                          'rel': None,
                          't_view': [0.5, 1000000000000.0, 1.0, 1.1, 1.5,
                                     1.75],
                          'transfer_cost': None,
                          'unit': None,
                          'window': [4, 5, 0, 1, 2],
                          'worker': 0},
                         {'alive': [True, False, True, True, True, True],
                          'depth': [5, 7, 5, 5, 5, 5],
                          'idle': False,
                          'limp': None,
                          'link_health': None,
                          'members': None,
                          'n_view': [7.0, 10.0, 9.0, 10.0, 11.0, 0.0],
                          'nc_view': None,
                          'now': 100.0,
                          'ntasks': None,
                          'num_workers': 6,
                          'open_arrival': False,
                          'pending': 30,
                          'qtasks': None,
                          'queued': [0.0, 7.0, 5.0, 2.0, 4.333333333333333,
                                     0.0],
                          'radius': 2,
                          'ran_any': True,
                          'rel': None,
                          't_view': [0.5, 1000000000000.0, 1.0, 1.25, 1.5,
                                     1.0],
                          'transfer_cost': None,
                          'unit': None,
                          'window': [0, 1, 2, 3, 4],
                          'worker': 2}]},
 'wedge': {'limp_log': [[100.0, 1, True], [100.0, 2, False]],
           'limping': [True, True, False, False, False, False],
           'published_t0': 7.5,
           'stale_flagged': [False, True, False, False, False, False],
           'views': [{'alive': [True, True, True, True, True, True],
                      'depth': [5, 5, 5, 5, 5, 5],
                      'idle': False,
                      'limp': [True, True, False, False, False, False],
                      'link_health': None,
                      'members': None,
                      'n_view': [7.0, 8.0, 9.0, 0.0, 11.0, 12.0],
                      'nc_view': None,
                      'now': 100.0,
                      'ntasks': None,
                      'num_workers': 6,
                      'open_arrival': False,
                      'pending': 30,
                      'qtasks': None,
                      'queued': [5.0, 0.0, 0.0, 0.0, 4.333333333333333,
                                 6.285714285714286],
                      'radius': 2,
                      'ran_any': True,
                      'rel': None,
                      't_view': [7.5, 1.0, 1.0, 2.5, 1.5, 1.75],
                      'transfer_cost': None,
                      'unit': None,
                      'window': [4, 5, 0, 1, 2],
                      'worker': 0},
                     {'alive': [True, True, True, True, True, True],
                      'depth': [5, 5, 5, 5, 5, 5],
                      'idle': False,
                      'limp': [False, True, False, False, False, False],
                      'link_health': None,
                      'members': None,
                      'n_view': [0.0, 8.0, 9.0, 10.0, 11.0, 12.0],
                      'nc_view': None,
                      'now': 100.0,
                      'ntasks': None,
                      'num_workers': 6,
                      'open_arrival': False,
                      'pending': 30,
                      'qtasks': None,
                      'queued': [0.0, 0.0, 0.0, 5.0, 4.333333333333333,
                                 6.285714285714286],
                      'radius': 2,
                      'ran_any': True,
                      'rel': None,
                      't_view': [1.25, 1.0, 1.0, 1.25, 1.5, 1.75],
                      'transfer_cost': None,
                      'unit': None,
                      'window': [1, 2, 3, 4, 5],
                      'worker': 3}]},
 'weighted': {'views': [{'alive': [True, True, True, True, True, False],
                         'depth': [6, 6, 6, 6, 6, 9],
                         'idle': False,
                         'limp': None,
                         'link_health': None,
                         'members': None,
                         'n_view': [22.676933918221373, 11.021720491569019,
                                    14.302403204272363, 0.0,
                                    29.806792959049144, 47.77460317460317],
                         'nc_view': [[2.0, 2.0, 2.0], [2.0, 2.0, 2.0],
                                     [2.0, 2.0, 2.0], [0.0, 0.0, 0.0],
                                     [2.0, 2.0, 2.0], [2.0, 2.0, 5.0]],
                         'now': 100.0,
                         'ntasks': [8.0, 9.0, 10.0, 0.0, 12.0, 16.0],
                         'num_workers': 6,
                         'open_arrival': False,
                         'pending': 36,
                         'qtasks': [6.0, 0.0, 0.0, 0.0, 5.333333333333333,
                                    9.0],
                         'queued': [20.774603174603172, 0.0, 0.0, 0.0,
                                    18.466313932980597, 40.77460317460317],
                         'radius': 2,
                         'ran_any': True,
                         'rel': [1.0, 2.7206349206349207, 6.666666666666666],
                         't_view': [0.5256709451575263, 0.6124270711785298,
                                    0.6991831971995333, 1.1,
                                    0.8817969661610269, 1000000000000.0],
                         'transfer_cost': None,
                         'unit': [3.462433862433862, 3.462433862433862,
                                  3.462433862433862, 1.0, 3.462433862433862,
                                  4.530511463844797],
                         'window': [4, 5, 0, 1, 2],
                         'worker': 0},
                        {'alive': [True, True, True, True, True, False],
                         'depth': [6, 6, 6, 6, 6, 9],
                         'idle': False,
                         'limp': None,
                         'link_health': None,
                         'members': None,
                         'n_view': [0.0, 10.864597667849642,
                                    14.102740378005056, 28.467689682659188,
                                    29.571644164597874, 47.62460317460317],
                         'nc_view': [[0.0, 0.0, 0.0], [2.0, 2.0, 2.0],
                                     [2.0, 2.0, 2.0], [2.0, 2.0, 2.0],
                                     [2.0, 2.0, 2.0], [2.0, 2.0, 5.0]],
                         'now': 100.0,
                         'ntasks': [0.0, 9.0, 10.0, 11.0, 12.0, 16.0],
                         'num_workers': 6,
                         'open_arrival': False,
                         'pending': 36,
                         'qtasks': [0.0, 0.0, 0.0, 6.0, 5.333333333333333,
                                    9.0],
                         'queued': [0.0, 0.0, 0.0, 20.624603174603173,
                                    18.332980599647264, 40.62460317460317],
                         'radius': 2,
                         'ran_any': True,
                         'rel': [1.0, 2.6456349206349206, 6.666666666666666],
                         't_view': [1.25, 0.6212839358032098,
                                    0.7090820458977051, 0.7968801559922005,
                                    0.8897855107244638, 1000000000000.0],
                         'transfer_cost': None,
                         'unit': [1.0, 3.437433862433862, 3.437433862433862,
                                  3.437433862433862, 3.437433862433862,
                                  4.51384479717813],
                         'window': [1, 2, 3, 4, 5],
                         'worker': 3},
                        {'alive': [True, True, True, True, True, False],
                         'depth': [6, 6, 6, 6, 6, 9],
                         'idle': False,
                         'limp': None,
                         'link_health': None,
                         'members': None,
                         'n_view': [7.547760463978758, 0.0, 14.192164983129594,
                                    22.97282330908322, 30.84722210282836,
                                    47.691269841269836],
                         'nc_view': [[2.0, 2.0, 2.0], [0.0, 0.0, 0.0],
                                     [2.0, 2.0, 2.0], [2.0, 2.0, 2.0],
                                     [2.0, 2.0, 2.0], [2.0, 2.0, 5.0]],
                         'now': 100.0,
                         'ntasks': [8.0, 0.0, 10.0, 11.0, 12.0, 16.0],
                         'num_workers': 6,
                         'open_arrival': False,
                         'pending': 36,
                         'qtasks': [0.0, 0.0, 0.0, 3.0, 6.0, 9.0],
                         'queued': [0.0, 0.0, 0.0, 10.34563492063492,
                                    20.69126984126984, 40.691269841269836],
                         'radius': 2,
                         'ran_any': True,
                         'rel': [1.0, 2.678968253968254, 6.666666666666666],
                         't_view': [0.5299585246630129, 1.2,
                                    0.7046141312398162, 0.791941934528218,
                                    0.8861798252110799, 1000000000000.0],
                         'transfer_cost': None,
                         'unit': [3.448544973544973, 1.0, 3.448544973544973,
                                  3.448544973544973, 3.448544973544973,
                                  4.5212522045855374],
                         'window': [2, 3, 4, 5, 0],
                         'worker': 4}]}}
# fmt: on
