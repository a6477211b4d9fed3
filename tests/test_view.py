"""Unit tests of the A2WS information model (core/view.py) on hand-built
planes: what a worker publishes, what it observes, what a thief concludes,
and where fresh work lands."""

from __future__ import annotations

import math

import numpy as np
import pytest

from repro.core.limp import LimpConfig, LimpState
from repro.core.netfault import LinkHealth, NetFaultSchedule, PartitionEvent
from repro.core.topology import Topology
from repro.core.view import DEAD_T, Plane, Rows, make_view, observe, place, publish


def _plane(p=4, *, depth=None, executed=None, dead=(), open_arrival=False,
           weighted=False, limp=None, classes=None, class_t=None, **kw):
    depth = list(depth if depth is not None else [3] * p)
    executed = list(executed if executed is not None else [5] * p)
    classes = classes if classes is not None else np.ones((p, 2))
    class_t = class_t if class_t is not None else np.full((p, 2), np.nan)
    states = [LimpState(limp) if limp is not None else None for _ in range(p)]
    log: list = []
    plane = Plane(
        depth=lambda g: depth[g], dead=lambda g: g in dead,
        executed=lambda g: executed[g], classes=lambda g: classes[g],
        class_t=lambda g: class_t[g], open_arrival=open_arrival,
        weighted=weighted, alpha=0.25,
        heartbeat=kw.pop("heartbeat", [float("nan")] * p),
        flagged=kw.pop("flagged", [False] * p), stale=[False] * p,
        limp=limp, state=lambda g: states[g],
        log=lambda g, f, t: log.append((t, g, f)), **kw,
    )
    return plane, states, log


def _rows(n, t, *, iview=0, members=None, limp=False, raw_t=None, order=None,
          nc=None, tc=None):
    m = len(n)
    return Rows(
        np.asarray(n, float), np.asarray(t, float),
        np.zeros(m, bool) if limp else None, nc, tc,
        None if members is None else np.asarray(members, np.int64),
        iview, m // 2, list(range(m)),
        raw_t=None if raw_t is None else np.asarray(raw_t, float),
        order=order,
    )


def _view(plane, rows, now=10.0, born=0.0, **kw):
    kw.setdefault("t0", 0.0)
    return make_view(
        plane, 0, rows, clock=lambda: now, born=born,
        rng=np.random.default_rng(0), ran_any=True, pending=lambda: 0,
        num_workers=len(rows.n) if rows is not None else 4, radius=1, **kw,
    )


# ------------------------------------------------------------------ publish
@pytest.mark.parametrize("open_arrival,executed,elapsed,want", [
    (False, 4, 9.0, (4 + 3, 2.0)),         # closed: executed + queued, mean t
    (True, 4, 9.0, (3, 2.0)),              # open: the queue depth
    (False, 0, 9.0, (3, 9.0)),             # before a finish: elapsed time
    (False, 0, 0.0, (3, 1e-9)),            # ... never 0
])
def test_publish_row(open_arrival, executed, elapsed, want):
    plane, _, _ = _plane(executed=[executed] * 4, open_arrival=open_arrival)
    n, t, limping, nc, tc = publish(plane, 1, 8.0, elapsed)
    assert (n, t) == want and limping is False and nc is None and tc is None


@pytest.mark.parametrize("recent,want", [(5.0, 5.0), (1.0, 1.6), (math.nan, 1.6)])
def test_publish_limper_reprices_to_its_recent_ewma(recent, want):
    plane, states, _ = _plane(limp=LimpConfig(), flagged=[False, True, False, False])
    states[1].recent = recent
    _, t, limping, _, _ = publish(plane, 1, 8.0, 1.0)  # mean t: 8 s / 5
    assert limping is True and t == want


def test_publish_weighted_payload_is_a_copy():
    class_t = np.array([[1.0, 2.0]] * 4)
    plane, _, _ = _plane(weighted=True, class_t=class_t)
    *_, nc, tc = publish(plane, 2, 8.0, 1.0)
    assert np.array_equal(nc, [1.0, 1.0]) and np.array_equal(tc, [1.0, 2.0])
    tc[0] = 99.0
    assert class_t[2, 0] == 1.0


# ------------------------------------------------------------------ observe
def test_observe_class_ewma():
    plane, _, _ = _plane()
    row = np.array([np.nan, np.nan])
    observe(plane, 0, 2.0, 1, row, None, lambda: ())
    assert np.isnan(row[0]) and row[1] == 2.0  # first sample taken whole
    observe(plane, 0, 6.0, 1, row, None, lambda: ())
    assert row[1] == 0.25 * 6.0 + 0.75 * 2.0


def test_observe_flags_a_boot_limper_against_live_peer_median():
    cfg = LimpConfig(min_samples=3)
    plane, states, log = _plane(limp=cfg, dead={3})
    peers = [(1, 1.0), (2, 1.0), (3, 100.0), (1, math.nan)]  # 3 is dead
    observe(plane, 0, 8.0, 0, None, states[0], lambda: peers, now=7.0)
    assert plane.flagged[0] and log == [(7.0, 0, True)]
    observe(plane, 0, 8.0, 0, None, states[0], lambda: peers, now=8.0)
    assert log == [(7.0, 0, True)]  # no transition, nothing logged


def test_observe_without_peers_keeps_the_verdict():
    plane, states, log = _plane(limp=LimpConfig())
    observe(plane, 0, 8.0, 0, None, states[0], lambda: ())
    assert not plane.flagged[0] and log == []


# --------------------------------------------------------------- conclude
def test_closed_view_estimates_queues_from_elapsed_time():
    plane, _, _ = _plane(depth=[2, 9, 9, 9])
    v = _view(plane, _rows([7, 10, 10, 3], [1.0, 2.0, 4.0, 1.0]), now=10.0)
    # own row: ground-truth depth; peers: n - min(elapsed / t, n)
    assert v.queued.tolist() == [2.0, 5.0, 7.5, 0.0]
    assert v.n_view.tolist() == [7.0, 10.0, 10.0, 3.0]
    assert v.worker == 0 and v.window == [0, 1, 2, 3] and v.idle is False


def test_done_base_is_the_planes_value():
    plane, _, _ = _plane()
    v = _view(plane, _rows([7, 10, 10, 10], [1.0] * 4), now=10.0, born=6.0,
              done_base=10.0)
    assert v.queued[1] == 0.0  # 10 s / 1 s done of 10
    v = _view(plane, _rows([7, 10, 10, 10], [1.0] * 4), now=10.0, born=6.0)
    assert v.queued[1] == 6.0  # the thief's 4 s elapsed: 4 done


def test_unreported_peer_priced_at_the_thiefs_elapsed_time():
    plane, _, _ = _plane()
    rows = _rows([7, 10, 10, 10], [1.0, 2.5, 2.5, 2.5],
                 raw_t=[1.0, math.nan, 2.5, math.nan])
    v = _view(plane, rows, now=10.0, born=5.0)
    assert v.t_view.tolist() == [1.0, 5.0, 2.5, 5.0]
    assert v.queued[1] == 9.0


@pytest.mark.parametrize("open_arrival,want_n", [(False, 5 + 6), (True, 6)])
def test_tombstone_counts_its_orphans_at_zero_speed(open_arrival, want_n):
    plane, _, _ = _plane(depth=[1, 6, 1, 1], dead={1}, open_arrival=open_arrival)
    v = _view(plane, _rows([7, 99, 7, 7], [1.0] * 4))
    assert (v.n_view[1], v.t_view[1], v.queued[1]) == (want_n, DEAD_T, 6.0)
    assert v.alive(1) is False and v.alive(2) is True


def test_open_view_takes_reported_depth():
    plane, _, _ = _plane(depth=[4, 0, 0, 0], open_arrival=True)
    v = _view(plane, _rows([9, 6, -1, 3], [1.0] * 4))
    assert v.n_view[0] == 4 and v.queued.tolist() == [4.0, 6.0, 0.0, 3.0]


def test_cell_view_translates_slots_and_prices_holes():
    plane, _, _ = _plane(p=8, depth=list(range(8)),
                         topology=Topology.uniform(1.0, 0.5))
    rows = _rows([3, 9, 9], [1.0, 1.0, 1.0], members=[5, -1, 7])
    v = _view(plane, rows)
    assert (v.n_view[1], v.t_view[1], v.queued[1]) == (0.0, DEAD_T, 0.0)
    assert [v.depth(j) for j in range(3)] == [5, 0, 7]
    assert [v.alive(j) for j in range(3)] == [True, False, True]
    assert v.transfer_cost(2, 2) == 2.0 and v.transfer_cost(1, 2) == math.inf
    assert v.transfer_cost(9, 2) == math.inf  # outside the cell


def test_wedge_raises_and_hands_back_the_flag_in_visiting_order():
    cfg = LimpConfig(stale_after=1.0)
    hb = [10.0, 5.0, 10.0, 4.0]
    plane, states, log = _plane(limp=cfg, heartbeat=hb)
    rows = _rows([7, 10, 10, 10], [1.0] * 4, limp=True, order=[3, 2, 1, 0])
    v = _view(plane, rows, now=10.0, born=5.0)
    assert log == [(10.0, 3, True), (10.0, 1, True)]  # the plane's order
    assert plane.stale[1] and plane.flagged[1]
    assert v.t_view[1] == 5.0 and v.limp.tolist() == [False, True, False, True]
    assert v.queued[1] == 9.0  # 5 s elapsed at 5 s a task: one done
    hb[1] = 9.9  # it beats again: the owner-side verdict takes over
    _view(plane, _rows([7, 10, 10, 10], [1.0] * 4, limp=True), now=10.0)
    assert not plane.stale[1] and not plane.flagged[1]
    assert log[-1] == (10.0, 1, False)


def test_partition_staleness_is_observer_local():
    nf = NetFaultSchedule(
        partitions=(PartitionEvent(side=(2,), start=3.0),), stale_after=1.0
    )
    plane, _, log = _plane(heartbeat=[10.0] * 4, netfaults=nf,
                           health=LinkHealth(nf), limp=LimpConfig())
    v = _view(plane, _rows([7, 10, 10, 10], [1.0] * 4, limp=True), now=10.0,
              t0=2.0)
    # cut at t0 + 3 = 5: the observer saw j's heartbeat stop 5 s ago
    assert v.t_view[2] == 5.0 and v.limp[2] and not plane.flagged[2]
    assert log == []
    assert v.link_health(2) == 0.0 and v.link_health(1) == 1.0
    v = _view(plane, _rows([7, 10, 10, 10], [1.0] * 4), now=10.0, t0=None)
    assert v.t_view[2] == 1.0 and v.link_health is None  # not started


def test_weighted_overlay_freezes_tombstones_and_keeps_counts():
    class_t = np.array([[1.0, 4.0]] * 4)
    classes = np.array([[2.0, 0.0], [0.0, 3.0], [1.0, 1.0], [3.0, 0.0]])
    plane, _, _ = _plane(weighted=True, dead={3}, class_t=class_t,
                         classes=classes, depth=[2, 3, 2, 3])
    rows = _rows([7, 10, 10, 10], [1.0, 2.0, 2.0, 2.0],
                 nc=np.zeros((4, 2)), tc=np.full((4, 2), np.nan))
    rows.tc[1] = [1.0, 4.0]
    v = _view(plane, rows)
    assert v.rel.tolist() == [1.0, 4.0]
    assert v.nc_view[0].tolist() == [2.0, 0.0]  # own: ground truth
    assert v.nc_view[3].tolist() == [3.0, 0.0]  # tombstone: read directly
    assert v.t_view[3] == DEAD_T  # frozen at its ~0-speed price
    assert v.ntasks.tolist() == [7.0, 10.0, 10.0, 8.0]  # counts, not work


def test_view_without_a_ring():
    plane, _, _ = _plane(depth=[0, 1, 2, 3])
    v = _view(plane, None, near_idle=True, inflight=lambda: 2)
    assert v.n_view is None and v.window == [0, 1, 2, 3] and v.radius == 1
    assert v.idle and v.near_idle and v.inflight() == 2
    assert v.depth(3) == 3


# -------------------------------------------------------------------- place
@pytest.mark.parametrize("flagged,ok,probe,want", [
    ([False] * 4, {0, 1, 2, 3}, False, 1),
    ([False, True, False, False], {0, 1, 2, 3}, False, 2),
    ([False, True, False, False], {0, 1, 2, 3}, True, 1),  # canary
    ([True] * 4, {0, 1, 2, 3}, False, 1),  # all limp: the first ok
    ([False] * 4, {0}, False, -1),
])
def test_place_routes_off_limpers(flagged, ok, probe, want):
    plane, states, _ = _plane(limp=LimpConfig(probation_every=1 if probe else 99),
                              flagged=list(flagged))
    assert place(plane, [1, 2, 3], lambda g: g in ok) == want
