"""The A2WS information model (§2.1-2.2.1), written once for both planes.

A worker PUBLISHES one row of the information board at its task
boundaries (``publish``), OBSERVES every task it finishes (``observe``),
and a thief CONCLUDES from the rows it has read what it may believe about
its window (``make_view``), returning the :class:`PolicyView` every policy
consumes.

The two planes differ only in how rows travel, and each keeps that part:

* the threaded ``WorkerPool`` reads a ``RingInfo``/``CellBoard`` snapshot
  (``view_window_all``) and keeps its locks and its steal leg;
* the simulator reads the relay-delayed report history of each peer at
  ``read_at`` (hop latency, relay pacing, the partition freeze) and keeps
  its event heap and its steal leg.

Each plane hands the model a :class:`Plane` of one-sided ground-truth
reads and the limp state the rules write, plus the :class:`Rows` it read.
Where the planes' values differ on purpose (the base of closed-mode
``done_est``, the simulator's ``near_idle``/``inflight``), the plane passes
its value in; nothing here asks which plane is calling.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Iterable, MutableSequence, Sequence

import numpy as np

from .limp import LimpConfig, LimpState, effective_heartbeat, normalize_duration
from .netfault import LinkHealth, NetFaultSchedule
from .policy import PolicyView
from .steal import OverlayBuffers, weighted_overlay
from .topology import Topology

__all__ = ["Plane", "Rows", "publish", "observe", "make_view", "place"]

#: believed seconds per task of a tombstone or a migration hole: speed ~0,
#: so the fair share (Eq. 5) never assigns it work
DEAD_T = 1e12


@dataclass(frozen=True)
class Plane:
    """What a plane lends the model: one-sided reads by GLOBAL worker id
    (one RMA atomic each in the paper's protocol) and the limp verdicts the
    rules write back.  ``flagged``/``stale``/``heartbeat`` are the plane's
    own sequences, held by reference."""

    depth: Callable[[int], int]  # queued tasks
    dead: Callable[[int], bool]  # tombstone
    executed: Callable[[int], int]  # tasks run (a tombstone's is readable)
    classes: Callable[[int], np.ndarray]  # queued tasks per cost class
    class_t: Callable[[int], np.ndarray]  # per-class EWMA runtimes
    open_arrival: bool
    weighted: bool  # per-class payload and the work-weighted overlay
    alpha: float  # per-class EWMA smoothing
    heartbeat: Sequence[float]  # last boundary each worker's own loop reached
    flagged: MutableSequence[bool]  # limp flag, by worker
    stale: MutableSequence[bool]  # the wedge path holds the flag
    limp: LimpConfig | None = None
    state: Callable[[int], LimpState | None] = lambda g: None
    #: ``log(worker, flag, t)`` records a flag transition; ``t`` None asks
    #: the plane to stamp it with its own clock
    log: Callable[[int, bool, float | None], None] = lambda g, f, t: None
    topology: Topology | None = None  # None: the policy plans unpriced
    netfaults: NetFaultSchedule | None = None
    health: LinkHealth | None = None

    def flag(self, g: int, flag: bool, t: float | None) -> None:
        if bool(self.flagged[g]) != flag:
            self.flagged[g] = flag
            self.log(g, flag, t)


@dataclass
class Rows:
    """The board as one observer read it, one row per slot (LOCAL slots of
    its cell under a hierarchy, ``members`` mapping them to global ids with
    ``-1`` for a migration hole).  The own row holds the observer's own
    report.  ``raw_t`` marks unreported peers with NaN where ``t`` holds
    the plane's fill (defaults to ``t``).  ``order`` is the plane's visiting
    order of the window (defaults to ``window``).  The model writes the
    arrays in place."""

    n: np.ndarray
    t: np.ndarray
    limp: np.ndarray | None
    nc: np.ndarray | None
    tc: np.ndarray | None
    members: np.ndarray | None
    iview: int
    radius: int
    window: list[int]
    raw_t: np.ndarray | None = None
    order: list[int] | None = None


def publish(
    plane: Plane, g: int, runtime_sum: float, elapsed: float
) -> tuple[float, float, bool, np.ndarray | None, np.ndarray | None]:
    """Worker ``g``'s row: ``(n, t, limping, nc, tc)``.

    Closed: n = executed + queued (§2.2); open: n = queue depth, since
    totals are meaningless while tasks keep arriving.  t = mean runtime, or
    the elapsed time before the first finish (§2.2.1).  A flagged limper
    publishes its collapsed fast EWMA instead when that is slower, so Eq. 5
    marks its queue surplus and thieves strip it by the ordinary path.
    Weighted: the own queue composition and per-class EWMAs ride along."""
    executed, depth = plane.executed(g), plane.depth(g)
    n = float(depth) if plane.open_arrival else float(executed + depth)
    t = runtime_sum / executed if executed > 0 else max(elapsed, 1e-9)
    limping = bool(plane.flagged[g])
    if limping:
        recent = plane.state(g).recent
        if recent == recent:
            t = max(t, recent)
    if not plane.weighted:
        return n, t, limping, None, None
    return n, t, limping, plane.classes(g), plane.class_t(g).copy()


def observe(
    plane: Plane,
    g: int,
    dt: float,
    cls: int,
    class_t: np.ndarray | None,
    state: LimpState | None,
    peers: Callable[[], Iterable[tuple[int, float]]],
    now: float | None = None,
) -> None:
    """Worker ``g`` finished a task of class ``cls`` in ``dt``: fold it into
    the per-class EWMA row ``class_t`` (None: no classes), then into the
    owner-side limp detector ``state`` in average-class terms.  Until the
    own baseline is trusted the reference is the median published t of the
    live ``peers`` (``(id, t)`` pairs the plane can see)."""
    if class_t is not None:
        prev = class_t[cls]
        if prev != prev:  # first observation of this class
            class_t[cls] = dt
        else:
            class_t[cls] = plane.alpha * dt + (1.0 - plane.alpha) * prev
    if state is None:
        return
    state.observe(normalize_duration(dt, cls, class_t))
    ref = float("nan")
    if state.samples < state.cfg.min_samples:
        vals = [t for j, t in peers() if not plane.dead(j) and t == t]
        if vals:
            ref = float(np.median(vals))
    plane.flag(g, state.evaluate(ref), now)


def place(
    plane: Plane, cands: Iterable[int], ok: Callable[[int], bool]
) -> int:
    """Where fresh work lands: the first candidate ``ok`` accepts that is
    not flagged limping (a limper's collapsed speed would bake straight into
    the task's latency).  A flagged one takes it only as a probation canary
    (``LimpState.should_probe`` — the only completions that can clear its
    flag) or when every accepted candidate limps.  -1 when none is ok."""
    fallback = -1
    for g in cands:
        if ok(g):
            if not plane.flagged[g]:
                return g
            st = plane.state(g)
            if st is not None and st.should_probe():
                return g
            if fallback < 0:
                fallback = g
    return fallback


def _conclude(
    plane: Plane,
    i: int,
    rows: Rows,
    now: float,
    t0: float | None,
    born: float,
    done_base: float | None,
    scratch: dict | None,
) -> dict:
    """What thief ``i`` may believe about its window, as PolicyView fields.

    Estimates use only the rows the thief read, its own state and one-sided
    reads of tombstones — never a live peer's state.  Over- and
    under-estimates are absorbed by the Fig. 3b adjust-and-correct protocol
    of the steal leg."""
    n, t, limp, iview = rows.n, rows.t, rows.limp, rows.iview
    raw = t if rows.raw_t is None else rows.raw_t
    gs = range(len(n)) if rows.members is None else rows.members.tolist()
    queued = np.zeros(len(n))
    if limp is not None:
        limp[iview] = plane.flagged[i]  # own flag: ground truth, no lag
    cfg, hbs, open_mode = plane.limp, plane.heartbeat, plane.open_arrival
    wedge = cfg is not None and math.isfinite(cfg.stale_after)
    nf = plane.netfaults if t0 is not None else None
    elapsed = max(now - born, 1e-9)
    # The threaded plane bases done_est on the thief's elapsed time; the
    # simulator, whose peers boot at 0, on the time since the run began.
    base = elapsed if done_base is None else done_base
    order = rows.window if rows.order is None else rows.order
    for jl in order:
        g = gs[jl]
        if g < 0:
            # Migration hole: no member behind this slot any more.
            queued[jl], t[jl], n[jl] = 0.0, DEAD_T, 0.0
            continue
        if jl == iview:
            queued[jl] = plane.depth(i)
            if open_mode:
                n[jl] = queued[jl]
            continue
        if plane.dead(g):
            # Tombstone: its cells are frozen garbage, but its deque is
            # still readable — count the orphans directly.
            queued[jl] = plane.depth(g)
            t[jl] = DEAD_T
            n[jl] = queued[jl] if open_mode else plane.executed(g) + queued[jl]
            continue
        if raw[jl] != raw[jl]:
            # No report from j yet: preemptive wall-time estimate — j looks
            # like it has finished 0 tasks in the thief's elapsed time.
            t[jl] = elapsed
        if wedge:
            # Wedge detector (LimpConfig.stale_after): j's heartbeat is the
            # last boundary its own loop reached; an idle worker keeps
            # beating, so only a worker stuck inside a task goes silent.
            # The owner-side EWMA can never flag it (it sees completed tasks
            # only), so the peer raises the flag and prices j's speed at one
            # task per silence: closed-mode done_est -> 0, the queue surplus.
            hb = hbs[g]
            if hb == hb and now - hb > cfg.stale_after:
                if not plane.stale[g]:
                    plane.stale[g] = True
                    plane.flag(g, True, now)
                t[jl] = max(t[jl], now - hb)
                limp[jl] = True
            elif plane.stale[g]:
                # Heartbeat is back: the owner-side hysteresis owns the flag.
                plane.stale[g] = False
                st = plane.state(g)
                plane.flag(g, bool(st.limping) if st is not None else False, now)
        if nf is not None:
            # Partition staleness (DESIGN.md §Fault fabric): across a cut,
            # the heartbeat the observer can see stops at the cut instant;
            # after nf.stale_after of that silence j is re-priced like a
            # wedge, in THIS row only — j's own side still sees it healthy.
            cut = nf.unreachable_since(g, i, now - t0)
            if cut < math.inf:
                hb_eff = effective_heartbeat(hbs[g], t0 + cut)
                if hb_eff == hb_eff and now - hb_eff > nf.stale_after:
                    t[jl] = max(t[jl], now - hb_eff)
                    if limp is not None:
                        limp[jl] = True
        if open_mode:
            # n_j IS the reported depth: it drains and refills, so decaying
            # it by elapsed time would under-count busy victims.
            queued[jl] = max(n[jl], 0.0)
        else:
            done_est = min(base / max(t[jl], 1e-9), n[jl])
            queued[jl] = max(n[jl] - done_est, 0.0)
    if not plane.weighted:
        return {"n_view": n, "t_view": t, "queued": queued, "limp": limp}
    # ---- work-weighted overlay (DESIGN.md §Work-weighted stealing) ----
    nc, tc = rows.nc, rows.tc
    nc[iview] = plane.classes(i)  # own row: ground truth
    tc[iview] = plane.class_t(i)
    # Holes and tombstones keep their ~0-speed price; a tombstone's queue
    # composition is read directly, like its depth.
    frozen = np.fromiter((g < 0 or plane.dead(g) for g in gs), bool, len(n))
    for jl in order:
        if jl != iview and frozen[jl] and gs[jl] >= 0:
            nc[jl] = plane.classes(gs[jl])
    m, c = tc.shape
    buf = None
    if scratch is not None:
        # Reused per worker: a boundary consumes its view before the next.
        buf = scratch.get(m)
        if buf is None:
            buf = scratch[m] = OverlayBuffers(m, c)
    n_w, t_w, queued_w, unit, qtasks, rel = weighted_overlay(
        n, t, queued, nc, tc, frozen=frozen, buf=buf
    )
    # ntasks keeps the COUNT estimate: the Fig. 3b reconciliation writes
    # the board's count-denominated n from it.
    return {
        "n_view": n_w, "t_view": t_w, "queued": queued_w, "unit": unit,
        "qtasks": qtasks, "rel": rel, "ntasks": n, "limp": limp,
        "nc_view": nc,
    }


def _scoped(members: np.ndarray | None, fn: Callable, hole):
    """``fn`` over global ids, called with a view slot: identity on a flat
    view, through the member map on a cell-scoped one (holes read ``hole``)."""
    if members is None:
        return fn

    def local(jl, *a):
        g = int(members[jl]) if 0 <= jl < len(members) else -1
        return hole if g < 0 else fn(g, *a)

    return local


def make_view(
    plane: Plane,
    i: int,
    rows: Rows | None,
    *,
    clock: Callable[[], float],
    t0: float | None,
    born: float,
    rng: np.random.Generator,
    ran_any: bool,
    pending: Callable[[], int],
    num_workers: int,
    radius: int,
    done_base: float | None = None,
    scratch: dict | None = None,
    **plane_fields,
) -> PolicyView:
    """Worker ``i``'s :class:`PolicyView` at a task boundary.

    ``rows`` is None for policies without the info ring; the view then
    spans ``num_workers`` with ``radius``.  ``clock`` is the plane's clock
    (read once for the ring, once for ``view.now``, and by the link-health
    hook at each call) and ``t0`` its origin (None: not started, so no
    fault fabric yet).  ``born`` is when ``i`` started, the base of its
    preemptive estimates.  ``scratch`` holds the overlay buffers of one
    deciding worker.  ``plane_fields`` pass through (``near_idle``,
    ``inflight``)."""
    ring: dict = {}
    if rows is None:
        members, iview, window = None, i, list(range(num_workers))
    else:
        ring = _conclude(plane, i, rows, clock(), t0, born, done_base, scratch)
        members, iview, window = rows.members, rows.iview, rows.window
        num_workers, radius = len(rows.n), rows.radius
    tcost = lh = None
    if plane.topology is not None:
        # transfer_cost(j, k) = seconds to move k tasks FROM j TO i.
        cost = plane.topology.cost
        tcost = _scoped(members, lambda g, k: cost(int(g), i, int(k)), math.inf)
    nf, health = plane.netfaults, plane.health
    if nf is not None and t0 is not None:
        # link_health(j) in [0, 1]: 0 across an active partition or a
        # backed-off link, else the link's success EWMA (floor-clamped).
        def link(g):
            tnow = clock() - t0
            if not nf.reachable(int(g), i, tnow):
                return 0.0
            return health.factor(i, int(g), tnow)

        lh = _scoped(members, link, 0.0)
    return PolicyView(
        worker=iview,
        now=clock(),
        idle=plane.depth(i) == 0,
        ran_any=ran_any,
        open_arrival=plane.open_arrival,
        radius=radius,
        num_workers=num_workers,
        rng=rng,
        window=window,
        depth=_scoped(members, plane.depth, 0),
        alive=_scoped(members, lambda g: not plane.dead(g), False),
        pending=pending,
        members=members,
        transfer_cost=tcost,
        link_health=lh,
        **ring,
        **plane_fields,
    )
