"""Device data-plane A2WS: the paper's scheduler as a jitted SPMD program.

XLA SPMD has no remote atomics, so the *asynchronous* theft of §2.3 cannot be
expressed verbatim inside one compiled step.  What CAN be expressed — and what
this module provides — is the paper's information/decision structure as a
**round-based, neighbour-only** rebalance:

* information ring (§2.1)  -> two ``lax.ppermute``s per round over the worker
  axis (bidirectional ring).  Each worker carries a (2R+1)-cell window of
  ``(n_j, t_j, q_j)``; one round shifts knowledge one hop outward, R rounds
  refresh the full radius.  No all-gather, no global barrier semantics beyond
  the compiled step — communication stays O(R) per worker, the paper's point.
* smart stealing (§2.2)    -> Eq. 5 steal rate, γ-rounding (Eq. 7) and victim
  selection as array ops; probabilistic victim choice via per-worker PRNG.
* asynchronous theft       -> a single request/grant exchange built from two
  ``lax.all_to_all``s.  The victim grants ``min(request, available)`` — the
  SPMD analogue of the Fig. 3b get-accumulate + occasional correction: the
  thief's optimistic claim is adjusted by the authoritative victim-side state,
  in one round trip, with no locks.

Used three ways:
  1. ``plan_rebalance`` — the training control plane (``runtime.het_dp``)
     calls it between steps to redistribute microbatch counts.
  2. ``virtual_run`` — a fully jitted virtual-time cluster: property tests and
     the technique's own roofline/dry-run cell run this.
  3. equivalence tests against ``repro.core.steal`` (same formulas, host vs
     device).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "SchedState",
    "init_state",
    "a2ws_round",
    "make_round_fn",
    "virtual_run",
    "steal_rate_window",
    "gamma_round",
]

_EPS = 1e-9


class SchedState(NamedTuple):
    """Per-worker scheduler state; leading axis = worker (sharded)."""

    queue: jax.Array   # i32[P, cap]   task ids, valid in [head, tail)
    head: jax.Array    # i32[P]
    tail: jax.Array    # i32[P]
    executed: jax.Array  # i32[P]
    t_avg: jax.Array   # f32[P]      mean task runtime (virtual seconds)
    clock: jax.Array   # f32[P]      per-worker virtual time
    win_n: jax.Array   # f32[P, W]   window: total tasks n_j
    win_t: jax.Array   # f32[P, W]   window: mean runtime t_j
    win_q: jax.Array   # f32[P, W]   window: queued tasks q_j
    key: jax.Array     # u32[P, 2]
    credit: jax.Array  # f32[P]      accumulated virtual time not yet spent


def init_state(
    num_workers: int,
    tasks_per_worker: jax.Array,
    speeds: jax.Array,
    radius: int,
    capacity: int,
    seed: int = 0,
) -> SchedState:
    """Static block partition (§2.2.1) across ``num_workers`` deques."""
    p = num_workers
    w = 2 * radius + 1
    counts = jnp.asarray(tasks_per_worker, jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    # queue[i, s] = global task id offsets[i] + s  (valid while s < counts[i])
    slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    queue = jnp.where(slot < counts[:, None], offsets[:, None] + slot, -1)
    t0 = 1.0 / jnp.asarray(speeds, jnp.float32)  # virtual seconds per task
    win_n = jnp.zeros((p, w), jnp.float32)
    win_t = jnp.full((p, w), jnp.nan, jnp.float32)
    win_q = jnp.zeros((p, w), jnp.float32)
    win_n = win_n.at[:, radius].set(counts.astype(jnp.float32))
    win_q = win_q.at[:, radius].set(counts.astype(jnp.float32))
    keys = jax.vmap(lambda s: jax.random.key_data(jax.random.key(s)))(
        jnp.arange(seed, seed + p)
    ).astype(jnp.uint32)
    return SchedState(
        queue=queue,
        head=jnp.zeros((p,), jnp.int32),
        tail=counts.astype(jnp.int32),
        executed=jnp.zeros((p,), jnp.int32),
        t_avg=t0.astype(jnp.float32),
        clock=jnp.zeros((p,), jnp.float32),
        win_n=win_n,
        win_t=win_t,
        win_q=win_q,
        key=keys,
        credit=jnp.zeros((p,), jnp.float32),
    )


# ------------------------------------------------------------------ formulas
def steal_rate_window(win_n: jax.Array, win_t: jax.Array, radius: int) -> jax.Array:
    """Eq. 5 on a (2R+1)-cell window; index R = self.  Shape [...]->scalar."""
    t = jnp.where(jnp.isnan(win_t), jnp.inf, jnp.maximum(win_t, _EPS))
    inv = jnp.where(jnp.isfinite(t), 1.0 / t, 0.0)
    known = jnp.isfinite(t)
    n = jnp.where(known, win_n, 0.0)
    big_n = n.sum(-1)
    big_t = inv.sum(-1)
    t_self = jnp.maximum(win_t[..., radius], _EPS)
    return big_n / (t_self * jnp.maximum(big_t, _EPS)) - win_n[..., radius]


def gamma_round(s: jax.Array, n_i, t_i, n_j, t_j) -> jax.Array:
    """Eqs. 6-8: round fractional steal rate to the γ-minimising integer."""
    lo = jnp.floor(s)
    hi = jnp.ceil(s)

    def u(amount, n, t):  # Eq. 6 (dimensionally-consistent product form)
        return jnp.maximum(n + amount, 0.0) * t

    g_lo = jnp.maximum(u(-lo, n_j, t_j), u(lo, n_i, t_i))
    g_hi = jnp.maximum(u(-hi, n_j, t_j), u(hi, n_i, t_i))
    return jnp.where(g_lo < g_hi, lo, hi).astype(jnp.int32)


def _pair_rate(n_i, t_i, n_j, t_j):
    """Eq. 10."""
    return (n_i + n_j) * t_j / jnp.maximum(t_i + t_j, _EPS) - n_i


# ------------------------------------------------------------------- round
def a2ws_round(
    state: SchedState,
    *,
    axis: str,
    radius: int,
    max_steal: int,
    num_workers: int,
    execute: bool = True,
    max_exec: int = 64,
    packed: bool = True,
) -> SchedState:
    """One scheduler round, to be called inside shard_map over ``axis``.

    Per-shard shapes carry a leading local dim of 1 (we index [0]).
    Sequence: (a) virtual-execute tasks for one virtual-time quantum;
    (b) refresh own window cell; (c) two-ppermute ring exchange;
    (d) steal-rate + victim selection; (e) request/grant all_to_all theft.
    """
    p = num_workers
    w = 2 * radius + 1
    queue = state.queue[0]
    head, tail = state.head[0], state.tail[0]
    executed = state.executed[0]
    t_avg, clock = state.t_avg[0], state.clock[0]
    win_n, win_t, win_q = state.win_n[0], state.win_t[0], state.win_q[0]
    key = state.key[0]
    credit = state.credit[0]

    # ------------------------------------- (a) execute one virtual quantum
    # One round = the slowest worker's task time (pmax).  Each worker spends
    # its accumulated virtual-time credit on as many tasks as its own speed
    # affords (so consumption rate is proportional to 1/t_avg), capped by the
    # static ``max_exec`` unroll bound.  Idle workers do not hoard credit.
    if execute:
        dt = lax.pmax(t_avg, axis)
        credit = credit + dt
        avail_q = jnp.maximum(tail - head, 0)
        k = jnp.floor(credit / jnp.maximum(t_avg, _EPS)).astype(jnp.int32)
        k = jnp.minimum(jnp.minimum(k, avail_q), max_exec)
        head = head + k
        executed = executed + k
        clock = clock + k.astype(jnp.float32) * t_avg
        credit = credit - k.astype(jnp.float32) * t_avg
        credit = jnp.minimum(credit, dt)

    qlen = (tail - head).astype(jnp.float32)
    n_self = (executed).astype(jnp.float32) + qlen
    # Preemptive estimate (§2.2.1): before the first finished task, t is the
    # elapsed virtual wall time (clock may be 0 at boot -> use t_avg prior).
    t_self = jnp.where(executed > 0, t_avg, jnp.maximum(clock, t_avg))

    # ------------------------------------------- (b) refresh own window cell
    win_n = win_n.at[radius].set(n_self)
    win_t = win_t.at[radius].set(t_self)
    win_q = win_q.at[radius].set(qlen)

    # ------------------------------------------------ (c) ring info exchange
    # From RIGHT neighbour: its cells [R, 2R-1] -> my cells [R+1, 2R].
    # From LEFT  neighbour: its cells [1, R]    -> my cells [0, R-1].
    right_to_left = [((i + 1) % p, i) for i in range(p)]
    left_to_right = [((i - 1) % p, i) for i in range(p)]

    def shift(buf_slice, perm):
        return lax.ppermute(buf_slice, axis, perm)

    if radius > 0:
        upper = jnp.stack([win_n[radius:2 * radius],
                           win_t[radius:2 * radius],
                           win_q[radius:2 * radius]])
        lower = jnp.stack([win_n[1:radius + 1],
                           win_t[1:radius + 1],
                           win_q[1:radius + 1]])
        from_right = shift(upper, right_to_left)
        from_left = shift(lower, left_to_right)
        win_n = win_n.at[radius + 1:].set(from_right[0]).at[:radius].set(from_left[0])
        win_t = win_t.at[radius + 1:].set(from_right[1]).at[:radius].set(from_left[1])
        win_q = win_q.at[radius + 1:].set(from_right[2]).at[:radius].set(from_left[2])

    # ------------------------------------- (d) steal rate + victim selection
    s_i = steal_rate_window(win_n, win_t, radius)
    idx = lax.axis_index(axis)
    offs = jnp.arange(-radius, radius + 1, dtype=jnp.int32)
    owner = jnp.mod(idx + offs, p)  # window cell -> worker id
    known = ~jnp.isnan(win_t)
    is_self = offs == 0

    # S_j per window cell (each cell uses the SAME window — i's knowledge).
    def cell_rate(c):
        rolled_n = jnp.roll(win_n, radius - c)  # put cell c at centre
        rolled_t = jnp.roll(win_t, radius - c)
        return steal_rate_window(rolled_n, rolled_t, radius)

    s_cells = jax.vmap(cell_rate)(jnp.arange(w))
    has_q = win_q > 0.0
    surplus = (s_cells < 0.0) & has_q & known & (~is_self)

    # Criterion 1 — closest rate: surplus volume scaled by match closeness.
    w1 = jnp.maximum(-s_cells, 0.0) / (
        1.0 + jnp.abs(-s_cells - jnp.maximum(s_i, 0.0))
    )
    # Criterion 2 — in-pair (Eq. 10) when no surplus candidate exists.
    pair = _pair_rate(n_self, t_self, win_n, jnp.where(known, win_t, jnp.inf))
    w2_mask = (pair > 0.0) & has_q & known & (~is_self)
    use_pair = ~surplus.any()
    cand = jnp.where(use_pair, w2_mask, surplus)
    weights = jnp.where(use_pair, jnp.maximum(pair, 0.0), w1)
    weights = jnp.where(cand, weights, 0.0)

    key, sub = jax.random.split(jax.random.wrap_key_data(key))
    logits = jnp.where(weights > 0.0, jnp.log(weights), -jnp.inf)
    pick = jax.random.categorical(sub, logits)
    any_cand = cand.any()

    # Idle workers always steal (relay rule, see core.steal.plan_steal);
    # busy workers steal preemptively only when S_i > 0.
    idle = qlen <= 0.0
    use_pair_amt = use_pair | (s_i <= 0.0)
    want = jnp.where(use_pair_amt, pair[pick], jnp.minimum(s_i, -s_cells[pick]))
    amount = gamma_round(
        jnp.maximum(want, 0.0), n_self, t_self, win_n[pick], win_t[pick]
    )
    amount = jnp.clip(amount, 0, max_steal)
    do_steal = ((s_i > 0.0) | idle) & any_cand & (amount > 0)
    victim = owner[pick]

    # --------------------------------------- (e) request / grant (all_to_all)
    # Request vector: how many tasks I ask of each worker.  ``packed``
    # (§Perf): requests ride as u16 (amounts <= max_steal << 65535) —
    # halves the wire bytes of the request round.
    req = jnp.zeros((p,), jnp.int32).at[victim].set(
        jnp.where(do_steal, amount, 0)
    )
    if packed:
        req_in = lax.all_to_all(req.astype(jnp.uint16), axis, 0, 0).astype(
            jnp.int32
        )
    else:
        req_in = lax.all_to_all(req, axis, 0, 0)  # req_in[j] = j's ask of me
    # Grant greedily, largest request first, bounded by my queue.
    order = jnp.argsort(-req_in)
    sorted_req = req_in[order]
    avail = jnp.maximum(tail - head, 0)
    cum_before = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sorted_req)[:-1]]
    )
    sorted_grant = jnp.clip(avail - cum_before, 0, sorted_req)
    grant = jnp.zeros((p,), jnp.int32).at[order].set(sorted_grant)
    grant_off = jnp.zeros((p,), jnp.int32).at[order].set(cum_before)
    total_grant = grant.sum()

    # Build payload [p, max_steal]: tasks popped from my tail.
    sslot = jnp.arange(max_steal, dtype=jnp.int32)[None, :]
    src = tail - 1 - (grant_off[:, None] + sslot)
    valid = sslot < grant[:, None]
    cap = queue.shape[0]
    use_u16 = packed and cap < 0xFFFF
    if use_u16:
        # Task ids < capacity fit u16: halves the payload exchange — the
        # dominant collective of the round (§Perf).
        payload = jnp.where(
            valid, queue[jnp.clip(src, 0, cap - 1)], 0xFFFF
        ).astype(jnp.uint16)
        recv = lax.all_to_all(payload, axis, 0, 0)  # [p, max_steal] u16
        got = recv != 0xFFFF
        recv_ids = recv.astype(jnp.int32)
    else:
        payload = jnp.where(valid, queue[jnp.clip(src, 0, cap - 1)], -1)
        recv = lax.all_to_all(payload, axis, 0, 0)  # [p, max_steal]
        got = recv >= 0
        recv_ids = recv
    tail = tail - total_grant
    incoming = got.sum().astype(jnp.int32)

    if packed:
        # Cumsum compaction (stable, two passes) instead of a full sort
        # (log^2 n bitonic passes) — received order is irrelevant.
        gotf = got.reshape(-1)
        pos = jnp.cumsum(gotf.astype(jnp.int32)) - 1
        dst = jnp.where(gotf, tail + pos, cap)
        queue = queue.at[dst].set(recv_ids.reshape(-1), mode="drop")
    else:
        flat = jnp.sort(
            jnp.where(got, recv_ids, jnp.iinfo(jnp.int32).max).reshape(-1)
        )  # valid ids first, sentinel-padded
        ok = jnp.arange(flat.shape[0], dtype=jnp.int32) < incoming
        dst = jnp.where(
            ok, tail + jnp.arange(flat.shape[0], dtype=jnp.int32), cap
        )
        queue = queue.at[dst].set(flat, mode="drop")  # index==cap -> dropped
    tail2 = tail + incoming

    qlen2 = (tail2 - head).astype(jnp.float32)
    win_q = win_q.at[radius].set(qlen2)
    win_n = win_n.at[radius].set(executed.astype(jnp.float32) + qlen2)

    return SchedState(
        queue=queue[None],
        head=head[None],
        tail=tail2[None],
        executed=executed[None],
        t_avg=t_avg[None],
        clock=clock[None],
        win_n=win_n[None],
        win_t=win_t[None],
        win_q=win_q[None],
        key=jax.random.key_data(key)[None],
        credit=credit[None],
    )


def make_round_fn(mesh: Mesh, axis: str, radius: int, max_steal: int,
                  execute: bool = True, packed: bool = True):
    """shard_map-wrapped jitted round function over ``axis`` of ``mesh``."""
    p = mesh.shape[axis]
    spec = SchedState(
        queue=P(axis, None), head=P(axis), tail=P(axis), executed=P(axis),
        t_avg=P(axis), clock=P(axis), win_n=P(axis, None),
        win_t=P(axis, None), win_q=P(axis, None), key=P(axis, None),
        credit=P(axis),
    )
    fn = functools.partial(
        a2ws_round, axis=axis, radius=radius, max_steal=max_steal,
        num_workers=p, execute=execute, packed=packed,
    )
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec)
    return jax.jit(sharded)


def virtual_run(
    mesh: Mesh,
    axis: str,
    speeds,
    num_tasks: int,
    radius: int,
    max_steal: int = 8,
    max_rounds: int = 4096,
    seed: int = 0,
):
    """Run the jitted scheduler to completion in virtual time.

    Returns (final_state, rounds, makespan).  Fully compiled: a
    ``lax.while_loop`` around the shard_map round — this is the cell used for
    the technique's own dry-run/roofline entry.
    """
    p = mesh.shape[axis]
    speeds = jnp.asarray(speeds, jnp.float32)
    base, rem = divmod(num_tasks, p)
    counts = jnp.array([base + (1 if i < rem else 0) for i in range(p)], jnp.int32)
    state = init_state(p, counts, speeds, radius, capacity=num_tasks, seed=seed)
    round_fn = make_round_fn(mesh, axis, radius, max_steal)

    def cond(carry):
        state, rounds = carry
        remaining = (state.tail - state.head).sum()
        return (remaining > 0) & (rounds < max_rounds)

    def body(carry):
        state, rounds = carry
        return round_fn(state), rounds + 1

    state, rounds = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
    makespan = state.clock.max()
    return state, int(rounds), float(makespan)
