"""Serving driver: batched greedy generation.

Serves the published config by default (a chip's worth of memory: phi4-mini
needs 8.9 GB of weights); ``--smoke`` swaps in the reduced config for a CPU
run.  Closed batch:

    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
        --smoke --requests 8 --prompt-len 32 --new-tokens 16

Open-arrival continuous batching (DESIGN.md §Open-arrival): requests arrive
as a Poisson stream into a live ``ServePool`` over heterogeneous replicas —
fast replicas steal queued requests from slow ones mid-flight, and the
driver reports per-request latency percentiles:

    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
        --smoke --requests 24 --prompt-len 16 --new-tokens 8 \
        --open-arrival --rate 8 --replicas 2 --slow-factor 4

``--policy`` swaps the scheduling policy balancing the replica pool
(DESIGN.md §Policy layer): a2ws (default) vs the ctws / lw / random
baselines, head-to-head on the same Poisson trace and latency metric.

``--autoscale-max N`` makes the pool ELASTIC (DESIGN.md §Elasticity): a
threshold autoscaler boots surge replicas up to N while the backlog
exceeds its per-replica bound and drains them back once traffic quiets.

``--limp-slowdown F`` injects a STRAGGLER fault (DESIGN.md §Straggler
plane): ``--limp-replica`` limps to F× its normal service time
``--limp-after`` seconds into the run.  ``--limp-factor`` (default on)
arms the adaptive limp detector — the pool re-prices the limping
replica's queue so the others strip it, stops routing new requests to
it, and reports the detector's flag transitions:

    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
        --smoke --requests 24 --prompt-len 16 --new-tokens 8 \
        --open-arrival --rate 8 --replicas 3 --slow-factor 1 \
        --limp-slowdown 16 --limp-after 0.5
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, get_config, get_smoke
from repro.core.limp import LimpConfig, SlowdownEvent, SlowdownSchedule
from repro.core.netfault import parse_netfaults
from repro.core.policy import POLICIES
from repro.core.spans import COUNTERS, span
from repro.core.topology import parse_topology
from repro.launch import compile_cache
from repro.models import lm
from repro.serve.engine import AutoscaleConfig, Replica, ServePool


def init_params(cfg, seed: int):
    """Random weights from ``seed``, drawn by one jitted program: the arrays
    land on the device once, with no eager intermediates beside them."""
    return jax.jit(lambda k: lm.init(cfg, k)[0])(jax.random.key(seed))


@functools.lru_cache(maxsize=None)
def make_decode(cfg):
    """One jitted decode step per config, shared by every caller (a fresh
    ``jax.jit`` per call would recompile every time).  Its programs are
    named ``jit_decode_step``, one per cache length."""

    def decode_step(params, tok, caches, pos):
        return lm.decode_step(params, tok, caches, pos, cfg)

    return jax.jit(decode_step, donate_argnums=(2,))


def generate(cfg, params, tokens: jnp.ndarray, new_tokens: int, decode=None):
    """Greedy generation for a [B, S] prompt batch (mesh-free path).

    Each of the S + N - 1 launches' host work (the decode dispatch, then the
    next prompt slice or the token selection) is a ``serve.step`` span with
    its position and phase.  The call adds its launches to the counter
    ``serve.launches`` and the thread CPU time of the launch loop to
    ``serve.host_cpu_ns``: a dispatch that sleeps on a full device queue, or
    a wait for the interpreter lock, adds none, so it is the host's cost and
    not the device's pace.  The clock is read once a call: on some hosts a
    thread CPU clock read costs microseconds and ticks in milliseconds."""
    b, s = tokens.shape
    cache_len = s + new_tokens
    caches = lm.init_caches(cfg, b, cache_len)
    # prefill re-runs through decode_step to keep the cache length fixed
    # (simple path for the smoke driver; the engine prefill is jitted).
    if decode is None:
        decode = make_decode(cfg)
    out = []
    tok = tokens[:, :1]
    t_cpu = time.thread_time_ns()
    for i in range(s + new_tokens - 1):
        prompt = i + 1 < s
        with span("serve.step", pos=i, phase="prompt" if prompt else "token"):
            logits, caches = decode(params, tok, caches, jnp.int32(i))
            if prompt:
                tok = tokens[:, i + 1 : i + 2]
            else:
                tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
                out.append(tok)
    COUNTERS.add("serve.host_cpu_ns", time.thread_time_ns() - t_cpu)
    COUNTERS.add("serve.launches", s + new_tokens - 1)
    return jnp.concatenate(out, axis=1)


def _closed_main(cfg, params, args) -> None:
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.requests, args.prompt_len)), jnp.int32
    )
    t0 = time.time()
    out = generate(cfg, params, prompts, args.new_tokens)
    dt = time.time() - t0
    total = args.requests * args.new_tokens
    print(f"generated {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s); sample: {np.asarray(out[0])[:8]}")


def run_open_arrival(cfg, params, args):
    """Continuous batching: Poisson arrivals into a live heterogeneous pool.

    Prints the run's summary and returns ``(futures, pool)``; the pool is
    shut down, and ``pool.errors`` lists every replica that died."""
    rng = np.random.default_rng(args.seed)

    # one shared jitted step: each request's caches are private (donation is
    # per-call, so concurrent replica threads don't interfere)
    decode = make_decode(cfg)

    def gen(request: dict) -> dict:
        out = generate(cfg, params, request["tokens"][None, :],
                       args.new_tokens, decode=decode)
        return {"completion": np.asarray(out[0]).tolist()}
    # one jit warm-up so compile time doesn't poison the latency stats
    gen({"tokens": jnp.zeros((args.prompt_len,), jnp.int32)})

    replicas = [Replica("replica0", gen)]
    for r in range(1, args.replicas):
        # replicas share the weights/compiled fn; heterogeneity is emulated
        # by slow_factor (on real hardware: different device slices)
        replicas.append(Replica(f"replica{r}", gen,
                                slow_factor=args.slow_factor))
    autoscale = None
    if args.autoscale_max > args.replicas:
        # Elastic pool (DESIGN.md §Elasticity): surge replicas boot at full
        # speed (fresh capacity) and drain back out once the backlog clears.
        autoscale = AutoscaleConfig(
            factory=lambda wid: Replica(f"surge{wid}", gen),
            min_replicas=args.replicas,
            max_replicas=args.autoscale_max,
        )
    slowdown = None
    limp = None
    if args.limp_slowdown > 1.0:
        # Straggler fault (DESIGN.md §Straggler plane): one replica limps
        # mid-run; the detector (unless disabled) re-prices its queue so
        # the healthy replicas strip it and new requests route around it.
        if not 0 <= args.limp_replica < args.replicas:
            raise SystemExit("--limp-replica must name a boot replica")
        slowdown = SlowdownSchedule((
            SlowdownEvent(args.limp_replica, args.limp_after,
                          args.limp_slowdown),
        ))
        if args.limp_factor > 1.0:
            limp = LimpConfig(limp_factor=args.limp_factor)
    netfaults = parse_netfaults(args.net_faults, args.replicas)
    pool = ServePool(replicas, seed=args.seed, policy=args.policy,
                     autoscale=autoscale, slowdown=slowdown, limp=limp,
                     topology=parse_topology(args.topology, args.replicas),
                     migration_cost=args.migration_cost,
                     netfaults=netfaults)
    pool.start()
    t0 = time.perf_counter()

    futs = []
    for _ in range(args.requests):
        time.sleep(float(rng.exponential(1.0 / args.rate)))
        req = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (args.prompt_len,)), jnp.int32)}
        futs.append(pool.submit(req))
    for f in futs:
        f.result(timeout=600)
    scale_outs = sum(1 for e in pool.scale_events if e[1] == "out")
    peak = pool.peak_live
    stats = pool.shutdown()
    pct = stats.latency_percentiles()
    per_rep = stats.per_worker_tasks
    print(f"served {len(futs)} streamed requests [{args.policy}]; "
          f"requests/replica={per_rep} steals={len(stats.steals)}")
    if autoscale is not None:
        print(f"autoscaler: peak {peak} replicas, {scale_outs} scale-outs")
    if slowdown is not None:
        flips = ", ".join(f"replica{w} {'limp' if f else 'recovered'}"
                          f" @{t - t0:.2f}s" for t, w, f in pool.limp_log)
        print(f"limp detector: {flips or 'no transitions'}")
    if netfaults is not None:
        print(f"fault fabric: {stats.net_failed} dropped steal requests, "
              f"{stats.lease_expired} leases expired")
    print("latency p50/p95/p99 = "
          + "/".join(f"{pct[q]*1e3:.0f}ms" for q in (50.0, 95.0, 99.0)))
    print(f"sample completion: {futs[0].result()['completion'][:8]}")
    return futs, pool


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU runs)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--open-arrival", action="store_true",
                    help="stream requests into a live ServePool")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate, requests/sec (open mode)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="model replicas in the pool (open mode)")
    ap.add_argument("--slow-factor", type=float, default=4.0,
                    help="slowdown of replicas 1.. vs replica 0 (open mode)")
    ap.add_argument("--policy", choices=POLICIES, default="a2ws",
                    help="scheduling policy for the replica pool (open mode)")
    ap.add_argument("--autoscale-max", type=int, default=0,
                    help="elastic pool: scale out to at most this many "
                         "replicas under backlog, drain back when idle "
                         "(0 = fixed pool; open mode)")
    ap.add_argument("--limp-slowdown", type=float, default=0.0,
                    help="straggler fault: limp one replica to this multiple "
                         "of its normal service time (0/1 = no fault; "
                         "open mode)")
    ap.add_argument("--limp-replica", type=int, default=0,
                    help="which boot replica the straggler fault hits")
    ap.add_argument("--limp-after", type=float, default=0.5,
                    help="seconds after start() the straggler fault begins")
    ap.add_argument("--topology", default="none",
                    help="network-cost model pricing steals between replicas "
                         "(DESIGN.md §Topology plane): none | "
                         "uniform:LAT:PER_TASK | two-level:K:INTRA:CROSS | "
                         "fat-tree:K:HOP (costs in seconds; open mode)")
    ap.add_argument("--net-faults", default="none",
                    help="network-fault plane on the replica steal fabric "
                         "(DESIGN.md §Fault fabric): none | drop:PROB | "
                         "delay:SEC | partition:START:DUR[:K] — combinable "
                         "with '+', e.g. drop:0.1+partition:5:30:2 "
                         "(open mode)")
    ap.add_argument("--migration-cost", type=float, default=0.0,
                    help="per-request warm-state cost of serving a stolen "
                         "request cold, folded into every remote link of "
                         "--topology (seconds; open mode)")
    ap.add_argument("--limp-factor", type=float, default=4.0,
                    help="limp detector threshold: flag a replica whose "
                         "recent service time exceeds its baseline by this "
                         "factor (<=1 disables detection — the count-based "
                         "ablation)")
    return ap


def main() -> None:
    args = build_parser().parse_args()

    compile_cache.enable()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.frontend != "none" or cfg.enc_layers:
        raise SystemExit("serve driver handles token-in archs")
    params = init_params(cfg, args.seed)
    if args.open_arrival:
        run_open_arrival(cfg, params, args)
    else:
        _closed_main(cfg, params, args)


if __name__ == "__main__":
    main()
