"""Survey cells: back-to-back surveys of FD3D shots through the A2WS
``WorkerPool``, one worker per chip, each shot a ``seismic.model.run_shot``
call with the program's default backend (the compiled Pallas kernel on a
TPU).

The window opens when the first survey starts and closes at the end of the
survey in flight when ``--seconds`` runs out, so no shot is cut.  A
``--trace 1`` run profiles the window's first survey whole.
"""

from __future__ import annotations

import threading
import time
from functools import partial

import numpy as np

from chipbench import traffic
from chipbench.counts import fd3d_bytes, fd3d_flops
from chipbench.harness import Check, Outcome, span


def make_velocity(cfg: dict, key):
    """Layered earth with seeded, dipping and undulating interfaces, on
    the device, float32, shape (nz, ny, nx)."""
    import jax
    import jax.numpy as jnp

    nz, ny, nx = cfg["nz"], cfg["ny"], cfg["nx"]
    vmin, vmax, n = cfg["vmin_m_per_s"], cfg["vmax_m_per_s"], cfg["layers"]
    k1, k2, k3 = jax.random.split(key, 3)
    depth = jnp.sort(jax.random.uniform(k1, (n - 1,), minval=0.05, maxval=0.95)) * nz
    speed = jnp.linspace(vmin, vmax, n) + (vmax - vmin) / n * jax.random.uniform(
        k2, (n,), minval=-0.5, maxval=0.5)
    phase = jax.random.uniform(k3, (n - 1, 3), maxval=2 * jnp.pi)
    y = jnp.arange(ny, dtype=jnp.float32)[None, :, None] / ny
    x = jnp.arange(nx, dtype=jnp.float32)[None, None, :] / nx
    z = jnp.arange(nz, dtype=jnp.float32)[:, None, None]
    layer = jnp.zeros((nz, ny, nx), jnp.int32)
    for i in range(n - 1):
        h = (depth[i] + cfg["dip"] * nz * (x - 0.5) * jnp.cos(phase[i, 2])
             + cfg["lateral"] * nz * jnp.sin(2 * jnp.pi * 2 * x + phase[i, 0])
             * jnp.sin(2 * jnp.pi * 1.5 * y + phase[i, 1]))
        layer = layer + (z > h).astype(jnp.int32)
    return jnp.clip(speed[layer], vmin, vmax).astype(jnp.float32)


def rel_err(got, want) -> float:
    """Largest deviation over the largest amplitude of the reference."""
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _shot_geometry(cfg: dict, shot: dict):
    """Aperture origin, and source and receiver indices inside it."""
    (ay, ax), (sy, sx) = shot["aperture"], shot["src_yx"]
    y0 = int(np.clip(sy - ay // 2, 0, cfg["ny"] - ay))
    x0 = int(np.clip(sx - ax // 2, 0, cfg["nx"] - ax))
    src = np.array([cfg["src_depth"], sy - y0, sx - x0], np.int32)
    edge = cfg["sponge"] + 2
    rx = np.linspace(edge, ax - edge - 1, cfg["receivers"]).astype(np.int32)
    rec = np.stack([np.full_like(rx, cfg["rec_depth"]),
                    np.full_like(rx, sy - y0), rx], axis=1).astype(np.int32)
    return (y0, x0), src, rec


def run(r) -> Outcome:
    import jax

    from repro.core.a2ws import WorkerPool
    from repro.seismic.model import SeismicModel, run_shot

    cfg, mix = r.config, r.mix
    nt, grid = mix["nt"], (cfg["ny"], cfg["nx"])
    devs = r.devices[: r.chips]
    classes = [tuple(c["aperture"]) for c in mix["classes"]]
    cells_of = {a: cfg["nz"] * a[0] * a[1] for a in classes}

    make = jax.jit(partial(make_velocity, cfg))
    vel0 = jax.device_put(make(jax.random.key(r.seed)), devs[0])
    vel_on = [vel0] + [jax.device_put(vel0, d) for d in devs[1:]]

    @partial(jax.jit, static_argnames=("size",))
    def window(vel, y0, x0, size):
        return jax.lax.dynamic_slice(vel, (0, y0, x0), (cfg["nz"], *size))

    def shoot(w: int, shot: dict):
        """The timed path: one shot on worker ``w``'s chip."""
        dev = devs[w]
        (y0, x0), src, rec = _shot_geometry(cfg, shot)
        with span("shot_dispatch"):
            vel = window(vel_on[w], y0, x0, size=tuple(shot["aperture"]))
            model = SeismicModel(velocity=vel, dx=cfg["spacing_m"],
                                 dt=cfg["dt_s"], f_peak=cfg["f_peak_hz"],
                                 sponge=cfg["sponge"],
                                 sponge_decay=cfg["sponge_decay"])
            seis = run_shot(model, jax.device_put(src, dev),
                            jax.device_put(rec, dev), nt=nt)
        with span("seis_copy"):
            return np.asarray(seis)

    # Warm-up: every shot shape on every chip, in parallel across chips.
    warm = traffic.survey_shots(mix, grid, r.seed, survey=-1)
    firsts = [next(s for s in warm if tuple(s["aperture"]) == a) for a in classes]
    errs: list = []

    def warm_chip(w):
        try:
            for s in firsts:
                shoot(w, s)
        except BaseException as e:  # noqa: BLE001 — reported below
            errs.append(e)

    threads = [threading.Thread(target=warm_chip, args=(w,)) for w in range(len(devs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]

    results: dict = {}
    lock = threading.Lock()

    def task_fn(w: int, shot: dict) -> None:
        seis = shoot(w, shot)
        with lock:
            results[(shot["survey"], shot["index"])] = seis

    n_classes = len(classes)
    class_of = {a: i for i, a in enumerate(sorted(classes, key=lambda a: a[0] * a[1]))}
    surveys, records, errors = [], [], []
    trace_box = None
    t0 = time.perf_counter()
    setup_s = t0 - r.t_start
    r.log(f"setup {setup_s:.2f} s; window opens")
    s = 0
    while True:
        shots = [dict(x, survey=s) for x in traffic.survey_shots(mix, grid, r.seed, s)]
        pool = WorkerPool(
            shots, len(devs), task_fn, seed=r.seed,
            cost_class_fn=(lambda x: class_of[tuple(x["aperture"])]) if n_classes > 1 else None,
            num_classes=n_classes)
        if s == 0:
            with r.traced() as box:
                ts = time.perf_counter()
                stats = pool.run()
                te = time.perf_counter()
            trace_box = box[0]
            t0 += time.perf_counter() - te  # stopping the profiler is not survey time
        else:
            ts = time.perf_counter()
            stats = pool.run()
            te = time.perf_counter()
        errors += pool.errors
        surveys.append({"start": ts, "end": te, "workers": len(devs),
                        "steals": len(stats.steals)})
        for rec in stats.records:
            records.append({"survey": s, "worker": rec.worker, "start": rec.start,
                            "end": rec.end, "cells": cells_of[tuple(rec.task["aperture"])],
                            "aperture": tuple(rec.task["aperture"]),
                            "index": rec.task["index"]})
        s += 1
        if te - t0 >= r.seconds or errors:
            break
    t1 = time.perf_counter()
    done_cells = sum(x["cells"] for x in records)
    attempted = sum(len(traffic.survey_shots(mix, grid, r.seed, i)) for i in range(s))
    failed = attempted - len(results)
    r.log(f"window {t1 - t0:.3f} s: {s} surveys, {len(records)} shots, "
          f"steals {[x['steals'] for x in surveys]}")
    memory_peak = r.memory_peak()

    # Correctness: a seeded sample of the window's shots, the largest
    # aperture always among them, against the plain float32 reference.
    ref = r.reference()
    rng = traffic.rng_for(r.seed, "sample")
    biggest = max(records, key=lambda x: x["cells"])
    others = [x for x in records if x is not biggest]
    pick = [biggest] + [others[i] for i in rng.choice(
        len(others), min(len(others), mix["sample"] - 1), replace=False)]
    worst = worst_ctrl = 0.0
    for x in pick:
        shot = traffic.survey_shots(mix, grid, r.seed, x["survey"])[x["index"]]
        (y0, x0), src, rec = _shot_geometry(cfg, shot)
        vel = window(vel0, y0, x0, size=tuple(shot["aperture"]))

        def reference(**kw):
            return np.asarray(ref.shot(
                vel, src, rec, nt=nt, dx=cfg["spacing_m"], dt=cfg["dt_s"],
                f_peak=cfg["f_peak_hz"], width=cfg["sponge"],
                decay=cfg["sponge_decay"], **kw))

        want = reference()
        worst = max(worst, rel_err(results[(x["survey"], x["index"])], want))
        if r.control:
            worst_ctrl = max(worst_ctrl, rel_err(reference(dtype=jax.numpy.bfloat16), want))
    limit = cfg["limits"]["seis_rel_err"]
    checks = [Check("seis_rel_err", worst, limit)]
    if r.control:
        checks.append(Check("seis_rel_err.control", worst_ctrl, limit))

    gcell = done_cells * nt / 1e9 / (t1 - t0)
    traced = [x for x in records if x["survey"] == 0]
    ctx = {
        "surveys": surveys, "records": records,
        "traced_bytes": fd3d_bytes(sum(x["cells"] for x in traced), nt),
        "traced_flops": fd3d_flops(sum(x["cells"] for x in traced), nt),
    }
    return Outcome(setup_s=setup_s, e2e={"survey_gcell_per_s": gcell},
                   attempted=attempted, failed=failed, checks=checks,
                   memory_peak_bytes=memory_peak, layer_ctx=ctx,
                   trace=trace_box)
