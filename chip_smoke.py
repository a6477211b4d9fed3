"""Smoke run of the system's main paths on one TPU chip.

    python3 chip_smoke.py                # one chip: device, shots, serve
    python3 chip_smoke.py --four-chips   # sharded serving, 1x4 mesh vs 1 chip

Phases (each prints its own summary line):

* device — platform, kind and count; anything but a TPU exits non-zero.
* shots — the paper's workload: FD3D shots on a 256^3 velocity model,
  scheduled by the A2WS ``WorkerPool`` over threads sharing the chip, with
  the compiled Pallas kernel; every seismogram is recomputed with the jnp
  oracle and compared.
* serve — phi4-mini at published widths and full depth, random weights from
  ``--seed``, served through the ``launch/serve.py`` open-arrival path by a
  ``ServePool`` of two replicas on the chip; decode-through-cache logits
  are compared with ``lm.prefill`` logits of the same weights.
* four-chips (only with ``--four-chips``) — the sharded prefill/decode steps
  on a (data=1, model=4) mesh against the mesh-free path on device 0.

The last line of standard output is ``{"ok": true, "device": {...}}``; any
failed check or exception exits non-zero before it.

``--cpu-rehearsal`` runs the same phases on the CPU at tiny sizes (reduced
model config, 32^3 shots, the kernel in the Pallas interpreter), with four
virtual devices under ``--four-chips``:

    JAX_PLATFORMS=cpu python3 chip_smoke.py --cpu-rehearsal
    JAX_PLATFORMS=cpu python3 chip_smoke.py --cpu-rehearsal --four-chips
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "phi4-mini-3.8b"

# Shot seismograms: f32 throughout.  The kernel sums the stencil terms in
# another order than the oracle, so the two differ by rounding, which the
# CFL-stable leapfrog carries over nt steps without amplifying it.  1e-4 of
# the peak amplitude allows ~1000 f32 ulps; a wrong coefficient or halo
# plane is an error of order 1e-2 or more.
SHOT_RTOL = 1e-4
# Logits: bf16 weights and activations (8-bit mantissa, 2^-8 per rounding)
# through 32 layers.  Prefill, decode and the sharded steps reduce their
# matmuls and attention in different orders, so they round differently; a
# cache, position or sharding fault gives a relative error of order 1.
LOGIT_RTOL = 5e-2


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def rel_err(got, want) -> float:
    """max |got - want| over max |want|, in f32."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def rel_l2(got, want) -> float:
    """||got - want|| / ||want||, in f32."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# ----------------------------------------------------------------- phases
def phase_device(rehearsal: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} jax={jax.__version__}", flush=True)
    if dev["platform"] != "tpu" and not rehearsal:
        print("no TPU found; chip_smoke.py has no CPU path", file=sys.stderr)
        raise SystemExit(1)
    return dev


def phase_shots(rehearsal: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.a2ws import WorkerPool
    from repro.kernels.fd3d import default_backend
    from repro.seismic.model import make_demo_model, make_shot_grid, run_shot

    n, nt, num_shots, workers = (32, 20, 4, 2) if rehearsal else (256, 100, 8, 3)
    # On the chip the shots take run_shot's default backend, which must
    # resolve to the compiled kernel; the rehearsal names the interpreter.
    backend = "pallas_interpret" if rehearsal else None
    if not rehearsal:
        check(default_backend() == "pallas",
              f"default FD3D backend is {default_backend()!r}, not 'pallas'")
    t0 = time.perf_counter()
    model = make_demo_model(n=n)
    check(model.cfl_ok(), "demo model violates CFL")
    shots = make_shot_grid(model, num_shots)
    srcs = [jnp.asarray(s.src) for s in shots]
    recs = [jnp.asarray(s.rec_array()) for s in shots]

    compiled = run_shot.lower(model, srcs[0], recs[0], nt=nt,
                              backend=backend).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if not rehearsal:
        check(has_kernel, "compiled shot program has no tpu_custom_call")
    run_shot(model, srcs[0], recs[0], nt=nt,
             backend=backend).block_until_ready()
    t_compile = time.perf_counter() - t0

    seis: dict[int, np.ndarray] = {}
    runs = [0] * num_shots
    lock = threading.Lock()

    def task_fn(wid: int, idx: int) -> None:
        out = np.asarray(run_shot(model, srcs[idx], recs[idx], nt=nt,
                                  backend=backend))
        with lock:
            runs[idx] += 1
            seis[idx] = out

    pool = WorkerPool(list(range(num_shots)), workers, task_fn, seed=0)
    t1 = time.perf_counter()
    stats = pool.run()
    t_pool = time.perf_counter() - t1
    check(not pool.errors, f"worker errors: {pool.errors}")
    check(runs == [1] * num_shots, f"shot run counts {runs}")
    check(sum(stats.per_worker_tasks) == num_shots,
          f"per-worker tasks {stats.per_worker_tasks}")

    errs = []
    for i in range(num_shots):
        want = np.asarray(run_shot(model, srcs[i], recs[i], nt=nt,
                                   backend="ref"))
        got = seis[i]
        check(got.shape == (nt, len(shots[i].receivers)),
              f"seismogram shape {got.shape}")
        check(bool(np.isfinite(got).all()), f"shot {i} not finite")
        check(float(np.abs(want).max()) > 0.0, f"shot {i} recorded nothing")
        errs.append(rel_err(got, want))
    worst = max(errs)
    print(f"[shots] grid={n}^3 nt={nt} shots={num_shots} workers={workers} "
          f"backend={backend or default_backend()} "
          f"tpu_custom_call={has_kernel} compile+warm={t_compile:.2f}s "
          f"pool={t_pool:.3f}s per_worker={stats.per_worker_tasks} "
          f"steals={len(stats.steals)} max_rel_err_vs_ref={worst:.3e} "
          f"(tol {SHOT_RTOL:g})", flush=True)
    check(worst <= SHOT_RTOL, f"seismogram error {worst:.3e} > {SHOT_RTOL:g}")


def _prompt(cfg, seed: int, length: int):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    return jnp.asarray(rng.integers(0, cfg.vocab, (1, length)), jnp.int32)


def phase_serve(rehearsal: bool, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config, get_smoke
    from repro.launch import serve
    from repro.models import lm

    requests, prompt_len, new_tokens = 6, 16, 8
    argv = ["--arch", ARCH, "--requests", str(requests),
            "--prompt-len", str(prompt_len), "--new-tokens", str(new_tokens),
            "--open-arrival", "--rate", "8", "--replicas", "2",
            "--slow-factor", "1", "--seed", str(seed)]
    if rehearsal:
        argv.append("--smoke")
    args = serve.build_parser().parse_args(argv)
    cfg = get_smoke(ARCH) if args.smoke else get_config(ARCH)

    t0 = time.perf_counter()
    params = jax.block_until_ready(serve.init_params(cfg, seed))
    t_init = time.perf_counter() - t0
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[serve] {cfg.name}: {cfg.n_layers}L d_model={cfg.d_model} "
          f"vocab={cfg.vocab} weights={nbytes / 1e9:.3f} GB "
          f"init={t_init:.2f}s", flush=True)

    t1 = time.perf_counter()
    futs, pool = serve.run_open_arrival(cfg, params, args)
    t_serve = time.perf_counter() - t1
    check(all(f.done() for f in futs), "not every request completed")
    for f in futs:
        out = f.result(timeout=0)["completion"]
        check(len(out) == new_tokens, f"completion length {len(out)}")
    check(not pool.errors, f"replica errors: {pool.errors}")

    # decode through the cache vs prefill, for one prompt
    tokens = _prompt(cfg, seed, prompt_len)
    pre_logits, _ = jax.jit(lambda p, b: lm.prefill(p, b, cfg))(
        params, {"tokens": tokens})
    decode = serve.make_decode(cfg)
    caches = lm.init_caches(cfg, 1, prompt_len)
    for i in range(prompt_len):
        logits, caches = decode(params, tokens[:, i : i + 1], caches,
                                jnp.int32(i))
    err = rel_l2(logits[:, -1], pre_logits[:, -1])
    print(f"[serve] requests={len(futs)} replicas=2 "
          f"served_per_replica={[sum(1 for f in futs if f.worker == r) for r in (0, 1)]} "
          f"wall={t_serve:.2f}s (incl. decode compile) replica_errors=0 "
          f"decode_vs_prefill rel_l2={err:.3e} "
          f"max_rel={rel_err(logits[:, -1], pre_logits[:, -1]):.3e} "
          f"(tol rel_l2 {LOGIT_RTOL:g})", flush=True)
    check(err <= LOGIT_RTOL, f"decode vs prefill rel_l2 {err:.3e}")


def _device_bytes(tree) -> dict:
    """Bytes of ``tree``'s shards held by each device."""
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def phase_four_chips(rehearsal: bool, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config, get_smoke
    from repro.launch import serve
    from repro.launch.mesh import make_debug_mesh
    from repro.models import lm
    from repro.parallel.sharding import (ParallelContext, make_context,
                                         serve_context, shardings_for)
    from repro.serve.engine import (cache_shardings, jit_decode_step,
                                    jit_prefill_step)

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    cfg = get_smoke(ARCH) if rehearsal else get_config(ARCH)
    prompt_len, new_tokens = 16, 8
    cache_len = prompt_len + new_tokens
    tokens = _prompt(cfg, seed, prompt_len)

    t0 = time.perf_counter()
    params = jax.block_until_ready(serve.init_params(cfg, seed))  # device 0
    mesh = make_debug_mesh(1, 4)
    ctx = make_context(mesh)
    batch_sds = {"tokens": jax.ShapeDtypeStruct(tokens.shape, jnp.int32)}
    prefill_sh = jit_prefill_step(cfg, ctx, batch_sds)
    decode_sh = jit_decode_step(cfg, ctx, 1, cache_len)
    prefill_1 = jit_prefill_step(cfg, ParallelContext(mesh=None), batch_sds)
    decode_1 = jit_decode_step(cfg, ParallelContext(mesh=None), 1, cache_len)

    before = [d.memory_stats() for d in devs[:4]]
    # the decode step's serving layout; with data=1 it equals the prefill
    # step's training layout
    serve_ctx = serve_context(mesh)
    shapes, specs = lm.init_shapes(cfg)
    params_sh = jax.block_until_ready(
        jax.device_put(params, shardings_for(specs, serve_ctx, shapes)))
    after = [d.memory_stats() for d in devs[:4]]
    per_dev = _device_bytes(params_sh)
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    shares = [per_dev.get(d, 0) / total for d in devs[:4]]
    grown = None
    if all(m is not None for m in before + after):
        grown = [(a["bytes_in_use"] - b["bytes_in_use"]) / total
                 for a, b in zip(after, before)]
    print(f"[four-chips] mesh={dict(mesh.shape)} weights={total / 1e9:.3f} GB "
          f"shard share per device={[round(s, 3) for s in shares]} "
          f"memory_stats growth share={grown and [round(g, 3) for g in grown]} "
          f"setup={time.perf_counter() - t0:.2f}s", flush=True)
    check(all(s >= 0.2 for s in shares), f"weights not spread: {shares}")
    if grown is not None:
        check(all(g >= 0.2 for g in grown), f"device memory growth {grown}")

    t1 = time.perf_counter()
    logits_sh, caches_sh = prefill_sh(params_sh, {"tokens": tokens})
    logits_1, caches_1 = prefill_1(params, {"tokens": tokens})
    errs = [rel_l2(logits_sh[:, -1], logits_1[:, -1])]
    caches_sh = jax.device_put(lm.pad_caches(caches_sh, cfg, cache_len),
                               cache_shardings(cfg, serve_ctx, 1, cache_len))
    caches_1 = lm.pad_caches(caches_1, cfg, cache_len)
    tok = jnp.argmax(logits_1[:, -1], axis=-1).astype(jnp.int32)[:, None]
    for i in range(prompt_len, cache_len):
        l_sh, caches_sh = decode_sh(params_sh, tok, caches_sh, jnp.int32(i))
        l_1, caches_1 = decode_1(params, tok, caches_1, jnp.int32(i))
        errs.append(rel_l2(l_sh[:, -1], l_1[:, -1]))
        tok = jnp.argmax(l_1[:, -1], axis=-1).astype(jnp.int32)[:, None]
    worst = max(errs)
    print(f"[four-chips] prefill rel_l2={errs[0]:.3e} decode steps="
          f"{len(errs) - 1} max rel_l2={max(errs[1:]):.3e} "
          f"wall={time.perf_counter() - t1:.2f}s (incl. compile) "
          f"(tol rel_l2 {LOGIT_RTOL:g})", flush=True)
    check(worst <= LOGIT_RTOL, f"sharded vs one-chip rel_l2 {worst:.3e}")


# ------------------------------------------------------------------- main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded serving path on a 1x4 mesh")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, kernels interpreted")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.cpu_rehearsal and args.four_chips:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import compile_cache

    t0 = time.perf_counter()
    cache = compile_cache.enable()
    dev = phase_device(args.cpu_rehearsal)
    print(f"[device] compile cache: {cache}", flush=True)
    if args.four_chips:
        phase_four_chips(args.cpu_rehearsal, args.seed)
    else:
        phase_shots(args.cpu_rehearsal)
        phase_serve(args.cpu_rehearsal, args.seed)
    print(f"[done] total {time.perf_counter() - t0:.2f}s", flush=True)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
