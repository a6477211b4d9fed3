"""Serve cells: requests through ``serve.engine.ServePool``, whose replicas
run ``launch.serve.generate`` with the shared ``launch.serve.make_decode``
program (the glue ``run_open_arrival`` uses), greedy.

Requests come in jobs (``traffic.serve_jobs``): a job is submitted whole
whenever fewer than ``backlog`` requests are outstanding, so the replicas
never run dry.  When ``--seconds`` runs out no further job is submitted;
the window closes when the last request submitted completes, so it holds
whole jobs only, and every request in it counts.

A ``--trace 1`` run profiles a sub-window of ``trace_seconds`` starting
``trace_after`` seconds into the window.
"""

from __future__ import annotations

import re
import threading
import time

import numpy as np

from chipbench import traffic
from chipbench.harness import Check, Outcome, span

_SHAPE_KEYS = {"d_model": "hidden_size", "d_ff": "intermediate_size",
               "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
               "head_dim_": "head_dim", "n_layers": "num_hidden_layers",
               "vocab": "vocab_size", "rope_theta": "rope_theta",
               "norm_eps": "rms_norm_eps", "tie_embeddings": "tie_word_embeddings",
               "max_seq": "max_position_embeddings", "dtype": "torch_dtype"}
# Keys the program takes as options: set from the file, as published.
_OPTIONS = ("norm_eps", "tie_embeddings", "max_seq")


def program_config(config: dict):
    """The program's own config for this model, with its options set as
    the file states them, and every other size checked against the file."""
    from repro.configs.base import get_config

    cfg = get_config(config["program_arch"]).with_(
        **{attr: config[_SHAPE_KEYS[attr]] for attr in _OPTIONS})
    for attr, key in _SHAPE_KEYS.items():
        if getattr(cfg, attr) != config[key]:
            raise SystemExit(f"program config {attr}={getattr(cfg, attr)!r} "
                             f"differs from {key}={config[key]!r}")
    return cfg


def make_params(cfg, seed: int):
    """Weights in the program's layout, drawn on the device from ``seed``
    by one jitted call, in the type they are served in."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    shapes = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0))[0])
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(key):
        out = []
        for i, (path, sds) in enumerate(paths):
            name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, sds.shape, jnp.float32)
            if "norm" in name:
                std = 0.1
            elif name in ("embed", "head"):
                std = 0.02
            else:
                std = sds.shape[-2] ** -0.5
            out.append((z * std).astype(sds.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(draw)(jax.random.key(seed))


def _wait(fut, timeout) -> None:
    """Wait for a request; its outcome is read from the future later."""
    try:
        fut.result(timeout=timeout)
    except Exception:  # noqa: BLE001 — a failed or late request is counted, not raised
        pass


def _alone_s(futs, t1: float, replicas: int) -> float:
    """Seconds after the first request starts in which fewer replicas run a
    request than the pool has (the drain at the end, and any stall)."""
    edges = sorted([(f.start_t, 1) for f in futs] + [(f.end_t, -1) for f in futs])
    busy, last, alone = 0, None, 0.0
    for t, step in edges:
        if last is not None and busy < replicas:
            alone += t - last
        busy, last = busy + step, t
    return alone + (t1 - last if last is not None and last < t1 else 0.0)


def module_name(fn) -> str:
    """The HLO module name XLA gives a jitted function."""
    name = getattr(fn, "__name__", "fn")  # "<lambda>" becomes "jit__lambda"
    return "jit_" + re.sub(r"[^A-Za-z0-9_]", "_", name).rstrip("_")


def run(r) -> Outcome:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import generate, make_decode
    from repro.serve.engine import Replica, ServePool

    config, mix = r.config, r.mix
    cfg = program_config(config)
    params = make_params(cfg, r.seed)
    decode = make_decode(cfg)

    def gen(request: dict) -> dict:
        with span("generate"):
            out = generate(cfg, params, jnp.asarray(request["prompt"])[None, :],
                           request["new_tokens"], decode=decode)
            return {"completion": np.asarray(out[0])}

    jobs = traffic.serve_jobs(mix, r.seconds, config["vocab_size"], r.seed)
    # Warm-up: one request of every (prompt, output) length the mix sends.
    shapes = sorted({(len(q["prompt"]), q["new_tokens"]) for q in jobs[0]})
    for s, n in shapes:
        gen({"prompt": np.zeros(s, np.int32), "new_tokens": n})

    # Programs compiled, or loaded from the compile cache, from here on:
    # warm-up has missed a shape when the window holds any.
    compiles: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    pool = ServePool([Replica(f"replica{i}", gen, slow_factor=config["slow_factor"])
                      for i in range(config["replicas"])], seed=r.seed)
    pool.start()
    served: list = []  # (request, future)
    t0 = time.perf_counter()
    setup_s = t0 - r.t_start
    r.log(f"setup {setup_s:.2f} s; warmed {shapes}; window opens")
    deadline = t0 + r.seconds
    exhausted = []

    def feeder():
        feed = iter(jobs)
        outstanding: list = []
        while time.perf_counter() < deadline:
            outstanding = [f for f in outstanding if not f.done()]
            if len(outstanding) >= mix["backlog"]:
                _wait(outstanding[0], 0.005)
                continue
            job = next(feed, None)
            if job is None:
                exhausted.append(True)
                return
            with span("submit"):
                futs = [pool.submit({"prompt": q["prompt"], "new_tokens": q["new_tokens"]})
                        for q in job]
            served.extend(zip(job, futs))
            outstanding += futs

    fed = threading.Thread(target=feeder)
    fed.start()
    t_tr = t0 + min(mix["trace_after"], r.seconds / 2)
    time.sleep(max(0.0, t_tr - time.perf_counter()))
    with r.traced() as box:
        if r.trace:
            time.sleep(min(mix["trace_seconds"], max(0.0, deadline - time.perf_counter())))
    trace_box = box[0]
    fed.join()
    for _, fut in served:
        _wait(fut, max(1.0, deadline + 240 - time.perf_counter()))
    pool.shutdown()
    t1 = max((f.end_t for _, f in served if f.done()), default=time.perf_counter())
    memory_peak = r.memory_peak()
    r.log(f"{len(compiles)} programs compiled or loaded in the window")

    ok = [(q, f) for q, f in served if f.done() and f.error is None]
    failed = len(served) - len(ok)
    if exhausted:
        r.log("the mix ran out of jobs before the window closed")
        failed += 1
    e2e = {"tokens_per_s": sum(q["new_tokens"] for q, _ in ok) / (t1 - t0)}
    r.log(f"window {t1 - t0:.3f} s, {len(served) // len(jobs[0])} jobs, "
          f"{len(served)} requests, {failed} failed, e2e {e2e}; "
          f"{_alone_s([f for _, f in ok], t1, config['replicas']):.3f} s with fewer replicas "
          f"busy than {config['replicas']}")
    timeline = sorted((round(f.start_t - t0, 3), round(f.end_t - t0, 3), f.worker,
                       len(q["prompt"]), q["new_tokens"]) for q, f in ok)
    r.log(f"requests (start s, end s, replica, prompt, output): {timeline}")

    # Correctness: a seeded sample of finished requests with the longest in
    # it, each prompt with its served tokens through the plain reference.
    ref = r.reference()
    rng = traffic.rng_for(r.seed, "sample")
    order = sorted(range(len(ok)), key=lambda i: -ok[i][0]["new_tokens"])
    pick = [order[0]] + [int(i) for i in rng.permutation(order[1:])]
    worst = worst_ctrl = 0.0
    tokens = 0
    pad = max(len(q["prompt"]) + q["new_tokens"] for q in jobs[0])
    for i in pick:
        if tokens >= mix["sample_tokens"]:
            break
        q, f = ok[i]
        s, got = len(q["prompt"]), np.asarray(f.result()["completion"])
        seq = np.zeros(pad, np.int32)
        seq[:s], seq[s:s + len(got)] = q["prompt"], got
        lg = ref.logits(params, jnp.asarray(seq), config)
        g = ref.gaps(lg[s - 1:s - 1 + len(got)], jnp.asarray(got))
        worst = max(worst, float(jnp.max(g)))
        if r.control:  # the token the lower precision puts first
            top = jnp.argmax(ref.logits(params, jnp.asarray(seq), config, fp8=True), -1)
            g = ref.gaps(lg[s - 1:s - 1 + len(got)], top[s - 1:s - 1 + len(got)])
            worst_ctrl = max(worst_ctrl, float(jnp.max(g)))
        tokens += len(got)
    limit = config["limits"]["logit_gap"]
    checks = [Check("logit_gap", worst, limit),
              Check("sampled_tokens_short", float(mix["sample_tokens"] > tokens), 0.0)]
    if r.control:
        checks.append(Check("logit_gap.control", worst_ctrl, limit))

    # Every token, prompt or generated, is one decode launch at its position.
    n_launch = sum(len(q["prompt"]) + q["new_tokens"] - 1 for q, _ in ok)
    pos_sum = sum((len(q["prompt"]) + q["new_tokens"] - 1)
                  * (len(q["prompt"]) + q["new_tokens"] - 2) / 2 for q, _ in ok)
    # One decode program per cache length (prompt + output) the mix sends.
    ctx = {"model": config, "decode_module": module_name(decode),
           "decode_programs": len({s + n for s, n in shapes}),
           "mean_pos": pos_sum / max(n_launch, 1)}
    return Outcome(setup_s=setup_s, e2e=e2e, attempted=len(served), failed=failed,
                   checks=checks, memory_peak_bytes=memory_peak, layer_ctx=ctx,
                   trace=trace_box)
