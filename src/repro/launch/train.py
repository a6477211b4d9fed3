"""Training driver: any assigned arch on whatever mesh exists.

On real hardware this runs the pjit train step over the production mesh; on
this CPU container use ``--smoke`` (reduced config, mesh-free) to run end to
end.  Fault tolerance: periodic async checkpoints, resume on start.

    PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \
        --smoke --steps 20 --batch 4 --seq 128 --ckpt /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint import store
from repro.configs.base import ARCH_IDS, get_config, get_smoke
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models import lm
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.parallel.sharding import make_context
from repro.train.step import jit_train_step, train_shardings


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--mesh", default="", help="DATAxMODEL, e.g. 2x4")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args()

    compile_cache.enable()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.frontend != "none" or cfg.enc_layers:
        raise SystemExit(
            "train driver feeds token batches; use examples/het_train.py for "
            "frontend-stubbed archs"
        )
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_debug_mesh(d, m)
    ctx = make_context(mesh)
    opt_cfg = AdamWConfig(lr=args.lr)

    params, specs = lm.init(cfg, jax.random.key(args.seed))
    opt_state = adamw_init(params, opt_cfg)
    if mesh is not None:
        param_sh, opt_sh = train_shardings(cfg, ctx, opt_cfg)
        params = jax.device_put(params, param_sh)
        opt_state = jax.device_put(opt_state, opt_sh)

    data = SyntheticLM(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
                   seed=args.seed)
    )
    start = 0
    ckpt = store.AsyncCheckpointer(args.ckpt) if args.ckpt else None
    if args.ckpt and store.latest_step(args.ckpt) is not None:
        restored, start = store.restore(
            args.ckpt, {"params": params, "opt": opt_state}
        )
        params, opt_state = restored["params"], restored["opt"]
        print(f"resumed from step {start}")

    batch0 = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in data.batch_at(0).items()}
    step_fn = jit_train_step(
        cfg, ctx, opt_cfg, batch0,
        schedule={"warmup": 10, "total": max(args.steps, 20)}, donate=True,
    )
    for step in range(start, args.steps):
        t0 = time.time()
        batch = {k: jax.numpy.asarray(v) for k, v in data.batch_at(step).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % args.log_every == 0:
            loss = float(metrics["loss"])
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):7.3f} "
                  f"dt {time.time()-t0:6.2f}s")
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt_state})
    if ckpt:
        ckpt.save(args.steps, {"params": params, "opt": opt_state})
        ckpt.wait()
    print("done")


if __name__ == "__main__":
    main()
