"""Dry-run + roofline record for the A2WS device scheduler itself — the cell
most representative of the paper's technique.

Lowers one jitted shard_map scheduler round (ring ppermutes + steal-rate +
request/grant all_to_all) for 256 workers on the production pod, records the
three roofline terms, and writes experiments/dryrun/a2ws-sched__round__16x16.json.

    REPRO_SCHED_VARIANT=baseline|packed python scripts/sched_cell.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=256"

import json
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.core import device_sched as ds
from repro.launch.cells import roofline_terms
from repro.launch.hlo_analysis import analyze_hlo

VARIANT = os.environ.get("REPRO_SCHED_VARIANT", "baseline")
P = 256
RADIUS = 51  # 20% of 256 (paper's operating point)
MAX_STEAL = 16
NUM_TASKS = 256 * 30


def main() -> None:
    mesh = jax.make_mesh((P,), ("workers",), axis_types=(AxisType.Auto,))
    speeds = jnp.concatenate(
        [jnp.full((P // 4,), s) for s in (24.0, 16.0, 4.0, 1.0)]
    )
    base, rem = divmod(NUM_TASKS, P)
    counts = jnp.array([base + (1 if i < rem else 0) for i in range(P)],
                       jnp.int32)
    state = ds.init_state(P, counts, speeds, RADIUS, capacity=NUM_TASKS)
    round_fn = ds.make_round_fn(mesh, "workers", RADIUS, MAX_STEAL,
                                packed=(VARIANT == "packed"))
    t0 = time.time()
    lowered = round_fn.lower(state)
    compiled = lowered.compile()
    dt = time.time() - t0
    costs = analyze_hlo(compiled.as_text())
    terms = roofline_terms(costs.flops, costs.bytes, costs.coll_bytes)
    mem = compiled.memory_analysis()
    rec = {
        "arch": "a2ws-sched",
        "shape": f"round_p{P}_r{RADIUS}",
        "kind": "sched",
        "variant": VARIANT,
        "chips": P,
        "mesh": "16x16",
        "status": "ok",
        "flops_per_device": costs.flops,
        "bytes_per_device": costs.bytes,
        "collective_bytes_per_device": costs.coll_bytes,
        "collectives": {k: int(v) for k, v in costs.coll.items()},
        **terms,
        "dominant": max(terms, key=terms.get),
        "live_bytes_per_device": int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
        ),
        "compile_s": round(dt, 2),
    }
    out = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun", f"a2ws-sched__round__16x16__{VARIANT}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    json.dump(rec, open(out, "w"), indent=1)
    print(json.dumps({k: rec[k] for k in (
        "variant", "t_compute", "t_memory", "t_collective", "dominant",
        "collective_bytes_per_device", "bytes_per_device", "compile_s")},
        indent=1))


if __name__ == "__main__":
    main()
