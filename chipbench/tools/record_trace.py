"""Record the small TPU trace the trace reducer is tested on.

    python3 chipbench/tools/record_trace.py tests/chipbench/data

Three FD3D shots (64^3 cells, 20 steps, the compiled Pallas kernel) inside
a ``chipbench.window`` host span, the last after a 50 ms host sleep inside
a ``chipbench.idle_probe`` span, so the trace holds a known idle gap.  Writes
``trace_small.xplane.pb`` and ``trace_small.json`` (what was run) there.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

N, NT, SHOTS, SLEEP = 64, 20, 3, 0.05


def main(out: str) -> None:
    import jax
    import numpy as np

    from chipbench import trace_reduce
    from repro.seismic.model import make_demo_model, make_shot_grid, run_shot

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    model = make_demo_model(n=N)
    shot = make_shot_grid(model, 1)[0]
    src, rec = jax.numpy.asarray(shot.src), jax.numpy.asarray(shot.rec_array())
    run_shot(model, src, rec, nt=NT).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    time.sleep(0.5)  # the device tracer starts a little after the host's
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for i in range(SHOTS):
            if i == SHOTS - 1:
                with jax.profiler.TraceAnnotation("chipbench.idle_probe"):
                    time.sleep(SLEEP)
            with jax.profiler.TraceAnnotation("chipbench.shot_dispatch"):
                seis = run_shot(model, src, rec, nt=NT)
            with jax.profiler.TraceAnnotation("chipbench.seis_copy"):
                np.asarray(seis)
    jax.profiler.stop_trace()
    dst = Path(out)
    dst.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace_reduce.find_xplane(tmp), dst / "trace_small.xplane.pb")
    shutil.rmtree(tmp)
    s = trace_reduce.reduce_file(str(dst / "trace_small.xplane.pb"))
    meta = {"n": N, "nt": NT, "shots": SHOTS, "sleep_s": SLEEP, "device_kind": jax.devices()[0].device_kind,
            "window_s": s.window_s, "busy_s": s.busy_s, "module_n": s.module_n,
            "op_n": s.op_n, "gaps": s.gaps[:5]}
    (dst / "trace_small.json").write_text(json.dumps(meta, indent=1))
    print(json.dumps(meta))


if __name__ == "__main__":
    main(sys.argv[1])
