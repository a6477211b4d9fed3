"""Model step: each decode launch's necessary bytes (the weights read once,
the K/V cache read and written at the launch's position: chipbench/counts.py)
over those launches' device time and the chip's HBM bandwidth, in %.  The
decode programs are those named exactly as the served decode step; the
run fails if the trace holds none, or more than the cache lengths served."""

from chipbench.counts import lm_decode_bytes


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or "decode_module" not in ctx:
        return None
    secs, n = tr.time_of_program(ctx["decode_module"], ctx["decode_programs"])
    need = n * lm_decode_bytes(ctx["model"], ctx["mean_pos"])
    return 100.0 * need / (secs * ctx["peaks"]["hbm_bytes_per_s"])
