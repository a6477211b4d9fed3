"""Shot program (seismic/model.py run_shot): the FD3D steps' necessary
bytes (16 B per cell per step) of the traced survey over the device time of
the shot programs and the chip's HBM bandwidth, in %.  The whole step's
share: it still bounds a gain once the kernel is taken off the path."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not ctx.get("traced_bytes"):
        return None
    secs, n = tr.time_of("module", "run_shot")
    if not n or secs <= 0:
        return None
    return 100.0 * ctx["traced_bytes"] / (secs * ctx["peaks"]["hbm_bytes_per_s"])
