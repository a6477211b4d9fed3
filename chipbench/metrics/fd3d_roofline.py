"""Kernel (kernels/fd3d, Pallas): the FD3D steps' necessary bytes (16 B per
cell per step) of the traced survey over the device time of the kernel's
events and the chip's HBM bandwidth, in %.  The kernel's events are the
Mosaic custom calls of the traced window's shot programs."""

KERNEL = "fd3d"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not ctx.get("traced_bytes"):
        return None
    secs, n = tr.time_of("op", KERNEL)
    if not n or secs <= 0:
        return None
    return 100.0 * ctx["traced_bytes"] / (secs * ctx["peaks"]["hbm_bytes_per_s"])
