"""Shot program, whole step: the FD3D stencil's operations (44 per cell per
step, chipbench/counts.py) of the traced survey over the traced window,
the chips and their bf16 peak, in %.  The stencil runs on the vector unit
in float32, so this stays far under 100; it bounds any kernel share."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not ctx.get("traced_flops"):
        return None
    return 100.0 * ctx["traced_flops"] / (
        tr.window_s * ctx["chips"] * ctx["peaks"]["bf16_flops"])
