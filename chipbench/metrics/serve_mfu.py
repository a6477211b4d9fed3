"""Model step (launch/serve.py decode, models/lm.py): model FLOPs of every
token the traced window processed (one decode launch each, prompt or
generated, at the mix's mean position), from the configuration's shapes,
over the traced window and the chip's bf16 peak, in %.  Decode launches
are found as for model_hbm_roofline."""

from chipbench.counts import lm_flops_per_token


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or "decode_module" not in ctx:
        return None
    _, n = tr.time_of_program(ctx["decode_module"], ctx["decode_programs"])
    flops = n * lm_flops_per_token(ctx["model"], ctx["mean_pos"])
    return 100.0 * flops / (tr.window_s * ctx["chips"] * ctx["peaks"]["bf16_flops"])
