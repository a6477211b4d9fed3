"""Model step, latent-attention MoE decoders: model FLOPs of every token the
traced window processed (one decode launch each, prompt or generated, at
the mix's mean position; chipbench/counts_mla_moe.py), over the traced
window and the chip's bf16 peak, in %.  Decode launches are found as for
model_hbm_roofline."""

from chipbench.counts_mla_moe import mla_moe_flops_per_token


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or "decode_module" not in ctx:
        return None
    _, n = tr.time_of_program(ctx["decode_module"], ctx["decode_programs"])
    flops = n * mla_moe_flops_per_token(ctx["model"], ctx["mean_pos"])
    return 100.0 * flops / (tr.window_s * ctx["chips"] * ctx["peaks"]["bf16_flops"])
