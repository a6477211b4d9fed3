"""Model step, latent-attention MoE decoders (launch/serve.py decode,
models/lm.py): each decode launch's necessary bytes (the weights outside
the routed experts once, the routed experts a balanced router sends its
token to, the latent cache at the launch's position:
chipbench/counts_mla_moe.py) over those launches' device time and the
chip's HBM bandwidth, in %.  Decode launches are found as for
model_hbm_roofline."""

from chipbench.counts_mla_moe import mla_moe_decode_bytes


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or "decode_module" not in ctx:
        return None
    secs, n = tr.time_of_program(ctx["decode_module"], ctx["decode_programs"])
    need = n * mla_moe_decode_bytes(ctx["model"], ctx["mean_pos"])
    return 100.0 * need / (secs * ctx["peaks"]["hbm_bytes_per_s"])
