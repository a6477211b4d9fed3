"""moonshot-v1-16b-a3b-ep4 — one chip's share of Moonlight-16B-A3B served on
a four-chip host with the experts split four ways (EP4): experts 0-15 of
each MoE layer, at full depth, with attention, the dense layer, the shared
experts, the router (all 64 outputs) and the head replicated on every chip.
The layer computes the part of its result that its 16 experts give; the
exchange with the other chips is not part of this program.  Not in
``ARCH_IDS``: it is a deployment of the registered model, not another one.
"""

from dataclasses import replace

from repro.configs import moonshot_v1_16b_a3b as published

EXPERTS_HELD = 16  # of 64: one of four chips

CONFIG = published.CONFIG.with_(
    name="moonshot-v1-16b-a3b-ep4",
    moe=replace(published.CONFIG.moe, experts_held=EXPERTS_HELD, expert_offset=0),
)

SMOKE = published.SMOKE.with_(
    name="moonshot-smoke-ep4",
    moe=replace(published.SMOKE.moe, experts_held=published.SMOKE.moe.num_experts // 4),
)
