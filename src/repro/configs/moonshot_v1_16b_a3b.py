"""moonshot-v1-16b-a3b — Moonlight-16B-A3B, as published
[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json].

27L d_model=2048 16H vocab=163840, untied head, rms eps 1e-5, rope theta
50000, 8192 positions.  MLA with no query low rank (q from the hidden
state), kv rank 512, head dims 128 nope + 64 rope, v 128.  Layer 0 is a
dense SwiGLU of 11264 (``d_ff``); layers 1-26 are MoE: 64 routed experts of
1408, 6 per token, 2 shared experts.  Routing is DeepSeek-V3's noaux_tc with
one group: sigmoid scores, a correction bias that chooses the experts, the
chosen scores normalised and scaled by 2.446.  No MTP layers.  Rotary pairs
are halves of the rope dims (the published code interleaves them; loading
its weights would permute the rope columns).
"""

from repro.models.config import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11_264,
    vocab=163_840,
    head_dim=128,
    rope_theta=50_000.0,
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        d_expert=1408,
        num_shared=2,
        first_k_dense=1,
        scoring="sigmoid",
        routed_scale=2.446,
    ),
    mla=MLAConfig(
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_dim=128,
    ),
    tie_embeddings=False,
    norm_eps=1e-5,
    max_seq=8192,
)

SMOKE = ModelConfig(
    name="moonshot-smoke",
    family="moe",
    n_layers=3,  # 1 dense + 2 MoE
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    vocab=256,
    head_dim=16,
    moe=MoEConfig(
        num_experts=16,
        top_k=4,
        d_expert=32,
        num_shared=2,
        first_k_dense=1,
        scoring="sigmoid",
        routed_scale=2.446,
        capacity_factor=2.0,
    ),
    mla=MLAConfig(
        q_lora_rank=None, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_dim=16
    ),
    norm_eps=1e-5,
    max_seq=256,
    remat="none",
)
