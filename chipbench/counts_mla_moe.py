"""The work a decode launch of a latent-attention, mixture-of-experts decoder
(DeepSeek-V3's block, as Moonlight-16B-A3B publishes it) needs, counted
from the shapes of a configuration file.

One launch processes one token at position ``pos`` (0-based).  Of the
routed experts it needs those its token chooses among the experts this
chip holds: ``num_experts_per_tok * held / routed`` of them per MoE layer
(1.5 for 6 of 64 with 16 held), the load a balanced router gives, which is
what noaux_tc routing's correction bias is built to keep.  The latent cache
holds ``kv_lora_rank + qk_rope_head_dim`` values per position and layer.
Weights are bfloat16 except the router (float32).
"""

from __future__ import annotations

__all__ = ["routed_per_layer", "mla_moe_decode_bytes", "mla_moe_flops_per_token"]

BF16, F32 = 2, 4


def routed_per_layer(c: dict) -> float:
    """Held experts a token reaches per MoE layer, with balanced routing."""
    routed = c["deployment"]["n_routed_experts_published"]
    return c["num_experts_per_tok"] * c["n_routed_experts"] / routed


def _attn_weights(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                        c["v_head_dim"], c["kv_lora_rank"])
    return (d * h * (nope + rope)  # q, straight from the hidden state
            + d * (r + rope)  # latent and shared rope key
            + r * h * nope + r * h * v  # the latent's up-projections
            + h * v * d)  # output


def _mats(c: dict) -> tuple[float, int]:
    """(bf16 matrix weights a token multiplies, float32 router weights)."""
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    moe = layers - dense
    expert = 3 * d * c["moe_intermediate_size"]
    bf16 = (layers * _attn_weights(c)
            + dense * 3 * d * c["intermediate_size"]
            + moe * (c["n_shared_experts"] * expert + routed_per_layer(c) * expert)
            + d * c["vocab_size"])
    return bf16, moe * d * c["deployment"]["n_routed_experts_published"]


def mla_moe_decode_bytes(c: dict, pos: float) -> float:
    """Bytes one decode launch at ``pos`` must move: every weight outside the
    routed experts once (the router in float32, with its bias), the routed
    experts the token reaches, the norms and one embedding row; the latent
    cache's ``pos + 1`` positions read and one written, in every layer."""
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    moe = layers - c["first_k_dense_replace"]
    bf16, router = _mats(c)
    norms = layers * (2 * d + c["kv_lora_rank"]) + d
    weights = BF16 * (bf16 + norms + d) + F32 * (router + moe * c["deployment"][
        "n_routed_experts_published"])
    latent = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return weights + layers * latent * BF16 * (pos + 2)


def mla_moe_flops_per_token(c: dict, pos: float) -> float:
    """Model FLOPs to process one token at ``pos``: 2 per weight multiplied
    (as counted for the bytes, the router included), and attention over the
    ``pos + 1`` cached positions in the absorbed form: scores against the
    latent and the rope key, 2 H (r + rope), and the latent-weighted sum,
    2 H r, per position and layer."""
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    bf16, router = _mats(c)
    attn = 2 * h * (r + c["qk_rope_head_dim"]) + 2 * h * r
    return 2 * (bf16 + router) + c["num_hidden_layers"] * attn * (pos + 1)
