"""The work an algorithm needs, counted from its shapes.

These are the numerators of every roofline and utilization share: the
algorithm's own bytes and operations, whatever implements it.  Halo
re-reads, padding copies and mask passes of an implementation are not
counted, so a change that removes them raises the share.
"""

from __future__ import annotations

__all__ = [
    "FD3D_BYTES_PER_CELL_STEP",
    "FD3D_FLOPS_PER_CELL_STEP",
    "fd3d_bytes",
    "fd3d_flops",
    "lm_weight_bytes",
    "lm_kv_bytes",
    "lm_decode_bytes",
    "lm_flops_per_token",
]

# u and u_prev and c^2 dt^2 read once, u_next written once, all float32.
FD3D_BYTES_PER_CELL_STEP = 4 * 4
# Radius-4 Laplacian: the centre term (1 mul) and 12 symmetric pairs
# (add the pair, multiply by the coefficient, accumulate: 3 each); the
# leapfrog update 2u - u_prev + c2dt2 * lap / dx^2 (5); the sponge on the
# new and the current field (2).
FD3D_FLOPS_PER_CELL_STEP = 1 + 12 * 3 + 5 + 2


def fd3d_bytes(cells: int, steps: int) -> int:
    return FD3D_BYTES_PER_CELL_STEP * cells * steps


def fd3d_flops(cells: int, steps: int) -> int:
    return FD3D_FLOPS_PER_CELL_STEP * cells * steps


def _layer_weights(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    q = d * c["num_attention_heads"] * hd
    kv = 2 * d * c["num_key_value_heads"] * hd
    o = c["num_attention_heads"] * hd * d
    mlp = 3 * d * c["intermediate_size"]
    return q + kv + o + mlp


def lm_weight_bytes(c: dict, bytes_per: int = 2) -> int:
    """Weights one decode step must read: every layer's matrices and norms,
    the final norm and the output head.  Of the embedding table only the
    looked-up row is needed."""
    d = c["hidden_size"]
    per_layer = _layer_weights(c) + 2 * d
    head = d * c["vocab_size"]
    return bytes_per * (c["num_hidden_layers"] * per_layer + d + head + d)


def lm_kv_bytes(c: dict, pos: int, bytes_per: int = 2) -> int:
    """K/V bytes a decode step at position ``pos`` must touch: the ``pos``
    cached entries read and the new one written, in every layer."""
    per_pos = 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per
    return c["num_hidden_layers"] * per_pos * (pos + 1)


def lm_decode_bytes(c: dict, pos: int) -> int:
    return lm_weight_bytes(c) + lm_kv_bytes(c, pos)


def lm_flops_per_token(c: dict, pos: int) -> int:
    """Model FLOPs to process one token at position ``pos`` (0-based):
    2 per weight of every matrix it multiplies (layers and head), and the
    attention scores and weighted values over the ``pos + 1`` visible
    positions (2 * 2 * heads * head_dim each)."""
    d, hd, h = c["hidden_size"], c["head_dim"], c["num_attention_heads"]
    mats = c["num_hidden_layers"] * _layer_weights(c) + d * c["vocab_size"]
    attn = c["num_hidden_layers"] * 4 * h * hd * (pos + 1)
    return 2 * mats + attn
