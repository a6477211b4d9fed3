from .ops import chosen_experts, compact, default_backend
from .moe_decode import moe_decode_pallas
from . import ref

__all__ = ["chosen_experts", "compact", "default_backend", "moe_decode_pallas", "ref"]
