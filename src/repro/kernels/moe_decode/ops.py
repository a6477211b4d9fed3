"""Public op for the routed experts of a decode step: the experts its
tokens chose, read from the whole stacks by layer and expert index.

``chosen_experts(x, top_i, top_w, w1, w3, w2, layer, lo=...)`` compacts the
tokens' choices to the held experts ``[lo, lo + E)`` and runs one of:

* ``"pallas"``: the compiled TPU kernel; raises off a TPU.
* ``"pallas_interpret"``: the same kernel body in the Pallas interpreter,
  only on explicit request (the CPU tests).
* ``"ref"``: the pure-jnp oracle.

``backend=None`` takes ``default_backend()``: "pallas" on a TPU, "ref"
elsewhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .moe_decode import moe_decode_pallas

__all__ = ["chosen_experts", "compact", "default_backend"]


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def compact(top_i: jax.Array, top_w: jax.Array, lo: int, held: int):
    """The held experts that the T tokens chose, as the kernel's slots.

    ``top_i``, ``top_w`` [T, k].  Returns ``ids`` [T * k] int32 (the
    ``n`` chosen held experts in increasing order, then the last of them
    repeated; expert 0 when none is chosen), ``n`` and ``wts`` [T * k, T]
    float32, slot ``j``'s weight for each token (0 where it chose
    otherwise)."""
    t, k = top_i.shape
    hit = jax.nn.one_hot(top_i - lo, held, dtype=jnp.float32)  # [T, k, E]
    weight = jnp.einsum("tke,tk->et", hit, top_w.astype(jnp.float32))
    present = hit.sum((0, 1)) > 0
    n = present.sum(dtype=jnp.int32)
    order = jnp.argsort(~present, stable=True).astype(jnp.int32)
    slot = jnp.minimum(jnp.arange(t * k), jnp.maximum(n - 1, 0))
    ids = order[slot]
    return ids, n, weight[ids]


def chosen_experts(x, top_i, top_w, w1, w3, w2, layer, *, lo: int,
                   backend: str | None = None):
    """Sum over each token's chosen held experts of its weight times the
    expert's SwiGLU.  ``x`` [T, d]; ``top_i``, ``top_w`` [T, k]; ``w1``,
    ``w3`` [L, E, d, f]; ``w2`` [L, E, f, d]; ``layer`` an int32 scalar.
    Needs ``T * k <= E``.  Returns [T, d] in ``x``'s dtype."""
    backend = backend or default_backend()
    args = (layer, *compact(top_i, top_w, lo, w1.shape[1]), x, w1, w3, w2)
    if backend == "ref":
        return ref.moe_decode(*args)
    if backend == "pallas":
        if jax.default_backend() != "tpu":
            raise RuntimeError(
                "backend='pallas' needs a TPU (found "
                f"{jax.default_backend()!r}); use 'pallas_interpret' to run "
                "the kernel body in the interpreter"
            )
        return moe_decode_pallas(*args, interpret=False)
    if backend == "pallas_interpret":
        return moe_decode_pallas(*args, interpret=True)
    raise ValueError(f"unknown backend {backend!r}")
