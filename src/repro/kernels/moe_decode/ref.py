"""Pure-jnp oracle for the chosen-expert decode kernel in ``moe_decode.py``.

Same operands and result: the ``n`` live slots' experts are gathered from
the ``(L, E, ...)`` stacks at ``layer``, each computes the SwiGLU of every
token row, and the rows are summed with the slots' weights in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_decode(layer, ids, n, wts, x, w1, w3, w2):
    """(T, d) in ``x``'s dtype; shapes as ``moe_decode_pallas`` takes them."""
    g1, g3, g2 = (w[layer][ids] for w in (w1, w3, w2))  # [S, ...]
    h = jnp.einsum("td,sdf->stf", x, g1, preferred_element_type=jnp.float32)
    u = jnp.einsum("td,sdf->stf", x, g3, preferred_element_type=jnp.float32)
    g = (jax.nn.silu(h) * u).astype(w2.dtype)
    y = jnp.einsum("stf,sfd->std", g, g2, preferred_element_type=jnp.float32)
    live = jnp.arange(ids.shape[0]) < n
    w = jnp.where(live[:, None], wts.astype(jnp.float32), 0.0)  # [S, T]
    return jnp.einsum("std,st->td", y, w).astype(x.dtype)
