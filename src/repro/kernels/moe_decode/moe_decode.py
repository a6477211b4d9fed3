"""Pallas TPU kernel: the routed experts of a decode step, reading only the
held experts its tokens chose.

A decode launch holds a few tokens, and each chooses ``top_k`` experts, so
of the ``E`` held experts of a layer only a few are needed.  Computing all
of them (the select-compute-scatter of ``models/moe.py::moe_apply``) reads
every held expert's weights; this kernel streams only the chosen ones.

* The weights stay whole: ``w1``/``w3`` are the ``(L, E, d, f)`` stacks of
  every layer and ``w2`` the ``(L, E, f, d)`` one, indexed by the layer
  (a scalar-prefetch operand) and by each slot's expert id.  A caller
  that scans layers hands the stacks over as they are, so no per-layer
  slice of the experts is ever copied.
* The grid walks the slots.  Slot ``j`` holds one chosen expert, ``ids[j]``,
  and its weight for every token row, ``wts[j * T + t]`` (0 where the
  token did not choose it).  Only the first ``n`` slots are live; the
  slots past them repeat the last live id, so the pipeline, which fetches
  a block only when its index changes, starts no copy for them, and
  ``pl.when(j < n)`` skips their compute.  With ``n = 0`` the grid still
  fetches one expert (the first slot's), and adds nothing.
* Blocks are whole experts (``d x f`` and ``f x d``): one long DMA each,
  three a slot, double-buffered (34.6 MB at Moonlight's 2048 x 1408 in
  bf16), within ``VMEM_LIMIT``.
* Each slot computes ``silu(x w1) * (x w3)`` for every token row in f32,
  rounds it to the weights' dtype for the MXU, multiplies by ``w2`` with an
  f32 result, and adds it, weighted per row, into an f32 accumulator; the
  output is cast once, after the last slot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_decode_pallas"]

# Within v5e's 128 MiB of VMEM; two buffers of three whole experts at
# Moonlight's widths take 34.6 MB.
VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(layer, ids, n, wts, x, w1, w3, w2, out, acc, *, rows):
    """One slot (module docstring).  ``layer`` (1,), ``ids`` (S,) and ``n``
    (1,) int32 and ``wts`` (S * rows,) float32 are scalar-prefetch refs."""
    del layer
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(j < n[0])
    def _():
        xv = x[...]
        h = jnp.dot(xv, w1[...], preferred_element_type=jnp.float32)
        u = jnp.dot(xv, w3[...], preferred_element_type=jnp.float32)
        g = (jax.nn.silu(h) * u).astype(w2.dtype)
        y = jnp.dot(g, w2[...], preferred_element_type=jnp.float32)
        for t in range(rows):
            acc[t : t + 1, :] += wts[j * rows + t] * y[t : t + 1, :]

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        out[...] = acc[...].astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_decode_pallas(
    layer: jax.Array,
    ids: jax.Array,
    n: jax.Array,
    wts: jax.Array,
    x: jax.Array,
    w1: jax.Array,
    w3: jax.Array,
    w2: jax.Array,
    *,
    interpret: bool,
) -> jax.Array:
    """The chosen experts' weighted sum for the ``T`` rows of ``x``.

    Shapes: ``layer`` and ``n`` int32 scalars; ``ids`` (S,) int32, slot
    ``j >= n`` repeating ``ids[n - 1]``; ``wts`` (S, T); ``x`` (T, d);
    ``w1``, ``w3`` (L, E, d, f); ``w2`` (L, E, f, d).  Returns (T, d) in
    ``x``'s dtype.  ``interpret=True`` runs the body in the Pallas
    interpreter (CPU tests); there is no default.
    """
    rows, d = x.shape
    f = w1.shape[-1]
    slots = ids.shape[0]

    def expert(j, layer, ids, *_):
        return layer[0], ids[j], 0, 0

    whole = lambda j, *_: (0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slots,),
            in_specs=[
                pl.BlockSpec((rows, d), whole),
                pl.BlockSpec((None, None, d, f), expert),
                pl.BlockSpec((None, None, d, f), expert),
                pl.BlockSpec((None, None, f, d), expert),
            ],
            out_specs=pl.BlockSpec((rows, d), whole),
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT
        ),
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        ids.astype(jnp.int32),
        jnp.reshape(n, (1,)).astype(jnp.int32),
        wts.astype(jnp.float32).reshape(-1),
        x,
        w1,
        w3,
        w2,
    )
