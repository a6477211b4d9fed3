"""Pallas TPU kernel for the fused 3-D acoustic FD time step.

The seismic shot (the paper's task payload, §3) spends its time in the
wave-equation stencil, so this is the compute hot-spot that earns a kernel.

TPU adaptation (vs. the CUDA shared-memory tiling a GPU paper would use):

* Blocks tile the LEADING (z) axis only; each block carries the full padded
  XY plane.  XY halos live in the array padding, so in-block x/y shifts are
  static slices on VMEM-resident data — the VPU's native access pattern
  (8x128 vector registers want contiguous trailing dims; NX should be a
  multiple of 128 lanes for full utilisation).
* Z halos come from ONE element-indexed view of the z/xy-padded wavefield:
  grid step ``i`` sees the ``bz + 2*HALO`` planes starting at element ``i*bz``
  (``pl.Element`` blocks may overlap, unlike plain blocked specs), so the
  kernel reads the centre block plus both halos in one DMA.
* The body walks the block one z-plane at a time (``fori_loop``), so the
  stencil's temporaries are single (NY, NX) planes, not (bz, NY, NX) slabs.
* VMEM, as the v5e compiler reports it (f32, smallest ``vmem_limit_bytes``
  that compiles): 1.6 MiB at 128^3 and 13.2 MiB at 256^3 with bz=8; 93.3 MiB
  at 512x512 planes with bz=8 and 28.6 MiB with bz=4.  Up to 256^2 planes
  the default 16 MiB scoped limit suffices; the kernel asks for
  ``VMEM_LIMIT`` so 512^2 planes compile at the default bz.  1024^2 planes
  need y-tiling (not implemented; the compiler refuses them).
* The stencil is VPU (element-wise) work, not MXU; arithmetic intensity is
  ~0.9 flop/byte so the kernel is HBM-bound and the win comes from fusing the
  whole leapfrog update (2u - u_prev + c2dt2 * lap) into ONE pass over HBM
  instead of the ~7 passes an unfused jnp implementation issues.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import C0, COEF, HALO

__all__ = ["fd3d_pallas"]

# Within v5e's 128 MiB of VMEM; fits 512x512 planes at bz=8 (93.3 MiB).
VMEM_LIMIT = 100 * 1024 * 1024


def _kernel(u_prev, col, c2dt2, out, *, bz, dx):
    """out = 2u - u_prev + c2dt2 * lap(u) on one z-block.

    ``col`` holds the block's ``bz`` centre planes plus ``HALO`` planes on
    each z side, all xy-padded; centre plane ``j`` is ``col[j + HALO]``.
    """
    inv_dx2 = 1.0 / (dx * dx)
    ny, nx = out.shape[1], out.shape[2]

    def xy(plane, dy=0, dxs=0):  # interior of a padded plane, shifted
        return plane[HALO + dy : HALO + dy + ny, HALO + dxs : HALO + dxs + nx]

    def body(j, carry):
        p = col[j + HALO]
        c = xy(p)
        lap = 3.0 * C0 * c
        for k, w in enumerate(COEF, start=1):
            lap = lap + w * (xy(col[j + HALO - k]) + xy(col[j + HALO + k]))
            lap = lap + w * (xy(p, dy=-k) + xy(p, dy=k))
            lap = lap + w * (xy(p, dxs=-k) + xy(p, dxs=k))
        out[j] = 2.0 * c - u_prev[j] + c2dt2[j] * (lap * inv_dx2)
        return carry

    jax.lax.fori_loop(0, bz, body, 0)


@functools.partial(jax.jit, static_argnames=("dx", "bz", "interpret"))
def fd3d_pallas(
    u: jax.Array,
    u_prev: jax.Array,
    c2dt2: jax.Array,
    *,
    dx: float,
    interpret: bool,
    bz: int = 8,
) -> jax.Array:
    """Fused FD step via pallas_call.  Shapes (NZ, NY, NX); NZ % bz == 0.

    ``interpret=True`` executes the kernel body in Python (CPU tests);
    ``interpret=False`` compiles it for the TPU.  There is no default.
    """
    nz, ny, nx = u.shape
    if nz % bz != 0:
        raise ValueError(f"NZ={nz} must be a multiple of bz={bz}")
    if bz < HALO:
        raise ValueError(f"bz={bz} must be >= HALO={HALO}")
    # HALO zeros on every face: Dirichlet boundaries, and the z halo of the
    # first/last block.
    up = jnp.pad(u, HALO)
    nyp, nxp = ny + 2 * HALO, nx + 2 * HALO

    col_spec = pl.BlockSpec(
        (pl.Element(bz + 2 * HALO), pl.Element(nyp), pl.Element(nxp)),
        lambda i: (i * bz, 0, 0),
    )
    plain_spec = pl.BlockSpec((bz, ny, nx), lambda i: (i, 0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, bz=bz, dx=dx),
        grid=(nz // bz,),
        in_specs=[plain_spec, col_spec, plain_spec],
        out_specs=plain_spec,
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), u.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(u_prev, up, c2dt2)
