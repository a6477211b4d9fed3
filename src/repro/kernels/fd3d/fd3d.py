"""Pallas TPU kernel for one time step of a seismic shot (paper Eq. 12).

The seismic shot (the paper's task payload, §3) spends its time in this
step, so it is the compute hot-spot that earns a kernel.  One call does the
whole step that ``seismic.model.run_shot`` runs ``nt`` times:

    out = (2 u - m v + c2dt2 lap(u) [+ amp at src]) * m

with ``v`` the previous field (undamped: the kernel applies the taper ``m``
to it), ``m = taper_z[z] * taper_xy[y, x]`` the separable sponge taper and
``amp`` the source term, added at ``src`` before the taper.  The plain
leapfrog step is the case ``m = 1``, ``amp = 0``.

TPU adaptation (vs. the CUDA shared-memory tiling a GPU paper would use):

* Blocks tile the LEADING (z) axis only; each block carries the full padded
  XY plane.  XY halos live in the array padding, so in-block x/y shifts are
  static slices on VMEM-resident data — the VPU's native access pattern
  (8x128 vector registers want contiguous trailing dims; NX should be a
  multiple of 128 lanes for full utilisation).
* Z halos come from ONE element-indexed view of the z/xy-padded wavefield:
  grid step ``i`` sees the ``bz + 2*HALO`` planes starting at element ``i*bz``
  (``pl.Element`` blocks may overlap, unlike plain blocked specs), so the
  kernel reads the centre block plus both halos in one DMA.  The halos are
  read again by the neighbouring blocks, and ``jnp.pad`` makes the padded
  copy in a pass of its own before the call.
* The taper adds no per-cell bytes: ``taper_xy`` is one (NY, NX) plane with
  a constant block index, fetched once per call, and ``taper_z`` and the
  source's index and amplitude are scalar-prefetch operands in SMEM.  Only
  the plane that holds ``src[0]`` pays for the source, under ``pl.when``.
* The output is aliased onto ``v``: block ``i`` of ``v`` is read before
  block ``i`` of the output is written, and the caller's leapfrog hands the
  previous field over to be overwritten, so no step allocates a field.
* The body walks the block one z-plane at a time (``fori_loop``), so the
  stencil's temporaries are single (NY, NX) planes, not (bz, NY, NX) slabs.
* VMEM, as the v5e compiler reports it (f32, smallest ``vmem_limit_bytes``
  that compiles, bz=8): 2.4 MiB at 128^3, 13.3 MiB at 192x256x256 and
  94.2 MiB at 512x512 planes.  Up to 256^2 planes the default 16 MiB scoped
  limit suffices; the kernel asks for ``VMEM_LIMIT`` so 512^2 planes
  compile at the default bz.  1024^2 planes need y-tiling (not implemented;
  the compiler refuses them).
* The stencil is VPU (element-wise) work, not MXU; arithmetic intensity is
  ~0.9 flop/byte, so the kernel is HBM-bound: per step it reads ``v``,
  ``c2dt2`` and the padded ``u`` (with its z halos) and writes ``out``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import C0, COEF, HALO

__all__ = ["fd3d_pallas"]

# Within v5e's 128 MiB of VMEM; fits 512x512 planes at bz=8.
VMEM_LIMIT = 100 * 1024 * 1024


def _kernel(src, amp, taper_z, v, col, c2dt2, taper_xy, out, *, bz, dx):
    """One z-block of the step (module docstring).

    ``src`` (3,) int32, ``amp`` (1,) and ``taper_z`` (NZ,) float32 are
    scalar-prefetch refs (SMEM holds 32-bit words).  ``col`` holds the
    block's ``bz`` centre planes plus ``HALO`` planes on each z side, all
    xy-padded; centre plane ``j`` is ``col[j + HALO]``.
    """
    inv_dx2 = 1.0 / (dx * dx)
    ny, nx = out.shape[1], out.shape[2]
    z0 = pl.program_id(0) * bz
    dtype = out.dtype

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
        m = taper_z[z0 + j].astype(dtype) * taper_xy[...]
        nxt = 2.0 * c - m * v[j] + c2dt2[j] * (lap * inv_dx2)
        out[j] = nxt * m

        @pl.when(z0 + j == src[0])
        def _():
            at = ((jax.lax.broadcasted_iota(jnp.int32, (ny, nx), 0) == src[1])
                  & (jax.lax.broadcasted_iota(jnp.int32, (ny, nx), 1) == src[2]))
            out[j] = jnp.where(at, nxt + amp[0].astype(dtype), nxt) * m

        return carry

    jax.lax.fori_loop(0, bz, body, 0)


@functools.partial(jax.jit, static_argnames=("dx", "bz", "interpret"))
def fd3d_pallas(
    u: jax.Array,
    v: jax.Array,
    c2dt2: jax.Array,
    taper_z: jax.Array,
    taper_xy: jax.Array,
    src: jax.Array,
    amp: jax.Array,
    *,
    dx: float,
    interpret: bool,
    bz: int = 8,
) -> jax.Array:
    """One step via pallas_call; the result takes ``v``'s buffer where ``v``
    is not needed afterwards.

    Shapes: ``u``, ``v``, ``c2dt2`` (NZ, NY, NX) with NZ % bz == 0;
    ``taper_z`` (NZ,); ``taper_xy`` (NY, NX); ``src`` (3,) int32; ``amp`` a
    scalar.  ``interpret=True`` executes the kernel body in Python (CPU
    tests); ``interpret=False`` compiles it for the TPU.  There is no
    default.
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

    # Index maps take the grid index, then the scalar-prefetch refs.
    col_spec = pl.BlockSpec(
        (pl.Element(bz + 2 * HALO), pl.Element(nyp), pl.Element(nxp)),
        lambda i, *_: (i * bz, 0, 0),
    )
    plain_spec = pl.BlockSpec((bz, ny, nx), lambda i, *_: (i, 0, 0))
    plane_spec = pl.BlockSpec((ny, nx), lambda i, *_: (0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, bz=bz, dx=dx),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nz // bz,),
            in_specs=[plain_spec, col_spec, plain_spec, plane_spec],
            out_specs=plain_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), u.dtype),
        input_output_aliases={3: 0},  # v (after the 3 scalar operands)
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(
        src.astype(jnp.int32),
        jnp.reshape(amp, (1,)).astype(jnp.float32),
        taper_z.astype(jnp.float32),
        v,
        up,
        c2dt2,
        taper_xy.astype(u.dtype),
    )
