"""Pure-jnp oracle for one time step of a seismic shot (paper Eq. 12).

Second-order in time, 8th-order in space, with the sponge taper ``m`` and
the source term of ``seismic.model.run_shot`` folded in:

    u_next = (2 u - m u_prev + (c dt)^2 * lap(u) [+ amp at src]) * m

``lap`` is the 7-point-per-axis (radius-4) Laplacian and ``m`` the separable
taper ``taper_z[z] * taper_xy[y, x]``; ``m = 1``, ``amp = 0`` is the plain
leapfrog step.  This module is the correctness reference for the Pallas
kernel in ``fd3d.py``; it is also fast enough on CPU for the small shots
used in tests/examples.
"""

from __future__ import annotations

import jax.numpy as jnp

# 8th-order central second-derivative coefficients (Fornberg).
C0 = -205.0 / 72.0
COEF = (8.0 / 5.0, -1.0 / 5.0, 8.0 / 315.0, -1.0 / 560.0)
HALO = 4


def laplacian(u: jnp.ndarray, dx: float) -> jnp.ndarray:
    """Radius-4 Laplacian with zero (Dirichlet) boundaries, same shape."""
    up = jnp.pad(u, HALO)
    out = 3.0 * C0 * u
    for axis in range(3):
        for k, c in enumerate(COEF, start=1):
            lo = [slice(HALO, -HALO)] * 3
            hi = [slice(HALO, -HALO)] * 3
            lo[axis] = slice(HALO - k, up.shape[axis] - HALO - k)
            hi[axis] = slice(HALO + k, up.shape[axis] - HALO + k)
            out = out + c * (up[tuple(lo)] + up[tuple(hi)])
    return out / (dx * dx)


def fd3d_step(
    u: jnp.ndarray,
    u_prev: jnp.ndarray,
    c2dt2: jnp.ndarray,
    taper_z: jnp.ndarray,
    taper_xy: jnp.ndarray,
    src: jnp.ndarray,
    amp: jnp.ndarray,
    dx: float,
) -> jnp.ndarray:
    """One leapfrog time step of Eq. 12 with the taper and the source."""
    m = (taper_z[:, None, None] * taper_xy[None]).astype(u.dtype)
    nxt = 2.0 * u - m * u_prev + c2dt2 * laplacian(u, dx)
    nxt = nxt.at[src[0], src[1], src[2]].add(jnp.asarray(amp, u.dtype))
    return nxt * m
