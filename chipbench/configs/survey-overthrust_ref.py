"""Plain reference for a survey-overthrust shot, in straightforward jnp.

3-D acoustic wave equation, second order in time and eighth order in
space (radius-4 Laplacian with zero values outside the grid), a Ricker
source added at the source point after each step, an exponential sponge
on five faces (z = 0 is a free surface) applied to the new and the
current field, and the new field recorded at the receivers:

    u_next = (2 u - u_prev + (v dt)^2 lap(u)) [+ w(t) (v dt)^2 at src]
    u_next *= mask;  u_prev' = u * mask;  seis[t] = u_next[receivers]

It imports nothing of the program under test.  ``dtype`` sets the
precision of every array and operation: float32 is the reference,
bfloat16 the control that must fail the comparison.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Eighth-order central second difference (Fornberg 1988).
CENTRE = -205.0 / 72.0
PAIRS = (8.0 / 5.0, -1.0 / 5.0, 8.0 / 315.0, -1.0 / 560.0)
R = len(PAIRS)


def ricker(f_peak, dt, nt):
    t = np.arange(nt) * dt - 1.0 / f_peak
    a = (np.pi * f_peak * t) ** 2
    return ((1.0 - 2.0 * a) * np.exp(-a)).astype(np.float32)


def sponge(shape, width, decay):
    def ramp(n, free_top):
        i = np.arange(n)
        dist = (n - 1 - i) if free_top else np.minimum(i, n - 1 - i)
        return np.where(dist < width, np.exp(-decay * (width - dist) ** 2), 1.0)

    nz, ny, nx = shape
    return (ramp(nz, True)[:, None, None] * ramp(ny, False)[None, :, None]
            * ramp(nx, False)[None, None, :]).astype(np.float32)


def laplacian(u, dx):
    n0, n1, n2 = u.shape
    p = jnp.pad(u, R)
    total = 3.0 * CENTRE * u
    for k, w in enumerate(PAIRS, start=1):
        total = total + w * (p[R - k:R - k + n0, R:R + n1, R:R + n2]
                             + p[R + k:R + k + n0, R:R + n1, R:R + n2])
        total = total + w * (p[R:R + n0, R - k:R - k + n1, R:R + n2]
                             + p[R:R + n0, R + k:R + k + n1, R:R + n2])
        total = total + w * (p[R:R + n0, R:R + n1, R - k:R - k + n2]
                             + p[R:R + n0, R:R + n1, R + k:R + k + n2])
    return total / (dx * dx)


@partial(jax.jit, static_argnames=("nt", "dx", "dt", "f_peak", "width",
                                   "decay", "dtype"))
def shot(vel, src, rec, *, nt, dx, dt, f_peak, width, decay,
         dtype=jnp.float32):
    """Seismogram ``(nt, n_rec)`` of one shot in the velocity block ``vel``
    (nz, ny, nx); ``src`` (3,) and ``rec`` (n_rec, 3) are grid indices."""
    with jax.default_matmul_precision("highest"):
        vel = vel.astype(dtype)
        c2 = (vel * jnp.asarray(dt, dtype)) ** 2
        mask = jnp.asarray(sponge(vel.shape, width, decay), dtype)
        wav = jnp.asarray(ricker(f_peak, dt, nt), dtype)
        src_c2 = c2[src[0], src[1], src[2]]

        def step(t, carry):
            u, u_prev, seis = carry
            nxt = 2.0 * u - u_prev + c2 * laplacian(u, dx).astype(dtype)
            nxt = nxt.at[src[0], src[1], src[2]].add(wav[t] * src_c2)
            nxt = nxt * mask
            seis = seis.at[t].set(nxt[rec[:, 0], rec[:, 1], rec[:, 2]].astype(jnp.float32))
            return nxt, u * mask, seis

        z = jnp.zeros(vel.shape, dtype)
        seis = jnp.zeros((nt, rec.shape[0]), jnp.float32)
        return jax.lax.fori_loop(0, nt, step, (z, z, seis))[2]
