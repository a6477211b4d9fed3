"""3-D acoustic seismic modeling — the paper's use case (§3).

A *shot* is one independent simulation: inject a Ricker source at a position
near the surface, propagate Eq. 12 for ``nt`` steps through the velocity
model, and record the pressure at receiver positions.  Shots are the
homogeneous tasks A2WS schedules.

A time step is the FD3D kernel (``repro.kernels.fd3d``): the stencil, the
source and a simple exponential sponge taper at the boundaries, in one
pass.  Everything is jittable; the shot loop is a ``lax.fori_loop`` so one
shot is a single XLA program.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fd3d import fd3d_step

__all__ = ["Shot", "SeismicModel", "ricker", "run_shot", "make_demo_model"]


def ricker(f_peak: float, dt: float, nt: int) -> jnp.ndarray:
    """Ricker wavelet source time function."""
    t = jnp.arange(nt) * dt - 1.0 / f_peak
    a = (jnp.pi * f_peak * t) ** 2
    return (1.0 - 2.0 * a) * jnp.exp(-a)


@dataclass(frozen=True)
class Shot:
    """One seismic experiment: source position + receiver line."""

    src: tuple[int, int, int]
    receivers: tuple[tuple[int, int, int], ...]

    def rec_array(self) -> np.ndarray:
        return np.asarray(self.receivers, dtype=np.int32)


@dataclass(frozen=True)
class SeismicModel:
    """Discretised velocity model + solver settings."""

    velocity: jnp.ndarray  # (NZ, NY, NX) m/s
    dx: float = 10.0  # m
    dt: float = 1e-3  # s  (must satisfy CFL: dt < 0.4 dx / vmax)
    f_peak: float = 12.0  # Hz
    sponge: int = 8
    sponge_decay: float = 0.012

    def cfl_ok(self) -> bool:
        vmax = float(jnp.max(self.velocity))
        return self.dt <= 0.5 * self.dx / (vmax * np.sqrt(3.0) / 2.0)


def _sponge_taper(
    shape: tuple[int, int, int], width: int, decay: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exponential absorbing taper near five faces (z=0 is the free surface,
    where sources and receivers live), as its separable factors: ``(taper_z
    (NZ,), taper_xy (NY, NX))``, the taper being ``taper_z[z] *
    taper_xy[y, x]``."""
    ramps = []
    for axis, n in enumerate(shape):
        idx = jnp.arange(n)
        if axis == 0:  # free surface at z=0: only absorb at the bottom
            edge = n - 1 - idx
        else:
            edge = jnp.minimum(idx, n - 1 - idx)
        ramps.append(
            jnp.where(edge < width, jnp.exp(-decay * (width - edge) ** 2), 1.0)
        )
    mz, my, mx = ramps
    return mz, my[:, None] * mx[None, :]


@partial(jax.jit, static_argnames=("nt", "backend"))
def run_shot(
    model: SeismicModel,
    src: jnp.ndarray,  # (3,) int32
    receivers: jnp.ndarray,  # (n_rec, 3) int32
    nt: int,
    backend: str | None = None,
) -> jnp.ndarray:
    """Propagate one shot; returns the (nt, n_rec) seismogram.

    Each time step is one ``fd3d_step``: the stencil, the source and the
    sponge taper in one pass.  The carry is the current field and the
    previous one before the taper, which the step applies to it.
    """
    vel = model.velocity
    c2dt2 = (vel * model.dt) ** 2
    taper_z, taper_xy = _sponge_taper(vel.shape, model.sponge,
                                      model.sponge_decay)
    amp = ricker(model.f_peak, model.dt, nt) * c2dt2[src[0], src[1], src[2]]
    # Built behind a barrier, so that XLA keeps the zeros out of the loop
    # body instead of selecting them there on every step.
    u, u_prev = jax.lax.optimization_barrier(
        (jnp.zeros_like(vel), jnp.zeros_like(vel)))
    seis = jnp.zeros((nt, receivers.shape[0]), vel.dtype)

    def step(it, u, u_prev, seis):
        u_next = fd3d_step(u, u_prev, c2dt2, taper_z, taper_xy, src, amp[it],
                           dx=model.dx, backend=backend)
        rec = u_next[receivers[:, 0], receivers[:, 1], receivers[:, 2]]
        return u_next, seis.at[it].set(rec)

    def body(i, carry):
        # Two steps, so each field comes back to its own slot of the carry:
        # the step's result may take its previous field's buffer, and the
        # pair swaps twice.  One step a body would copy a field per step.
        u, u_prev, seis = carry
        w, seis = step(2 * i, u, u_prev, seis)
        u_next, seis = step(2 * i + 1, w, u, seis)
        return u_next, w, seis

    u, u_prev, seis = jax.lax.fori_loop(0, nt // 2, body, (u, u_prev, seis))
    if nt % 2:
        _, seis = step(nt - 1, u, u_prev, seis)
    return seis


jax.tree_util.register_pytree_node(
    SeismicModel,
    lambda m: ((m.velocity,), (m.dx, m.dt, m.f_peak, m.sponge, m.sponge_decay)),
    lambda aux, kids: SeismicModel(kids[0], *aux),
)


def make_demo_model(
    n: int = 48, dx: float = 10.0, dt: float = 1e-3, layers: int = 3
) -> SeismicModel:
    """Small layered-earth model for tests/examples."""
    z = np.linspace(0, 1, n)[:, None, None]
    vel = 1500.0 + 1000.0 * np.floor(z * layers)
    vel = np.broadcast_to(vel, (n, n, n)).astype(np.float32)
    return SeismicModel(velocity=jnp.asarray(vel), dx=dx, dt=dt)


def make_shot_grid(
    model: SeismicModel, num_shots: int, depth: int = 2, n_rec: int = 8
) -> list[Shot]:
    """A line of shots across the surface with a fixed receiver line."""
    nz, ny, nx = model.velocity.shape
    xs = np.linspace(6, nx - 7, num_shots).astype(int)
    rec_y = ny // 2
    recs = tuple(
        (depth, rec_y, int(x)) for x in np.linspace(4, nx - 5, n_rec).astype(int)
    )
    return [Shot(src=(depth, rec_y, int(x)), receivers=recs) for x in xs]
