"""Public op for one FD3D time step: picks Pallas or the jnp oracle.

``fd3d_step(u, u_prev, c2dt2, taper_z, taper_xy, src, amp, dx)`` is what the
seismic substrate calls (the step is in ``fd3d.py``'s docstring).  Backends:

* ``"pallas"``: the compiled TPU kernel; raises off a TPU.
* ``"pallas_interpret"``: the same kernel body run by the Pallas
  interpreter — only ever on explicit request (the CPU tests).
* ``"ref"``: the pure-jnp oracle.

``backend=None`` takes ``default_backend()``: "pallas" on a TPU, "ref"
elsewhere.
"""

from __future__ import annotations

import functools

import jax

from . import ref
from .fd3d import fd3d_pallas

__all__ = ["fd3d_step", "default_backend"]


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@functools.partial(jax.jit, static_argnames=("dx", "backend", "bz"))
def fd3d_step(
    u: jax.Array,
    u_prev: jax.Array,
    c2dt2: jax.Array,
    taper_z: jax.Array,
    taper_xy: jax.Array,
    src: jax.Array,
    amp: jax.Array,
    *,
    dx: float,
    backend: str | None = None,
    bz: int = 8,
) -> jax.Array:
    backend = backend or default_backend()
    args = (u, u_prev, c2dt2, taper_z, taper_xy, src, amp)
    if backend == "ref":
        return ref.fd3d_step(*args, dx)
    if backend == "pallas":
        if jax.default_backend() != "tpu":
            raise RuntimeError(
                "backend='pallas' needs a TPU (found "
                f"{jax.default_backend()!r}); use 'pallas_interpret' to run "
                "the kernel body in the interpreter"
            )
        return fd3d_pallas(*args, dx=dx, bz=bz, interpret=False)
    if backend == "pallas_interpret":
        return fd3d_pallas(*args, dx=dx, bz=bz, interpret=True)
    raise ValueError(f"unknown backend {backend!r}")
