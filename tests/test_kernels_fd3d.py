"""Pallas FD3D kernel vs the pure-jnp oracle: shape/dtype/block sweeps in
interpret mode (the container is CPU; TPU is the target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fd3d import fd3d_step
from repro.kernels.fd3d.fd3d import fd3d_pallas
from repro.kernels.fd3d.ref import fd3d_step as ref_step, laplacian, HALO


def _fields(shape, dtype=jnp.float32, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    u = jax.random.normal(k1, shape, dtype)
    up = jax.random.normal(k2, shape, dtype)
    c2 = jnp.full(shape, 0.1, dtype)
    return u, up, c2


@pytest.mark.parametrize("shape,bz", [
    ((8, 16, 16), 8),
    ((16, 16, 16), 8),
    ((16, 24, 16), 4),     # bz smaller than a block row
    ((32, 16, 32), 16),    # multiple blocks, wide x
    ((8, 8, 8), 4),
])
def test_pallas_matches_ref_shapes(shape, bz):
    u, up, c2 = _fields(shape)
    got = fd3d_pallas(u, up, c2, dx=10.0, bz=bz, interpret=True)
    want = ref_step(u, up, c2, 10.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_dtypes(dtype):
    u, up, c2 = _fields((8, 16, 16), dtype)
    got = fd3d_pallas(u, up, c2, dx=5.0, bz=4, interpret=True)
    want = ref_step(u, up, c2, 5.0)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_laplacian_of_quadratic_is_constant():
    """lap(x^2 + y^2 + z^2) == 6 exactly for an 8th-order stencil."""
    n = 24
    ax = jnp.arange(n, dtype=jnp.float32)
    x, y, z = jnp.meshgrid(ax, ax, ax, indexing="ij")
    u = x * x + y * y + z * z
    lap = laplacian(u, dx=1.0)
    core = lap[HALO + 1 : -HALO - 1, HALO + 1 : -HALO - 1, HALO + 1 : -HALO - 1]
    np.testing.assert_allclose(np.asarray(core), 6.0, rtol=1e-3, atol=1e-3)


def test_invalid_blocks_raise():
    u, up, c2 = _fields((12, 16, 16))
    with pytest.raises(ValueError):
        fd3d_pallas(u, up, c2, dx=1.0, bz=8, interpret=True)  # 12 % 8 != 0
    with pytest.raises(ValueError):
        fd3d_pallas(u, up, c2, dx=1.0, bz=2, interpret=True)  # bz < HALO


def test_ops_backend_dispatch():
    u, up, c2 = _fields((8, 16, 16))
    a = fd3d_step(u, up, c2, dx=10.0, backend="ref")
    b = fd3d_step(u, up, c2, dx=10.0, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


def test_pallas_backend_refuses_off_tpu():
    """backend='pallas' is the compiled kernel only: off a TPU it raises
    instead of quietly running the interpreter."""
    if jax.default_backend() == "tpu":
        pytest.skip("runs the compiled kernel on a TPU")
    u, up, c2 = _fields((8, 16, 16))
    with pytest.raises(RuntimeError, match="needs a TPU"):
        fd3d_step(u, up, c2, dx=10.0, backend="pallas")
