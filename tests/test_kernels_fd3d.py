"""Pallas FD3D kernel vs the pure-jnp oracle: shape/dtype/block sweeps in
interpret mode (the container is CPU; TPU is the target), each for the
plain leapfrog step (taper 1, no source) and for the step with a taper and
a source in the first, an interior or the last z-block, or at a block's
edge."""

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


def _plain(shape):
    """Taper and source of the plain leapfrog step: m = 1, amp = 0."""
    nz, ny, nx = shape
    return (jnp.ones(nz), jnp.ones((ny, nx)), jnp.zeros(3, jnp.int32),
            jnp.float32(0.0))


def _src_at(shape, bz, where):
    """A source in the first, an interior or the last z-block, or on the
    last plane of the first block at a corner of its plane."""
    nz, ny, nx = shape
    blocks = nz // bz
    z, y, x = {
        "first": (1, ny // 3, nx // 2),
        "interior": ((blocks // 2) * bz + bz // 2, ny // 2, nx // 3),
        "last": (nz - 2, ny - 3, 2),
        "edge": (bz - 1, ny - 1, 0),
    }[where]
    return jnp.array([z, y, x], jnp.int32)


def _fused(shape, bz, where, seed=1):
    """A taper in (0.5, 1] that varies along every axis, and a source."""
    nz, ny, nx = shape
    k1, k2 = jax.random.split(jax.random.key(seed))
    taper_z = jax.random.uniform(k1, (nz,), minval=0.5, maxval=1.0)
    taper_xy = jax.random.uniform(k2, (ny, nx), minval=0.5, maxval=1.0)
    return taper_z, taper_xy, _src_at(shape, bz, where), jnp.float32(3.0)


def _taper_src(case, shape, bz):
    return _plain(shape) if case == "plain" else _fused(shape, bz, case)


CASES = ["plain", "first", "interior", "last", "edge"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("shape,bz", [
    ((8, 16, 16), 8),
    ((16, 16, 16), 8),
    ((16, 24, 16), 4),     # bz smaller than a block row
    ((32, 16, 32), 16),    # multiple blocks, wide x
    ((8, 8, 8), 4),
])
def test_pallas_matches_ref_shapes(shape, bz, case):
    u, up, c2 = _fields(shape)
    extra = _taper_src(case, shape, bz)
    got = fd3d_pallas(u, up, c2, *extra, dx=10.0, bz=bz, interpret=True)
    want = ref_step(u, up, c2, *extra, 10.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ref_plain_case_is_the_leapfrog_step():
    """m = 1, amp = 0 is 2u - u_prev + c2dt2 lap(u), bit for bit."""
    shape = (8, 16, 16)
    u, up, c2 = _fields(shape)
    got = ref_step(u, up, c2, *_plain(shape), 10.0)
    want = 2.0 * u - up + c2 * laplacian(u, 10.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case", ["plain", "interior"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_dtypes(dtype, case):
    shape, bz = (8, 16, 16), 4
    u, up, c2 = _fields(shape, dtype)
    extra = _taper_src(case, shape, bz)
    got = fd3d_pallas(u, up, c2, *extra, dx=5.0, bz=bz, interpret=True)
    want = ref_step(u, up, c2, *extra, 5.0)
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
    shape = (12, 16, 16)
    u, up, c2 = _fields(shape)
    extra = _plain(shape)
    with pytest.raises(ValueError):  # 12 % 8 != 0
        fd3d_pallas(u, up, c2, *extra, dx=1.0, bz=8, interpret=True)
    with pytest.raises(ValueError):  # bz < HALO
        fd3d_pallas(u, up, c2, *extra, dx=1.0, bz=2, interpret=True)


@pytest.mark.parametrize("case", ["plain", "interior"])
def test_ops_backend_dispatch(case):
    shape = (8, 16, 16)
    u, up, c2 = _fields(shape)
    extra = _taper_src(case, shape, 8)
    a = fd3d_step(u, up, c2, *extra, dx=10.0, backend="ref")
    b = fd3d_step(u, up, c2, *extra, dx=10.0, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


def test_pallas_backend_refuses_off_tpu():
    """backend='pallas' is the compiled kernel only: off a TPU it raises
    instead of quietly running the interpreter."""
    if jax.default_backend() == "tpu":
        pytest.skip("runs the compiled kernel on a TPU")
    u, up, c2 = _fields((8, 16, 16))
    with pytest.raises(RuntimeError, match="needs a TPU"):
        fd3d_step(u, up, c2, *_plain(u.shape), dx=10.0, backend="pallas")
