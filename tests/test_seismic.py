"""Seismic modeling substrate (paper §3): physics sanity + A2WS shot driver."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.a2ws import A2WSRuntime
from repro.kernels.fd3d.ref import laplacian
from repro.seismic.model import (
    SeismicModel,
    make_demo_model,
    make_shot_grid,
    ricker,
    run_shot,
)


def test_ricker_wavelet_properties():
    w = np.asarray(ricker(10.0, 1e-3, 400))
    assert w.max() == pytest.approx(1.0, abs=1e-3)  # unit peak at t=1/f
    assert abs(w[0]) < 1e-2 and abs(w[-1]) < 1e-2  # compact support


def test_demo_model_cfl():
    m = make_demo_model(n=24)
    assert m.cfl_ok()


def test_shot_produces_signal_and_stays_finite():
    m = make_demo_model(n=24)
    shots = make_shot_grid(m, 1)
    seis = run_shot(m, jnp.asarray(shots[0].src), jnp.asarray(shots[0].rec_array()),
                    nt=120)
    s = np.asarray(seis)
    assert s.shape == (120, 8)
    assert np.isfinite(s).all()
    assert np.abs(s).max() > 1e-8  # the wave reached the receivers
    # energy arrives later at farther receivers (finite propagation speed)
    src_x = shots[0].src[2]
    rec_x = shots[0].rec_array()[:, 2]
    arrival = np.argmax(np.abs(s) > 1e-4 * np.abs(s).max(), axis=0)
    near = arrival[np.argmin(np.abs(rec_x - src_x))]
    far = arrival[np.argmax(np.abs(rec_x - src_x))]
    assert near <= far


def test_sponge_damps_boundary_energy():
    m = make_demo_model(n=24)
    shots = make_shot_grid(m, 1)
    seis = run_shot(m, jnp.asarray(shots[0].src),
                    jnp.asarray(shots[0].rec_array()), nt=400)
    s = np.asarray(seis)
    # late-time energy must not exceed the first-arrival energy (no
    # reflection blow-up from the absorbing boundaries)
    early = np.abs(s[:200]).max()
    late = np.abs(s[350:]).max()
    assert late < early


def test_a2ws_schedules_real_shots():
    """End-to-end §4-style mini-run: shots as A2WS tasks on 2 workers."""
    import threading

    m = make_demo_model(n=16)
    shots = make_shot_grid(m, 6)
    results = []
    lock = threading.Lock()

    def task_fn(wid, shot):
        seis = run_shot(m, jnp.asarray(shot.src), jnp.asarray(shot.rec_array()),
                        nt=40)
        with lock:
            results.append(np.asarray(seis))

    rt = A2WSRuntime(shots, 2, task_fn, seed=0)
    stats = rt.run()
    assert len(results) == 6
    assert sum(stats.per_worker_tasks) == 6
    assert all(np.isfinite(s).all() for s in results)


def _ramp(n, width, decay, free_top):
    i = jnp.arange(n)
    edge = (n - 1 - i) if free_top else jnp.minimum(i, n - 1 - i)
    return jnp.where(edge < width, jnp.exp(-decay * (width - edge) ** 2), 1.0)


def _shot_damped_carry(m, src, rec, nt):
    """``run_shot`` as formulated before the step took in the taper and the
    source: the plain leapfrog step, then the source added to its result,
    then the taper as a full mask, and the previous field carried after the
    taper."""
    vel = m.velocity
    nz, ny, nx = vel.shape
    c2dt2 = (vel * m.dt) ** 2
    mask = (_ramp(nz, m.sponge, m.sponge_decay, True)[:, None, None]
            * _ramp(ny, m.sponge, m.sponge_decay, False)[None, :, None]
            * _ramp(nx, m.sponge, m.sponge_decay, False)[None, None, :])
    wavelet = ricker(m.f_peak, m.dt, nt)
    u = u_prev = jnp.zeros_like(vel)
    seis = []
    for it in range(nt):
        u_next = 2.0 * u - u_prev + c2dt2 * laplacian(u, m.dx)
        u_next = u_next.at[src[0], src[1], src[2]].add(
            wavelet[it] * c2dt2[src[0], src[1], src[2]])
        u_next = u_next * mask
        u, u_prev = u_next, u * mask
        seis.append(u_next[rec[:, 0], rec[:, 1], rec[:, 2]])
    return np.asarray(jnp.stack(seis))


@pytest.mark.parametrize("nt", [7, 8])  # odd: the two-step loop's tail step
def test_run_shot_backends_match_the_damped_carry_formulation(nt):
    """The kernel and the jnp form of the fused step agree with each other
    and with the step-then-source-then-mask program, to float32 rounding."""
    vel = make_demo_model(n=16).velocity
    m = SeismicModel(velocity=vel, sponge=4, sponge_decay=0.05)
    src = np.array([2, 9, 3], np.int32)  # inside the x sponge
    rec = np.array([[2, 9, x] for x in range(1, 15, 2)]  # x sponges too
                   + [[12, 9, 5], [2, 13, 5]], np.int32)  # z and y sponges
    want = _shot_damped_carry(m, src, rec, nt)
    got = {b: np.asarray(run_shot(m, jnp.asarray(src), jnp.asarray(rec),
                                  nt=nt, backend=b))
           for b in ("ref", "pallas_interpret")}
    peak = np.abs(want).max()
    assert got["ref"].shape == want.shape == (nt, len(rec))
    assert peak > 0
    for b, seis in got.items():
        assert np.abs(seis - want).max() <= 1e-6 * peak, b
    assert np.abs(got["ref"] - got["pallas_interpret"]).max() <= 1e-6 * peak
