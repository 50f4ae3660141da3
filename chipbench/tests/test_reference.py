"""The plain references against the program's own outputs, on the CPU at
small sizes, and the AT adjoint against finite differences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import at_wave

GEO = dict(nx=32, ny=12, nz=12, nt=80, dx=100.0, dt=0.008, c0=3000.0,
           f0=4.0, n_receivers=16, lr=0.4)
ANOMALY = [(12.0, 6.0, 6.0, 2.2, 230.0)]


def _program(geo):
    from repro.apps.adjoint_tomography import ATConfig
    return ATConfig(**geo)


def test_at_forward_matches_program():
    from repro.apps.adjoint_tomography import simulate
    c = jnp.asarray(at_wave.target_model(GEO, ANOMALY))
    got = simulate(c, _program(GEO))
    ref = at_wave.forward(c, GEO)
    assert float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))) < 1e-5


def test_at_gradient_matches_program():
    # in float64, so that the comparison sees the mathematics and not the
    # round-off of a receiver that sits on the source cell
    from repro.apps.adjoint_tomography import step_kernel
    with jax.enable_x64(True):
        f64 = jnp.float64
        obs = at_wave.forward(jnp.asarray(
            at_wave.target_model(GEO, ANOMALY), f64), GEO, f64)
        c = jnp.asarray(at_wave.target_model(
            GEO, [(20.0, 6.0, 5.0, 2.0, -150)]), f64)
        got = step_kernel(_program(GEO))(c, obs)["grad"]
        chi, ref = at_wave.gradient(c, obs, GEO, f64)
        assert got.dtype == f64
        assert float(jnp.max(jnp.abs(got - ref))
                     / jnp.max(jnp.abs(ref))) < 1e-9


def test_at_adjoint_matches_finite_differences():
    geo = dict(GEO, nx=16, ny=8, nz=8, nt=40)
    with jax.enable_x64(True):
        f64 = jnp.float64
        obs = at_wave.forward(jnp.asarray(at_wave.target_model(
            geo, [(6.0, 4.0, 4.0, 1.5, 200.0)]), f64), geo, f64)
        c = jnp.full((16, 8, 8), 3000.0, f64)
        _, grad = at_wave.gradient(c, obs, geo, f64)

        def chi(m):
            return float(at_wave.misfit(at_wave.forward(m, geo, f64), obs))
        for cell in [(8, 4, 2), (5, 4, 2), (8, 3, 1), (11, 5, 4)]:
            e = jnp.zeros(c.shape, f64).at[cell].set(1e-3)
            fd = (chi(c + e) - chi(c - e)) / 2e-3
            assert fd == pytest.approx(float(grad[cell]), rel=1e-5, abs=1e-12)


def test_at_inversion_first_iteration_matches_program():
    from repro.apps.adjoint_tomography import step_kernel, step_update
    obs = at_wave.forward(jnp.asarray(at_wave.target_model(GEO, ANOMALY)),
                          GEO)
    m0 = jnp.full((32, 12, 12), 3000.0, jnp.float32)
    cfg = _program(GEO)
    m1 = step_update(cfg)(m0, step_kernel(cfg)(m0, obs)["grad"])["model"]
    chis, ref = at_wave.inversion(m0, obs, GEO, 1)
    d_got, d_ref = np.asarray(m1 - m0), np.asarray(ref - m0)
    assert np.max(np.abs(d_got - d_ref)) / np.max(np.abs(d_ref)) < 1e-2

