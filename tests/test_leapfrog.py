"""The leapfrog VMEM kernels (Pallas TPU interpret mode) against the plain
float32 reference and the XLA path, and the choice between the two paths.

Three meshes: one whose x extent fills fewer lanes than a tile, one with
every extent odd, so that the zero padding the kernels rely on for their
Dirichlet boundary is exercised off any tile boundary, and one whose x
extent spans two lane tiles, so that the interleaved layout's seam lies
inside the mesh along x as well as along y.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from chipbench.reference import at_wave
from repro.kernels.leapfrog import kernel as K
from repro.kernels.leapfrog import ops, ref
from repro.obs.metrics import REGISTRY

MESHES = {"on-tile": (32, 12, 12, 80), "off-tile": (17, 9, 11, 40),
          "two-lane-tiles": (133, 9, 7, 80)}


def _geo(nx, ny, nz, nt):
    return dict(nx=nx, ny=ny, nz=nz, nt=nt, dx=100.0, dt=0.008, c0=3000.0,
                f0=4.0, n_receivers=16, lr=0.4)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.fixture(scope="module")
def solved():
    """Per mesh: the kernels' forward, gradient and history, the XLA
    path's and the reference's, computed once."""
    out = {}
    for name, (nx, ny, nz, nt) in MESHES.items():
        geo = _geo(nx, ny, nz, nt)
        (sx, sy, sz), (rx, ry, rz) = at_wave.geometry(geo)
        w = at_wave.ricker(geo)
        kw = dict(dt=geo["dt"], dx=geo["dx"], source=(sx, sy, sz),
                  receivers=(jnp.asarray(rx), ry, rz))
        c = jnp.asarray(at_wave.target_model(
            geo, [(nx * 0.4, ny / 2, nz / 2, 2.2, 230.0)]))
        obs = at_wave.forward(jnp.asarray(at_wave.target_model(
            geo, [(nx * 0.6, ny / 2, nz / 2, 2.0, -150.0)])), geo)

        def chi(solve):
            return lambda m: 0.5 * jnp.sum((solve(m, w, **kw) - obs) ** 2)

        grid = K.Grid(nz, ny, nx, (sz, sy, sx), (rz, ry), geo["dx"])
        k = (c * geo["dt"]) ** 2
        kt = K.to_layout(k, grid)
        # Each interpreted solve is one program that runs alone, its inputs
        # ready and its outputs waited for: the interpreter's memory
        # callbacks can deadlock against a computation running beside them.
        f = k[sx, sy, sz] * w
        jax.block_until_ready((c, w, obs, kt, f))
        with pltpu.force_tpu_interpret_mode():
            seis = jax.block_until_ready(jax.jit(
                lambda c: ops.vmem_seismograms(c, w, **kw))(c))
            grad = jax.block_until_ready(
                jax.jit(jax.grad(chi(ops.vmem_seismograms)))(c))
            _, hist = jax.block_until_ready(
                K.leapfrog_fwd(kt, f, grid=grid, history=True))
        _, ref_hist = at_wave._forward(c, cfg_items=tuple(sorted(geo.items())),
                                       dtype=jnp.float32)
        with jax.enable_x64(True):
            f64 = jnp.float64
            _, grad64 = at_wave.gradient(
                jnp.asarray(c, f64), jnp.asarray(obs, f64), geo, f64)
        out[name] = dict(
            seis=seis, grad=grad, hist=hist, grid=grid, src=(sx, sy, sz),
            ref_seis=at_wave.forward(c, geo),
            ref_grad=at_wave.gradient(c, obs, geo)[1], ref_hist=ref_hist,
            xla_seis=ref.seismograms(c, w, **kw),
            xla_grad=jax.grad(chi(ref.seismograms))(c), grad64=grad64)
    return out


@pytest.mark.parametrize("check", ["forward", "gradient", "history"])
@pytest.mark.parametrize("mesh", list(MESHES))
def test_vmem_kernels_match_reference_and_xla_path(solved, mesh, check):
    r = solved[mesh]
    if check == "forward":
        assert _rel(r["seis"], r["ref_seis"]) < 1e-5
        assert _rel(r["seis"], r["xla_seis"]) < 1e-5
    elif check == "gradient":
        # Near the source every float32 solver is off the float64 gradient
        # by rounding, the XLA path by up to 1.0e-3 of its largest value at
        # the source cell itself. So the source cell is held to the float64
        # gradient at 2e-3, and every other cell to the float32 reference
        # and the XLA path at 1e-4; where the XLA path is itself further
        # than that from the reference, at 1.5 times its distance.
        src = r["src"]
        away = np.ones(r["grad"].shape, bool)
        away[src] = False
        grad, ref_grad, xla_grad = (np.asarray(r[k], np.float64) for k in (
            "grad", "ref_grad", "xla_grad"))
        scale = np.max(np.abs(ref_grad))
        xla_off = np.max(np.abs(xla_grad - ref_grad)[away]) / scale
        tol = 1e-4 if xla_off < 1e-4 else 1.5 * xla_off
        for other in (ref_grad, xla_grad):
            assert np.max(np.abs(grad - other)[away]) < tol * scale
        g64 = np.asarray(r["grad64"])
        assert abs(grad[src] - g64[src]) < 2e-3 * np.max(np.abs(g64))
    else:
        grid = r["grid"]
        hist = np.asarray(r["hist"])
        ref_hist = np.asarray(r["ref_hist"])
        np.testing.assert_allclose(
            np.asarray(K.from_layout(hist, grid)), ref_hist, rtol=0,
            atol=1e-5 * np.max(np.abs(ref_hist)))
        pad = np.asarray(K.to_layout(
            jnp.ones((grid.nx, grid.ny, grid.nz)), grid)) == 0
        assert not hist[:, pad].any()


FIG12 = (208, 44, 46)


@pytest.mark.parametrize("shape,dtype,backend,want", [
    (FIG12, jnp.float32, "cpu", "xla"),
    (FIG12, jnp.float64, "tpu", "xla"),
    ((416, 88, 92), jnp.float32, "tpu", "xla"),
    (FIG12, jnp.float32, "tpu", "vmem"),
])
def test_solver_path(shape, dtype, backend, want):
    assert ops.solver_path(shape, dtype, 200, backend) == want


def test_fig12_working_set_fits_and_a_doubled_mesh_does_not():
    g = K.Grid(46, 44, 208, (2, 22, 104), (2, 22), 100.0)
    budget = K.VMEM_LIMIT - ops.VMEM_HEADROOM
    assert K.fwd_vmem_bytes(g, 200) < K.adj_vmem_bytes(g, 200) <= budget
    big = K.Grid(92, 88, 416, (2, 44, 208), (2, 44), 100.0)
    assert K.adj_vmem_bytes(big, 200) > budget


@pytest.mark.parametrize("backend,path", [("tpu", "vmem"), ("cpu", "xla")])
def test_counters_count_the_path_taken(monkeypatch, backend, path):
    """Traced only (``eval_shape``): no kernel starts."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    geo = _geo(*MESHES["on-tile"])
    (sx, sy, sz), (rx, ry, rz) = at_wave.geometry(geo)
    kw = dict(dt=geo["dt"], dx=geo["dx"], source=(sx, sy, sz),
              receivers=(jnp.asarray(rx), ry, rz))
    c = jax.ShapeDtypeStruct((32, 12, 12), jnp.float32)
    w = jax.ShapeDtypeStruct((80,), jnp.float32)
    names = ("at.wave_solver.vmem", "at.wave_solver.xla")
    before = {n: REGISTRY.counter(n).value for n in names}
    seis = jax.eval_shape(lambda c, w: ops.seismograms(c, w, **kw), c, w)
    grad = jax.eval_shape(jax.grad(
        lambda c, w: jnp.sum(ops.seismograms(c, w, **kw) ** 2)), c, w)
    after = {n: REGISTRY.counter(n).value for n in names}
    assert seis.shape == (80, 16) and grad.shape == (32, 12, 12)
    assert after[f"at.wave_solver.{path}"] - before[
        f"at.wave_solver.{path}"] == 2
    other, = set(names) - {f"at.wave_solver.{path}"}
    assert after[other] == before[other]
