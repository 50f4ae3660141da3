"""Plain reference of the adjoint-tomography inversion (arXiv:1710.01429 §4).

A 3-D acoustic wave equation, second-order leapfrog in time and a 7-point
Laplacian with zero (Dirichlet) boundaries, a Ricker source and a line of
receivers; its misfit; the gradient of the misfit with respect to the
velocity model by the discrete adjoint-state method (a reverse-time
adjoint wavefield, written out by hand, no automatic differentiation); and
the normalised steepest-descent update. Written from the equations alone:
it imports nothing of the program under test.

Every function takes ``dtype``: float32 is the configuration's precision,
bfloat16 is the benchmark's control (the nearest precision below).

The discrete scheme, for t = 0 .. nt-1, with u_{-1} = u_0 = 0:

    u_{t+1} = 2 u_t - u_{t-1} + k * L(u_t) + k[s] w_t e_s,   k = (c dt)^2
    rec_t   = u_{t+1}[receivers]
    chi     = 1/2 sum_t |rec_t - obs_t|^2

Its adjoint, with lam_{nt+1} = lam_{nt+2} = 0 and L symmetric:

    lam_j   = R^T (rec_{j-1} - obs_{j-1}) + 2 lam_{j+1} + L(k lam_{j+1}) - lam_{j+2}
    dchi/dk = sum_t lam_{t+1} L(u_t) + e_s sum_t lam_{t+1}[s] w_t
    dchi/dc = dchi/dk * 2 c dt^2
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def geometry(cfg: dict):
    """Source cell and receiver cells, as the configuration states them."""
    nx, ny = cfg["nx"], cfg["ny"]
    src = (nx // 2, ny // 2, 2)
    rx = np.linspace(4, nx - 5, cfg["n_receivers"]).astype(np.int32)
    return src, (rx, ny // 2, 2)


def ricker(cfg: dict, dtype=jnp.float32):
    t = np.arange(cfg["nt"], dtype=np.float64) * cfg["dt"] - 1.0 / cfg["f0"]
    a = (math.pi * cfg["f0"]) ** 2 * t * t
    return jnp.asarray((1 - 2 * a) * np.exp(-a), dtype)


def laplacian(u, dx: float):
    """7-point Laplacian; cells outside the grid read as zero."""
    p = jnp.pad(u, 1)
    nx, ny, nz = u.shape
    s = (p[:-2, 1:-1, 1:-1] + p[2:, 1:-1, 1:-1]
         + p[1:-1, :-2, 1:-1] + p[1:-1, 2:, 1:-1]
         + p[1:-1, 1:-1, :-2] + p[1:-1, 1:-1, 2:])
    return (s - 6 * u) / jnp.asarray(dx * dx, u.dtype)


def _k(c, cfg):
    return (c * jnp.asarray(cfg["dt"], c.dtype)) ** 2


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _forward(c, *, cfg_items, dtype):
    cfg = dict(cfg_items)
    c = c.astype(dtype)
    k = _k(c, cfg)
    (sx, sy, sz), (rx, ry, rz) = geometry(cfg)
    w = ricker(cfg, dtype)

    def step(carry, w_t):
        u_prev, u = carry
        u_next = 2 * u - u_prev + k * laplacian(u, cfg["dx"])
        u_next = u_next.at[sx, sy, sz].add(k[sx, sy, sz] * w_t)
        return (u, u_next), (u_next[rx, ry, rz], u)

    z = jnp.zeros(c.shape, dtype)
    _, (seis, hist) = jax.lax.scan(step, (z, z), w)
    return seis, hist          # seis (nt, nr); hist[t] = u_t, t = 0..nt-1


def forward(c, cfg: dict, dtype=jnp.float32):
    """Seismograms (nt, n_receivers) of velocity model ``c``."""
    return _forward(c, cfg_items=tuple(sorted(cfg.items())), dtype=dtype)[0]


def misfit(seis, obs):
    r = seis - obs.astype(seis.dtype)
    return 0.5 * jnp.sum(r * r)


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _gradient(c, obs, *, cfg_items, dtype):
    cfg = dict(cfg_items)
    c = c.astype(dtype)
    k = _k(c, cfg)
    (sx, sy, sz), (rx, ry, rz) = geometry(cfg)
    w = ricker(cfg, dtype)
    seis, hist = _forward(c, cfg_items=cfg_items, dtype=dtype)
    res = seis - obs.astype(dtype)                     # (nt, nr)

    def adj(carry, xs):
        lam1, lam2, g = carry                          # lam_{t+2}, lam_{t+3}
        res_t, u_t, w_t = xs                           # t runs nt-1 .. 0
        # lam_{t+1}: direct term at the receivers, then the two-step recursion
        lam = (2 * lam1 + laplacian(k * lam1, cfg["dx"]) - lam2)
        lam = lam.at[rx, ry, rz].add(res_t)
        g = g + lam * laplacian(u_t, cfg["dx"])
        g = g.at[sx, sy, sz].add(lam[sx, sy, sz] * w_t)
        return (lam, lam1, g), None

    z = jnp.zeros(c.shape, dtype)
    (_, _, gk), _ = jax.lax.scan(adj, (z, z, z), (res, hist, w), reverse=True)
    grad = gk * 2 * c * jnp.asarray(cfg["dt"] ** 2, dtype)
    return misfit(seis, obs), grad


def gradient(c, obs, cfg: dict, dtype=jnp.float32):
    """(chi, dchi/dc) by the adjoint-state method."""
    return _gradient(c, obs, cfg_items=tuple(sorted(cfg.items())),
                     dtype=dtype)


def update(c, grad, cfg: dict):
    g = grad / (jnp.max(jnp.abs(grad)) + 1e-20)
    return c - cfg["lr"] * g * 20.0


def inversion(c0, obs, cfg: dict, iterations: int, dtype=jnp.float32):
    """``iterations`` steps from ``c0``: (chi of each iterate, final model)."""
    c = jnp.asarray(c0, dtype)
    chis = []
    for _ in range(iterations):
        chi, grad = gradient(c, obs, cfg, dtype)
        chis.append(float(chi))
        c = update(c, grad, cfg)
    return chis, c


def target_model(cfg: dict, anomalies) -> np.ndarray:
    """Background velocity plus Gaussian anomalies ``(cx, cy, cz, r, amp)``."""
    x, y, z = np.meshgrid(np.arange(cfg["nx"]), np.arange(cfg["ny"]),
                          np.arange(cfg["nz"]), indexing="ij")
    c = np.full((cfg["nx"], cfg["ny"], cfg["nz"]), cfg["c0"], np.float64)
    for cx, cy, cz, r, amp in anomalies:
        c += amp * np.exp(-((x - cx) ** 2 + (y - cy) ** 2 + (z - cz) ** 2)
                          / r ** 2)
    return c.astype(np.float32)
