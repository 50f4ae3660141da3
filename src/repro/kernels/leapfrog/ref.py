"""The leapfrog acoustic solver as a plain ``lax.scan`` (the XLA path).

Each time step is rematerialized under ``jax.checkpoint``, so reverse-mode
AD through the scan stores one carry per step and recomputes the rest. This
is the path wherever the VMEM kernels do not apply: off the TPU, in float64,
and for meshes too large for VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _shift(u: jnp.ndarray, axis: int, d: int) -> jnp.ndarray:
    """Shift with zero boundaries (Dirichlet), no wraparound."""
    pad = [(0, 0)] * u.ndim
    pad[axis] = (max(d, 0), max(-d, 0))
    up = jnp.pad(u, pad)
    idx = [slice(None)] * u.ndim
    idx[axis] = slice(max(-d, 0), up.shape[axis] - max(d, 0))
    return up[tuple(idx)]


def _laplacian(u: jnp.ndarray, dx: float) -> jnp.ndarray:
    """7-point 3D Laplacian, zero (Dirichlet) boundaries."""
    lap = -6.0 * u
    for axis in range(3):
        lap = lap + _shift(u, axis, 1) + _shift(u, axis, -1)
    return lap / (dx * dx)


def seismograms(c, src, *, dt: float, dx: float, source, receivers):
    """Leapfrog FD on velocity ``c`` (nx, ny, nz) with wavelet ``src``
    (nt,) injected at cell ``source``; returns the wavefield at
    ``receivers`` = (rx, ry, rz) after every step, (nt, n_receivers)."""
    sx, sy, sz = source
    rx, ry, rz = receivers
    c2dt2 = (c * dt) ** 2

    def step(carry, s_t):
        u_prev, u = carry
        lap = _laplacian(u, dx)
        u_next = 2 * u - u_prev + c2dt2 * lap
        u_next = u_next.at[sx, sy, sz].add(c2dt2[sx, sy, sz] * s_t)
        rec = u_next[rx, ry, rz]
        return (u, u_next), rec

    u0 = jnp.zeros(c.shape)
    step = jax.checkpoint(step)
    (_, _), seis = jax.lax.scan(step, (u0, u0), src)
    return seis
