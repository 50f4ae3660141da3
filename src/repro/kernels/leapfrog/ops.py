"""The leapfrog wave solver's public op: the VMEM kernels on a TPU, the XLA
scan elsewhere.

On the kernel path the solve is a ``jax.custom_vjp``: its primal is the
forward kernel, its fwd rule the forward kernel with history, its bwd rule
the adjoint kernel. So ``jax.grad`` through it runs one forward solve that
writes the history and one adjoint solve that reads it: no checkpointed
scan, no per-step stash and no recomputation. The receiver gather, the
source amplitude k[s] w_t and k = (c dt)^2 stay outside the ``custom_vjp``,
where JAX differentiates them.

The path is chosen from what the input shows (:func:`solver_path`): the
kernels need the TPU backend, float32, and a padded working set within the
VMEM they ask the compiler for. Everything else runs :mod:`.ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.leapfrog import ref
from repro.kernels.leapfrog.kernel import (VMEM_LIMIT, Grid, adj_vmem_bytes,
                                           fwd_vmem_bytes, leapfrog_adj,
                                           leapfrog_fwd, to_layout)
from repro.obs.metrics import REGISTRY

VMEM_HEADROOM = 16 * 2 ** 20    # of VMEM_LIMIT, left to Mosaic's temporaries


def solver_path(shape, dtype, nt: int, backend: str) -> str:
    """``"vmem"`` where the kernels apply to an (nx, ny, nz) mesh of
    ``dtype`` over ``nt`` steps on ``backend``, else ``"xla"``."""
    if backend != "tpu" or jnp.dtype(dtype) != jnp.float32:
        return "xla"
    nx, ny, nz = shape
    grid = Grid(nz, ny, nx, (0, 0, 0), (0, 0), 1.0)
    need = max(fwd_vmem_bytes(grid, nt), adj_vmem_bytes(grid, nt))
    return "vmem" if need <= VMEM_LIMIT - VMEM_HEADROOM else "xla"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _solve(k, f, grid: Grid):
    return leapfrog_fwd(k, f, grid=grid, history=False)


def _solve_fwd(k, f, grid: Grid):
    rows, hist = leapfrog_fwd(k, f, grid=grid, history=True)
    return rows, (k, hist)


def _solve_bwd(grid: Grid, res, rows_bar):
    k, hist = res
    dk, lam_src = leapfrog_adj(k, rows_bar, hist, grid=grid)
    return dk, lam_src[:, grid.col(grid.src[2])]


_solve.defvjp(_solve_fwd, _solve_bwd)


def vmem_seismograms(c, src, *, dt: float, dx: float, source, receivers):
    """The kernel path of :func:`seismograms`."""
    nx, ny, nz = c.shape
    sx, sy, sz = source
    rx, ry, rz = receivers
    grid = Grid(nz, ny, nx, (sz, sy, sx), (rz, ry), dx)
    k = (c * dt) ** 2
    f = k[sx, sy, sz] * src.astype(k.dtype)
    return _solve(to_layout(k, grid), f, grid)[:, grid.col(rx)]


def seismograms(c, src, *, dt: float, dx: float, source, receivers):
    """Leapfrog FD on velocity ``c`` (nx, ny, nz) with wavelet ``src`` (nt,)
    at cell ``source`` = (sx, sy, sz); the wavefield at ``receivers`` =
    (rx, ry, rz), ``rx`` an index array, after every step: (nt, len(rx)).
    Counts the path it takes, once per trace."""
    kw = dict(dt=dt, dx=dx, source=source, receivers=receivers)
    if solver_path(c.shape, c.dtype, src.shape[0],
                   jax.default_backend()) == "vmem":
        REGISTRY.inc("at.wave_solver.vmem")
        return vmem_seismograms(c, src, **kw)
    REGISTRY.inc("at.wave_solver.xla")
    return ref.seismograms(c, src, **kw)
