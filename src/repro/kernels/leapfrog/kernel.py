"""Pallas TPU kernels for the leapfrog acoustic wave solver and its discrete
adjoint. Each runs the whole time loop in one invocation with every field
resident in VMEM, so HBM sees only the inputs, the outputs and the history.

The scheme, for t = 0 .. nt-1 with u_{-1} = u_0 = 0, k = (c dt)^2:

    u_{t+1} = 2 u_t - u_{t-1} + k L(u_t) + f_t e_s
    rows_t  = u_{t+1}[receiver row]

and its adjoint, for t = nt-1 .. 0 with lam_{nt+1} = lam_{nt+2} = 0:

    lam_{t+1} = 2 lam_{t+2} + L(k lam_{t+2}) - lam_{t+3} + R^T g_t
    dk        = sum_t lam_{t+1} L(u_t)
    df_t      = lam_{t+1}[s]

L is the 7-point Laplacian (sum of the six neighbours - 6 u) / dx^2 with zero
(Dirichlet) boundaries, f_t the source amplitude injected at cell s.

Layout: a field of the (nx, ny, nz) mesh is held as (nz, ny_p, nx_p): z on
the major axis (planes), y on the sublanes, x on the lanes, zero-padded to
ny_p >= ny + 1 (J vreg rows of 8 sublanes) and nx_p >= nx + 1 (H vregs of
128 lanes). Within a plane y and x are interleaved across vregs: y = s J + j
sits on sublane s of vreg row j, x = l H + h on lane l of vreg column h. So
the y+1 neighbour of vreg row j is vreg row j + 1, and only the last row's
is the first row rolled by one sublane (likewise along x, by one lane):
a plane's Laplacian rolls one vreg row and one vreg column each way, not
every vreg, and needs no select to stitch neighbouring vregs. At a mesh
cell the rolls' wraparound reads padding, and the padding of every field
read across cells (u, and k lam in the adjoint) stays zero since k = 0
there, so the rolls give the Dirichlet boundary without masks. A field read
across planes carries one zero plane on each side (nz + 2 planes).

The receivers lie on one row of one plane (plane rz, y = ry): the forward
kernel emits that row at every step, and the adjoint kernel takes the
residual as such rows. The forward kernel's history variant writes u_t to
HBM by an async DMA that overlaps step t; the adjoint kernel reads it back
double-buffered, fetching u_{t-1} while it computes step t.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
LANES = 128
F32 = 4
UNROLL = 2        # planes per iteration of the loop over z
VMEM_LIMIT = 64 * 2 ** 20     # scoped VMEM the kernels ask the compiler for


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class Grid(NamedTuple):
    """Static geometry of one solve, in the kernel's (z, y, x) order."""
    nz: int
    ny: int
    nx: int
    src: Tuple[int, int, int]   # (z, y, x) of the source cell
    rec: Tuple[int, int]        # (z, y) of the receiver row
    dx: float

    @property
    def ny_p(self) -> int:
        return _round_up(self.ny + 1, SUBLANES)

    @property
    def nx_p(self) -> int:
        return _round_up(self.nx + 1, LANES)

    def row(self, y):
        """Sublane row of a plane that holds ``y``."""
        j = self.ny_p // SUBLANES
        return (y % j) * SUBLANES + y // j

    def col(self, x):
        """Lane of a plane row that holds ``x``, an int or index array."""
        h = self.nx_p // LANES
        return (x % h) * LANES + x // h

    @property
    def plane_bytes(self) -> int:
        return self.ny_p * self.nx_p * F32

    def rows_bytes(self, nt: int) -> int:
        """An (nt, 1, nx_p) row stack in VMEM, each row padded to a tile."""
        return nt * SUBLANES * self.nx_p * F32


def to_layout(a, grid: Grid):
    """An (nx, ny, nz) field in the kernels' (nz, ny_p, nx_p) layout."""
    j, h = grid.ny_p // SUBLANES, grid.nx_p // LANES
    a = jnp.pad(jnp.transpose(a, (2, 1, 0)),
                ((0, 0), (0, grid.ny_p - grid.ny), (0, grid.nx_p - grid.nx)))
    a = a.reshape(grid.nz, SUBLANES, j, LANES, h).transpose(0, 2, 1, 4, 3)
    return a.reshape(grid.nz, grid.ny_p, grid.nx_p)


def from_layout(a, grid: Grid):
    """Inverse of :func:`to_layout` on the leading ``(nz, ny_p, nx_p)``
    axes' trailing two; leading axes before them are kept."""
    *lead, nz, ny_p, nx_p = a.shape
    j, h = ny_p // SUBLANES, nx_p // LANES
    n = len(lead)
    a = a.reshape(*lead, nz, j, SUBLANES, h, LANES)
    a = jnp.transpose(a, (*range(n), n, n + 2, n + 1, n + 4, n + 3))
    a = a.reshape(*lead, nz, ny_p, nx_p)[..., :grid.ny, :grid.nx]
    return jnp.transpose(a, (*range(n), n + 2, n + 1, n))


def fwd_vmem_bytes(grid: Grid, nt: int) -> int:
    """VMEM the forward kernel holds: two wavefields with halo planes, k,
    and the receiver rows."""
    planes = 2 * (grid.nz + 2) + grid.nz
    return planes * grid.plane_bytes + grid.rows_bytes(nt)


def adj_vmem_bytes(grid: Grid, nt: int) -> int:
    """VMEM the adjoint kernel holds: two history slots and two k*lam
    fields with halo planes, two adjoint fields, k and the gradient, and
    the residual and source rows."""
    planes = 4 * (grid.nz + 2) + 4 * grid.nz
    return planes * grid.plane_bytes + 2 * grid.rows_bytes(nt)


def _laplacian(c, below, above, inv_dx2: float):
    """7-point Laplacian of plane ``c`` between planes ``below``/``above``,
    summed as the XLA path sums: x-1, x+1, y-1, y+1, z-1, z+1.

    In the interleaved layout a neighbour along x or y is the next or
    previous vreg, except across the layout's seam, where it is the first
    or last vreg rotated by one lane or sublane."""
    ny, nx = c.shape
    xs = [c[:, h:h + LANES] for h in range(0, nx, LANES)]
    ys = [c[j:j + SUBLANES] for j in range(0, ny, SUBLANES)]
    cat = jnp.concatenate
    x_lo = cat([pltpu.roll(xs[-1], 1, 1)] + xs[:-1], axis=1)
    x_hi = cat(xs[1:] + [pltpu.roll(xs[0], LANES - 1, 1)], axis=1)
    y_lo = cat([pltpu.roll(ys[-1], 1, 0)] + ys[:-1], axis=0)
    y_hi = cat(ys[1:] + [pltpu.roll(ys[0], SUBLANES - 1, 0)], axis=0)
    lap = -6.0 * c
    lap = lap + x_lo + x_hi
    lap = lap + y_lo + y_hi
    lap = lap + below + above
    return lap * inv_dx2


def _over_planes(lo: int, hi: int, body, carry):
    """``carry = body(z, carry)`` for z in [lo, hi), UNROLL planes to an
    iteration of the loop."""
    n = (hi - lo) // UNROLL

    def group(i, c):
        for j in range(UNROLL):
            c = body(lo + i * UNROLL + j, c)
        return c

    carry = jax.lax.fori_loop(0, n, group, carry)
    for z in range(lo + n * UNROLL, hi):
        carry = body(z, carry)
    return carry


def _fwd_kernel(f_ref, k_ref, rows_ref, *rest, grid: Grid, nt: int,
                history: bool):
    if history:
        hist_ref, u, sem = rest
    else:
        (u,) = rest
    nz = grid.nz
    sz, sy, sx = grid.src[0], grid.row(grid.src[1]), grid.col(grid.src[2])
    rz, ry = grid.rec[0], grid.row(grid.rec[1])
    inv = 1.0 / (grid.dx * grid.dx)
    shape = (grid.ny_p, grid.nx_p)
    src = ((jax.lax.broadcasted_iota(jnp.int32, shape, 0) == sy)
           & (jax.lax.broadcasted_iota(jnp.int32, shape, 1) == sx))
    u[...] = jnp.zeros(u.shape, jnp.float32)

    def step(t, carry):
        cur = jax.lax.rem(t, 2)          # u_t; u_{t-1} in the other slot,
        nxt = 1 - cur                    # overwritten plane by plane
        if history:
            copy = pltpu.make_async_copy(u.at[cur, pl.ds(1, nz)],
                                         hist_ref.at[t], sem)
            copy.start()

        def plane(z, c):
            below, ut = c                # u_t planes z-1 and z, carried
            above = u[cur, z + 2]
            lap = _laplacian(ut, below, above, inv)
            u[nxt, z + 1] = 2 * ut - u[nxt, z + 1] + k_ref[z] * lap
            return ut, above

        _over_planes(0, nz, plane, (u[cur, 0], u[cur, 1]))
        u[nxt, sz + 1] = u[nxt, sz + 1] + jnp.where(src, f_ref[t], 0.0)
        rows_ref[t] = u[nxt, rz + 1, ry:ry + 1, :]
        if history:
            copy.wait()                  # step t+1 overwrites this slot
        return carry

    jax.lax.fori_loop(0, nt, step, 0)


@functools.partial(jax.jit, static_argnames=("grid", "history"))
def leapfrog_fwd(k, f, *, grid: Grid, history: bool):
    """Forward solve. ``k``: (nz, ny_p, nx_p) float32, zero in the padding;
    ``f``: (nt,) source amplitudes. Returns the receiver rows (nt, nx_p)
    and, with ``history``, u_t for t = 0 .. nt-1 as (nt, nz, ny_p, nx_p)."""
    nt = f.shape[0]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    rows = jax.ShapeDtypeStruct((nt, 1, grid.nx_p), jnp.float32)
    field = (2, grid.nz + 2, grid.ny_p, grid.nx_p)
    out_shape, out_specs = [rows], [vmem]
    scratch = [pltpu.VMEM(field, jnp.float32)]
    if history:
        out_shape.append(jax.ShapeDtypeStruct(
            (nt, grid.nz, grid.ny_p, grid.nx_p), jnp.float32))
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch.append(pltpu.SemaphoreType.DMA(()))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, grid=grid, nt=nt, history=history),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name="at_leapfrog_fwd",
    )(f, k)
    rows = out[0].reshape(nt, grid.nx_p)
    return (rows, out[1]) if history else rows


def _adj_kernel(k_ref, res_ref, hist_ref, g_ref, lsrc_ref, u, q, lam, sem,
                *, grid: Grid, nt: int):
    nz = grid.nz
    sz, sy = grid.src[0], grid.row(grid.src[1])
    rz, ry = grid.rec[0], grid.row(grid.rec[1])
    inv = 1.0 / (grid.dx * grid.dx)
    rec = jax.lax.broadcasted_iota(jnp.int32, (grid.ny_p, grid.nx_p), 0) == ry
    for buf in (u, q, lam, g_ref):
        buf[...] = jnp.zeros(buf.shape, jnp.float32)

    def fetch(t, slot):
        return pltpu.make_async_copy(hist_ref.at[t], u.at[slot, pl.ds(1, nz)],
                                     sem.at[slot])

    fetch(nt - 1, 0).start()

    def step(i, carry):
        t = nt - 1 - i
        a = jax.lax.rem(i, 2)            # lam_{t+2}, k lam_{t+2}, u_t
        b = 1 - a                        # lam_{t+3}, overwritten by lam_{t+1}

        @pl.when(t > 0)
        def _():
            fetch(t - 1, b).start()

        fetch(t, a).wait()

        def plane(z, c, receivers: bool):
            qb, qm, ub, um = c           # planes z-1 and z, carried
            qa, ua = q[a, z + 2], u[a, z + 2]
            lq = _laplacian(qm, qb, qa, inv)
            lt = 2 * lam[a, z] + lq - lam[b, z]
            if receivers:
                lt = lt + jnp.where(rec, res_ref[t], 0.0)
            lam[b, z] = lt
            q[b, z + 1] = k_ref[z] * lt
            lu = _laplacian(um, ub, ua, inv)
            g_ref[z] = g_ref[z] + lt * lu
            return qm, qa, um, ua

        def planes(lo, hi, c):
            return _over_planes(lo, hi, lambda z, c: plane(z, c, False), c)

        c = (q[a, 0], q[a, 1], u[a, 0], u[a, 1])
        c = planes(0, rz, c)
        c = plane(rz, c, True)
        planes(rz + 1, nz, c)
        lsrc_ref[t] = lam[b, sz, sy:sy + 1, :]
        return carry

    jax.lax.fori_loop(0, nt, step, 0)


@functools.partial(jax.jit, static_argnames=("grid",))
def leapfrog_adj(k, res, hist, *, grid: Grid):
    """Adjoint solve. ``k`` as for :func:`leapfrog_fwd`; ``res``: (nt, nx_p)
    cotangent of the receiver rows; ``hist``: the forward history. Returns
    (sum_t lam_{t+1} L(u_t) as (nz, ny_p, nx_p), the source row of
    lam_{t+1} as (nt, nx_p))."""
    nt = res.shape[0]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    halo = (2, grid.nz + 2, grid.ny_p, grid.nx_p)
    g, lsrc = pl.pallas_call(
        functools.partial(_adj_kernel, grid=grid, nt=nt),
        in_specs=[vmem, vmem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[vmem, vmem],
        out_shape=[jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct((nt, 1, grid.nx_p), jnp.float32)],
        scratch_shapes=[pltpu.VMEM(halo, jnp.float32),     # u_t, u_{t-1}
                        pltpu.VMEM(halo, jnp.float32),     # k lam
                        pltpu.VMEM((2, grid.nz, grid.ny_p, grid.nx_p),
                                   jnp.float32),            # lam
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name="at_leapfrog_adj",
    )(k, res.reshape(nt, 1, grid.nx_p), hist)
    return g, lsrc.reshape(nt, grid.nx_p)
