"""Pallas TPU kernel for the Mamba-1 selective scan.

TPU adaptation (vs. the CUDA selective-scan): the sequence is chunked on the
*grid* — grid = (B, n_dblocks, n_chunks) with chunks innermost so the SSM
state for one (batch, channel-block) stays resident in VMEM scratch across
chunk steps. Channels are blocked (``block_d``) so the working set
(chunk x bd inputs + N x bd state) fits VMEM.

Layout: the state is held as (N, bd) — channels on the lanes, the small SSM
state dim N on the sublanes — so a timestep's ``dt``/``x`` row broadcasts
over N without a relayout. B and C are passed transposed, (Bt, N, L), so a
timestep's B/C is a static lane column of a (N, tile) block. Time advances
in aligned 8-row tiles of x/dt/y (``pl.multiple_of``): each tile is loaded
and stored whole and its 8 steps are unrolled statically, so no load or
store sits at an unaligned dynamic sublane offset.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8          # sublane tile: time steps per aligned x/dt/y load and store
LANES = 128       # lane tile: time steps per B/C load


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
                 y_ref, hlast_ref, h_scr, *, chunk: int, tile: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)      # (N, bd)

    A = a_ref[...].astype(jnp.float32)                  # (N, bd)
    Dv = d_ref[...].astype(jnp.float32)                 # (1, bd)
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, A.shape[1]), 0)

    def tile_body(k, h):
        t0 = pl.multiple_of(k * tile, tile)
        Bk = b_ref[0, :, pl.ds(t0, tile)].astype(jnp.float32)   # (N, tile)
        Ck = c_ref[0, :, pl.ds(t0, tile)].astype(jnp.float32)
        for s in range(tile // ROWS):
            r0 = pl.multiple_of(t0 + s * ROWS, ROWS)
            x8 = x_ref[0, pl.ds(r0, ROWS), :].astype(jnp.float32)   # (8, bd)
            dt8 = dt_ref[0, pl.ds(r0, ROWS), :].astype(jnp.float32)
            y8 = jnp.zeros(x8.shape, jnp.float32)
            for j in range(ROWS):
                t = s * ROWS + j
                xt, dtt = x8[j:j + 1], dt8[j:j + 1]                 # (1, bd)
                h = (jnp.exp(dtt * A) * h
                     + (dtt * xt) * Bk[:, t:t + 1])                 # (N, bd)
                yt = jnp.sum(h * Ck[:, t:t + 1], axis=0, keepdims=True)
                y8 = jnp.where(row == j, yt + Dv * xt, y8)
            y_ref[0, pl.ds(r0, ROWS), :] = y8.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // tile, tile_body, h_scr[...])
    h_scr[...] = h

    @pl.when(ci == nc - 1)
    def _finish():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


def selective_scan_fwd(x, dt, A, B, C, D, h0, *, chunk: int = 512,
                       block_d: int = 512, interpret: bool = False):
    """Shapes as in ref.selective_scan_ref. Returns (y, h_last)."""
    Bt, L, di = x.shape
    N = A.shape[1]
    chunk = min(chunk, L)
    block_d = min(block_d, di)
    tile = min(LANES, chunk)
    assert L % chunk == 0 and di % block_d == 0
    assert chunk % tile == 0 and tile % ROWS == 0
    grid = (Bt, di // block_d, L // chunk)

    kernel = functools.partial(_scan_kernel, chunk=chunk, tile=tile)
    y, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),  # x
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),  # dt
            pl.BlockSpec((N, block_d), lambda b, d, c: (0, d)),            # A^T
            pl.BlockSpec((1, N, chunk), lambda b, d, c: (b, 0, c)),        # B^T
            pl.BlockSpec((1, N, chunk), lambda b, d, c: (b, 0, c)),        # C^T
            pl.BlockSpec((1, block_d), lambda b, d, c: (0, d)),            # D
            pl.BlockSpec((1, N, block_d), lambda b, d, c: (b, 0, d)),      # h0^T
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, N, block_d), lambda b, d, c: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, L, di), x.dtype),
            jax.ShapeDtypeStruct((Bt, N, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32)],
        interpret=interpret,
    )(x, dt, A.T, B.transpose(0, 2, 1), C.transpose(0, 2, 1),
      D.reshape(1, di), h0.transpose(0, 2, 1))
    return y, h_last.transpose(0, 2, 1)
