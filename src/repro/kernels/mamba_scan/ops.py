"""Jitted wrapper for the selective scan: Pallas on TPU, closed-form-VJP
associative scan elsewhere (and for dry-run lowering).

The linear recurrence  h_t = a_t h_{t-1} + b_t  has a closed-form adjoint:

    lam_t = g_t + a_{t+1} lam_{t+1}        (reverse linear scan)
    db_t  = lam_t
    da_t  = lam_t * h_{t-1}
    dh_0  = a_1 lam_1

so the backward pass is ONE more associative scan plus elementwise ops —
letting JAX differentiate *through* the associative scan instead costs
~100 tensor passes (measured; see EXPERIMENTS.md §Perf falcon iteration).
This is the same structure the original Mamba CUDA kernel uses; here it is
the jnp/XLA path, and the TPU Pallas kernel slots into the same custom_vjp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mamba_scan import ref
from repro.kernels.mamba_scan.kernel import selective_scan_fwd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _scan(x, dt, A, B, C, D, h0, chunk):
    return selective_scan_fwd(x, dt, A, B, C, D, h0, chunk=chunk)


def _scan_fwd(x, dt, A, B, C, D, h0, chunk):
    return _scan(x, dt, A, B, C, D, h0, chunk), (x, dt, A, B, C, D, h0)


def _scan_bwd(chunk, res, g):
    x, dt, A, B, C, D, h0 = res
    return _closed_form_bwd(x, dt, A, B, C, D, h0, g,
                            chunk=_mem_chunk(chunk, x))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _mem_chunk(chunk: int, x) -> int:
    """Outer chunk bounding the (B, chunk, d, N) working set."""
    return min(x.shape[1], max(chunk, 4096))


# ---------------------------------------------------------------------------
# Closed-form-adjoint selective scan (the jnp / lowering path).
# ---------------------------------------------------------------------------

def _ab(x, dt, A, B, sdt=jnp.float32):
    a = jnp.exp(dt[..., None] * A).astype(sdt)             # (Bt,L,d,N)
    b = ((dt * x)[..., None] * B[:, :, None, :]).astype(sdt)
    return a, b


def _fwd_states(x, dt, A, B, h0, chunk, sdt=jnp.float32):
    """All states h_{1..T} plus h_{0..T-1}, chunked associative scans.

    ``sdt`` sets the materialization dtype of the (B,L,d,N) scan tensors —
    bf16 halves the dominant HBM traffic of SSM training at a measured
    ~1e-2 relative output error (see EXPERIMENTS.md §Perf falcon)."""
    Bt, L, di = x.shape
    hs = []
    h = h0.astype(sdt)
    for c0 in range(0, L, chunk):
        sl = slice(c0, min(c0 + chunk, L))
        a, b = _ab(x[:, sl], dt[:, sl], A, B[:, sl], sdt)
        a_cum, s = ref._chunk_scan(a, b)
        hc = s + a_cum * h[:, None]
        hs.append(hc)
        h = hc[:, -1]
    return jnp.concatenate(hs, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _cf_scan(x, dt, A, B, C, D, h0, chunk, sdt):
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    h = _fwd_states(xf, dtf, A.astype(jnp.float32),
                    B.astype(jnp.float32), h0.astype(jnp.float32), chunk,
                    sdt)
    y = jnp.einsum("blds,bls->bld", h.astype(jnp.float32),
                   C.astype(jnp.float32))
    y = y + D.astype(jnp.float32) * xf
    return y.astype(x.dtype), h[:, -1].astype(jnp.float32)


def _cf_fwd(x, dt, A, B, C, D, h0, chunk, sdt):
    return _cf_scan(x, dt, A, B, C, D, h0, chunk, sdt), (x, dt, A, B, C, D, h0)


def _closed_form_bwd(x, dt, A, B, C, D, h0, cotangents, *, chunk,
                     sdt=jnp.float32):
    y_bar, hlast_bar = cotangents
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af, Bf, Cf = (t.astype(jnp.float32) for t in (A, B, C))
    yb = y_bar.astype(jnp.float32)
    Bt, L, di = x.shape

    h = _fwd_states(xf, dtf, Af, Bf, h0.astype(jnp.float32), chunk, sdt)
    h_prev = jnp.concatenate([h0.astype(sdt)[:, None], h[:, :-1]], 1)
    a, _ = _ab(xf, dtf, Af, Bf, sdt)

    # g_t = ybar_t (x) C_t  (+ final-state cotangent at T)
    g = (yb[..., None] * Cf[:, :, None, :]).astype(sdt)
    g = g.at[:, -1].add(hlast_bar.astype(sdt))
    # lam_t = g_t + a_{t+1} lam_{t+1}: reverse linear scan with shifted decay
    a_shift = jnp.concatenate([a[:, 1:], jnp.ones_like(a[:, :1])], axis=1)
    lam_chunks = []
    lam_carry = jnp.zeros(h0.shape, sdt)
    for c0 in reversed(range(0, L, chunk)):
        sl = slice(c0, min(c0 + chunk, L))
        ar = jnp.flip(a_shift[:, sl], 1)
        gr = jnp.flip(g[:, sl], 1)
        a_cum, s = ref._chunk_scan(ar, gr)
        lam_r = s + a_cum * lam_carry[:, None]
        lam_carry = lam_r[:, -1]
        lam_chunks.append(jnp.flip(lam_r, 1))
    lam = jnp.concatenate(lam_chunks[::-1], axis=1)        # (Bt,L,d,N)

    lam = lam.astype(jnp.float32) if lam.dtype != jnp.float32 else lam
    h_prev = h_prev.astype(jnp.float32)
    a = a.astype(jnp.float32)
    h = h.astype(jnp.float32)
    a_bar = lam * h_prev
    # a = exp(dt A):  dt_bar += sum_n a_bar a A ;  A_bar += sum_t a_bar a dt
    aa = a_bar * a
    dt_bar = jnp.einsum("blds,ds->bld", aa, Af)
    A_bar = jnp.einsum("blds,bld->ds", aa, dtf)
    # b = (dt x) (x) B: lam is b_bar
    lamB = jnp.einsum("blds,bls->bld", lam, Bf)
    dt_bar = dt_bar + xf * lamB
    x_bar = dtf * lamB + D.astype(jnp.float32) * yb
    B_bar = jnp.einsum("blds,bld->bls", lam, dtf * xf)
    C_bar = jnp.einsum("blds,bld->bls", h, yb)
    D_bar = jnp.einsum("bld,bld->d", yb, xf)
    h0_bar = a[:, 0] * lam[:, 0]
    return (x_bar.astype(x.dtype), dt_bar.astype(dt.dtype),
            A_bar.astype(A.dtype), B_bar.astype(B.dtype),
            C_bar.astype(C.dtype), D_bar.astype(D.dtype),
            h0_bar.astype(h0.dtype))


def _cf_bwd(chunk, sdt, res, cot):
    x, dt, A, B, C, D, h0 = res
    return _closed_form_bwd(x, dt, A, B, C, D, h0, cot, chunk=chunk, sdt=sdt)


_cf_scan.defvjp(_cf_fwd, _cf_bwd)


def selective_scan(x, dt, A, B, C, D, h0, *, chunk: int = 512,
                   scan_dtype: str = "float32"):
    """Public op; see ref.selective_scan_ref for shapes.

    TPU: Pallas sequential-in-VMEM kernel. Elsewhere (and for the dry-run
    lowering): associative scan with the closed-form adjoint.
    """
    if _on_tpu():
        return _scan(x, dt, A, B, C, D, h0, chunk)
    return _cf_scan(x, dt, A, B, C, D, h0, _mem_chunk(chunk, x),
                    jnp.dtype(scan_dtype))


selective_step = ref.selective_step_ref
