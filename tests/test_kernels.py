"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes/dtypes
(Pallas interpret mode; tests/test_tpu_compile.py compiles the kernels for
the TPU at real widths)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention_kernel_call
from repro.kernels.mamba_scan import ref as ms_ref
from repro.kernels.mamba_scan.kernel import selective_scan_fwd

RNG = np.random.default_rng(42)


def _mk(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,H,KV,S,D", [
    (1, 2, 2, 128, 128),      # MHA
    (2, 4, 2, 256, 128),      # GQA 2:1
    (1, 8, 2, 128, 128),      # GQA 4:1
    (1, 2, 1, 384, 128),      # non-pow2 block count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, KV, S, D, dtype, causal):
    q = _mk((B, S, H, D), dtype)
    k = _mk((B, S, KV, D), dtype)
    v = _mk((B, S, KV, D), dtype)
    scale = D ** -0.5
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    out = flash_attention_fwd(qt, kt, vt, scale=scale, causal=causal,
                              interpret=True)
    ref = fa_ref.attention_ref(q, k, v, scale=scale, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref.transpose(0, 2, 1, 3), np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_padding_wrapper():
    """Seq not a multiple of the block, head dim not lane-aligned."""
    B, S, H, KV, D = 1, 200, 2, 1, 96
    q, k, v = _mk((B, S, H, D), jnp.float32), _mk((B, S, KV, D), jnp.float32), \
        _mk((B, S, KV, D), jnp.float32)
    out = flash_attention_kernel_call(q, k, v, scale=D ** -0.5, causal=True,
                                      interpret=True)
    ref = fa_ref.attention_ref(q, k, v, scale=D ** -0.5, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_kv_len_mask():
    B, S, H, D = 1, 128, 2, 128
    q, k, v = (_mk((B, S, H, D), jnp.float32) for _ in range(3))
    out = flash_attention_fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), scale=0.1, causal=False,
                              kv_len=70, interpret=True)
    ref = fa_ref.attention_ref(q, k, v, scale=0.1, causal=False, kv_len=70)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.transpose(0, 2, 1, 3)), atol=2e-5)


def test_chunked_ref_matches_direct():
    B, S, H, KV, D = 2, 320, 4, 2, 64
    q, k, v = _mk((B, S, H, D), jnp.float32), _mk((B, S, KV, D), jnp.float32), \
        _mk((B, S, KV, D), jnp.float32)
    for causal in (True, False):
        a = fa_ref.attention_ref(q, k, v, scale=0.3, causal=causal)
        b = fa_ref.attention_ref_chunked(q, k, v, scale=0.3, causal=causal,
                                         q_chunk=128)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ---------------------------------------------------------------------------
# Mamba selective scan
# ---------------------------------------------------------------------------

def _scan_args(Bt, L, di, N, dtype):
    x = _mk((Bt, L, di), dtype)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (Bt, L, di)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (di, N)), jnp.float32)
    B = _mk((Bt, L, N), dtype)
    C = _mk((Bt, L, N), dtype)
    D = jnp.asarray(RNG.normal(size=(di,)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(Bt, di, N)), jnp.float32)
    return x, dt, A, B, C, D, h0


@pytest.mark.parametrize("Bt,L,di,N,chunk,block_d", [
    (1, 64, 32, 8, 16, 32),
    (2, 128, 64, 16, 32, 32),
    (2, 96, 48, 16, 32, 16),      # L not a power of two
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan_sweep(Bt, L, di, N, chunk, block_d, dtype):
    args = _scan_args(Bt, L, di, N, dtype)
    y, h = selective_scan_fwd(*args, chunk=chunk, block_d=block_d,
                              interpret=True)
    y_ref, h_ref = ms_ref.selective_scan_ref(*args, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=2e-4)


def test_mamba_step_matches_scan():
    """Decode single-step recurrence == scan applied one token at a time."""
    Bt, L, di, N = 2, 8, 16, 4
    x, dt, A, B, C, D, h0 = _scan_args(Bt, L, di, N, jnp.float32)
    y_ref, h_ref = ms_ref.selective_scan_ref(x, dt, A, B, C, D, h0, chunk=8)
    h = h0
    ys = []
    for t in range(L):
        y_t, h = ms_ref.selective_step_ref(x[:, t], dt[:, t], A, B[:, t],
                                           C[:, t], D, h)
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-4)


def test_mamba_chunk_invariance():
    """Chunk size must not change results (cross-chunk carry correctness)."""
    args = _scan_args(1, 64, 16, 8, jnp.float32)
    y1, h1 = ms_ref.selective_scan_ref(*args, chunk=8)
    y2, h2 = ms_ref.selective_scan_ref(*args, chunk=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)
