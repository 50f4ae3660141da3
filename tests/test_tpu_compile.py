"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test compiles for a described (not attached) v5e chip
and checks that the program holds the kernel (``tpu_custom_call``). This is
what interpret mode cannot show — Mosaic refuses unaligned dynamic slices
and layouts that the interpreter accepts.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.flash_attention.ops import flash_attention_kernel_call
from repro.kernels.leapfrog import kernel as leapfrog
from repro.kernels.mamba_scan.kernel import selective_scan_fwd

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent
    compilation cache off: a program compiled for a described chip cannot
    be read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "llama3.2-3b"])
def test_flash_forward_compiles_for_v5e(one_chip, arch):
    """Prefill attention at the arch's head widths: batch 4, seq 2048."""
    cfg = get_config(arch)
    B, S, hd = 4, 2048, cfg.hdim
    q = _spec((B, S, cfg.n_heads, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, S, cfg.n_kv_heads, hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention_kernel_call(
        q, k, v, scale=hd ** -0.5)).lower(q, kv, kv).compile()
    assert KERNEL in compiled.as_text()


def test_selective_scan_compiles_for_v5e(one_chip):
    """falcon-mamba-7b widths: d_inner 8192, N 16; batch 1, seq 2048."""
    cfg = get_config("falcon-mamba-7b")
    Bt, L, di, N = 1, 2048, cfg.d_inner, cfg.ssm_state
    f32 = jnp.float32
    args = [_spec(s, f32, one_chip) for s in (
        (Bt, L, di), (Bt, L, di), (di, N), (Bt, L, N), (Bt, L, N), (di,),
        (Bt, di, N))]
    compiled = jax.jit(selective_scan_fwd).lower(*args).compile()
    assert KERNEL in compiled.as_text()


@pytest.mark.parametrize("solve", ["forward", "forward+history", "adjoint"])
def test_leapfrog_kernels_compile_for_v5e(one_chip, solve):
    """The adjoint-tomography wave solver at the paper's Fig. 12 mesh
    (208x44x46, nt 200), with its VMEM limit."""
    nt = 200
    g = leapfrog.Grid(46, 44, 208, (2, 22, 104), (2, 22), 100.0)
    k = _spec((g.nz, g.ny_p, g.nx_p), jnp.float32, one_chip)
    if solve == "adjoint":
        rows = _spec((nt, g.nx_p), jnp.float32, one_chip)
        hist = _spec((nt, g.nz, g.ny_p, g.nx_p), jnp.float32, one_chip)
        compiled = jax.jit(lambda k, r, h: leapfrog.leapfrog_adj(
            k, r, h, grid=g)).lower(k, rows, hist).compile()
    else:
        f = _spec((nt,), jnp.float32, one_chip)
        compiled = jax.jit(lambda k, f: leapfrog.leapfrog_fwd(
            k, f, grid=g, history=solve != "forward")).lower(k, f).compile()
    assert KERNEL in compiled.as_text()
