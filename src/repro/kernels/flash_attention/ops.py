"""Jitted wrapper: dispatches to the Pallas kernel on TPU, ref elsewhere.

Handles padding (seq to block multiples, head dims to 128 lanes) and the
(B,S,H,d) <-> (B,H,S,d) transposes the kernel wants. The backward pass uses
the jnp reference via ``jax.custom_vjp`` (a flash recompute-style backward
kernel is future work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.kernel import flash_attention_fwd


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention_kernel_call(q, k, v, *, scale, causal=True, kv_len=None,
                                block_q=128, block_k=128, interpret=False):
    """(B,S,H,d)-layout entry point around the Pallas kernel."""
    B, Sq, H, dq = q.shape
    Skv, dv = k.shape[1], v.shape[-1]
    qt = _pad_to(_pad_to(q.transpose(0, 2, 1, 3), 2, block_q), 3, 128)
    kt = _pad_to(_pad_to(k.transpose(0, 2, 1, 3), 2, block_k), 3, 128)
    vt = _pad_to(_pad_to(v.transpose(0, 2, 1, 3), 2, block_k), 3, 128)
    eff_kv = Skv if kv_len is None else kv_len
    o = flash_attention_fwd(qt, kt, vt, scale=scale, causal=causal,
                            kv_len=eff_kv, block_q=block_q, block_k=block_k,
                            interpret=interpret)
    return o[:, :, :Sq, :dv].transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fa(q, k, v, scale, causal):
    return flash_attention_kernel_call(q, k, v, scale=scale, causal=causal)


def _fa_fwd(q, k, v, scale, causal):
    return _fa(q, k, v, scale, causal), (q, k, v)


def _fa_bwd(scale, causal, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: ref.attention_ref(q, k, v, scale=scale, causal=causal),
        q, k, v)
    return vjp(g)


_fa.defvjp(_fa_fwd, _fa_bwd)


CHUNK_THRESHOLD = 1024


def flash_attention(q, k, v, *, scale: float, causal: bool = True):
    """Public op: (B,Sq,H,dq) x (B,Skv,KV,dq) x (B,Skv,KV,dv) -> (B,Sq,H,dv)."""
    if _on_tpu():
        return _fa(q, k, v, scale, causal)
    if q.shape[1] > CHUNK_THRESHOLD:
        return ref.attention_ref_chunked(q, k, v, scale=scale, causal=causal)
    return ref.attention_ref(q, k, v, scale=scale, causal=causal)
