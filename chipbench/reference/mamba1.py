"""Plain forward of a Mamba-1 language model as falcon-mamba publishes it,
and the seeded weights the benchmark serves it with.

Source: hf ``tiiuae/falcon-mamba-7b`` (``modeling_falcon_mamba.py``,
``config.json``) and arXiv:2410.05355. Per layer, with the residual ``h``
kept in float32::

    u = RMSNorm(h) * w_norm                      (eps layer_norm_epsilon)
    [x, z] = u W_in
    x = SiLU(causal depthwise conv_k(x) + b)
    [d, B, C] = x W_x
    d, B, C <- weightless RMSNorm of each        (eps mixer_rms_eps)
    Delta = softplus(d W_dt + b_dt),  A = -exp(A_log)
    s_t = exp(Delta_t A) * s_{t-1} + (Delta_t x_t) (outer) B_t
    y_t = s_t C_t + D * x_t
    h <- h + (y * SiLU(z)) W_out

then a final RMSNorm and the LM head (not tied to the embedding). The scan
is a ``lax.scan`` over time; no kernel, cache or batching trick, and every
product runs under ``jax.default_matmul_precision("highest")``.

Departures from the source:

- Weights are random, drawn from the seed (``layer_weights``,
  ``head_weights``): normal at 1/sqrt(fan in) for the projections, std 0.1
  (the source's ``initializer_range``) for the embedding, Mamba's
  initialisation for ``A_log`` (log 1..N), ``D`` (ones) and the dt bias
  (softplus^-1 of a log-uniform step in [1e-3, 1e-1]), uniform +-1/sqrt(k)
  for the convolution, ones for the norms.
- ``A_log``, ``D`` and the dt bias are held in float32, as the served
  program holds them; the source stores them in bfloat16. Every other weight
  is drawn in float32 and rounded to the configuration's dtype: those
  rounded values are the model, and the reference computes with them exactly.
- Weights are laid out (in, out), so a projection is ``u @ W``; the
  convolution's ``conv_w[j]`` multiplies ``x_{t-k+1+j}``, as the source's
  ``conv1d.weight[:, 0, j]`` does.

``logits(..., precision=...)`` also computes the same model in lower
precisions: ``"bfloat16"`` (the configuration's precision: bfloat16 weights
and activations, the scan and the residual in float32; its gap to float32
is the check's measure of what rounding alone costs) and ``"int8"`` (the
same with every weight matrix rounded to int8 per output channel: the
control one precision lower, put in the program's place). ``dt_bc_norm=False`` plants the fault of
a Mamba-1 layer that leaves out falcon-mamba's norms on dt, B and C.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# the precision below each configured one, for the control
CONTROL = {"bfloat16": "int8"}
# leaves held in float32 whatever the configuration's dtype
FLOAT32_LEAVES = ("dt_bias", "A_log", "D")
# matrices rounded per output channel by the int8 control, with the axis
# each output channel runs along (the contraction axis, or the row of a
# lookup table)
INT8_AXIS = {"in_proj": 0, "conv_w": 0, "x_proj": 0, "dt_proj": 0,
             "out_proj": 0, "lm_head": 0, "embedding": 1}


@dataclass(frozen=True)
class Dims:
    """The sizes the reference reads, under the source's config keys."""
    hidden_size: int
    intermediate_size: int
    state_size: int
    conv_kernel: int
    time_step_rank: int
    vocab_size: int
    num_hidden_layers: int
    layer_norm_epsilon: float
    mixer_rms_eps: float
    dtype: str

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        return cls(dtype=cfg["torch_dtype"], **{
            k: cfg[k] for k in cls.__dataclass_fields__ if k != "dtype"})


def seed_key(seed: int):
    """A key for any whole number (seeds pass 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _served(name: str, v, dims: Dims):
    return v.astype(F32 if name in FLOAT32_LEAVES else dims.dtype)


def layer_weights(key, dims: Dims, layer) -> dict:
    """Layer ``layer``'s weights, in the types they are served in."""
    d, di, n = dims.hidden_size, dims.intermediate_size, dims.state_size
    r, k = dims.time_step_rank, dims.conv_kernel
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1),
                                             layer), 7)

    def normal(kk, shape, fan_in):
        return jax.random.normal(kk, shape, F32) / math.sqrt(fan_in)

    log_dt = jax.random.uniform(ks[5], (di,), F32, math.log(1e-3),
                                math.log(1e-1))
    dt = jnp.exp(log_dt)
    bound = 1.0 / math.sqrt(k)
    w = {
        "norm": jnp.ones((d,), F32),
        "in_proj": normal(ks[0], (d, 2 * di), d),
        "conv_w": jax.random.uniform(ks[1], (k, di), F32, -bound, bound),
        "conv_b": jax.random.uniform(ks[2], (di,), F32, -bound, bound),
        "x_proj": normal(ks[3], (di, r + 2 * n), di),
        "dt_proj": normal(ks[4], (r, di), r),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),        # softplus^-1(dt)
        "A_log": jnp.broadcast_to(np.log(np.arange(1, n + 1,
                                                   dtype=np.float32)), (di, n)),
        "D": jnp.ones((di,), F32),
        "out_proj": normal(ks[6], (di, d), di),
    }
    return {name: _served(name, v, dims) for name, v in w.items()}


def head_weights(key, dims: Dims) -> dict:
    d, v = dims.hidden_size, dims.vocab_size
    k0, k1 = jax.random.split(jax.random.fold_in(key, 0))
    w = {"embedding": 0.1 * jax.random.normal(k0, (v, d), F32),
         "lm_head": jax.random.normal(k1, (d, v), F32) / math.sqrt(d),
         "final_norm": jnp.ones((d,), F32)}
    return {name: _served(name, x, dims) for name, x in w.items()}


@functools.partial(jax.jit, static_argnums=0)
def _all_weights(dims: Dims, key):
    # one layer at a time inside the call, so that only the served types
    # of all layers are ever held at once
    layers = jax.lax.map(lambda i: layer_weights(key, dims, i),
                         jnp.arange(dims.num_hidden_layers))
    return {**head_weights(key, dims), "layers": layers}


def weights(dims: Dims, seed: int) -> dict:
    """Every weight of the model, made on the device in one jitted call:
    the head's under their names, each layer's stacked under ``layers``."""
    return _all_weights(dims, seed_key(seed))


# ---------------------------------------------------------------- forward
def _rms(x, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _int8(w, axis: int):
    """``w`` rounded to int8 per output channel, as the values it stands
    for."""
    w = w.astype(F32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


class _Numerics:
    """What one precision rounds: activations between operations (``act``),
    and weights (``int8``). Within an operation, arithmetic is float32."""

    def __init__(self, precision: str):
        self.int8 = precision == "int8"
        self.act = F32 if precision == "float32" else jnp.bfloat16

    def w(self, name: str, v):
        if self.int8 and name in INT8_AXIS:
            v = _int8(v, INT8_AXIS[name])
        return v.astype(F32 if name in FLOAT32_LEAVES else self.act)

    def mm(self, a, w):
        return jnp.matmul(a.astype(self.act), w,
                          preferred_element_type=F32).astype(self.act)


def selective_scan(x, delta, A, B, C, D):
    """``lax.scan`` over time, in the inputs' dtype. x, delta: (n, T, di);
    B, C: (n, T, N); A: (di, N); D: (di,). Returns y (n, T, di)."""
    def step(s, inp):
        x_t, d_t, b_t, c_t = inp
        s = jnp.exp(d_t[..., None] * A) * s \
            + (d_t * x_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("bdn,bn->bd", s, c_t) + D * x_t

    s0 = jnp.zeros(x.shape[:1] + A.shape, jnp.result_type(x, delta, A, B))
    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (x, delta, B, C))
    _, y = jax.lax.scan(step, s0, seq)
    return jnp.moveaxis(y, 0, 1)


def causal_conv(x, w, b):
    """Depthwise causal convolution over time: x (n, T, di), w (k, di)."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[j].astype(F32) for j in range(k)) \
        + b.astype(F32)


def block(dims: Dims, w: dict, h, num: _Numerics, *, dt_bc_norm: bool):
    """One Mamba-1 layer on the residual ``h`` (n, T, d)."""
    di, n, r = dims.intermediate_size, dims.state_size, dims.time_step_rank
    w = {name: num.w(name, v) for name, v in w.items()}
    u = (_rms(h, dims.layer_norm_epsilon) * w["norm"].astype(F32)
         ).astype(num.act)
    xz = num.mm(u, w["in_proj"])
    x, z = xz[..., :di], xz[..., di:]
    x = jax.nn.silu(causal_conv(x, w["conv_w"], w["conv_b"])).astype(num.act)
    proj = num.mm(x, w["x_proj"])
    d, B, C = proj[..., :r], proj[..., r:r + n], proj[..., r + n:]
    if dt_bc_norm:
        d, B, C = (_rms(t, dims.mixer_rms_eps).astype(num.act)
                   for t in (d, B, C))
    delta = jax.nn.softplus(
        jnp.matmul(d.astype(num.act), w["dt_proj"],
                   preferred_element_type=F32) + w["dt_bias"])
    y = selective_scan(x.astype(F32), delta, -jnp.exp(w["A_log"]),
                       B.astype(F32), C.astype(F32), w["D"])
    g = (y * jax.nn.silu(z.astype(F32))).astype(num.act)
    out = jnp.matmul(g, w["out_proj"], preferred_element_type=F32)
    return h + out


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(dims, key, tokens, precision):
    emb = _Numerics(precision).w("embedding",
                                 head_weights(key, dims)["embedding"])
    return jnp.take(emb, tokens, axis=0).astype(F32)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _layer(dims, key, layer, h, precision, dt_bc_norm):
    return block(dims, layer_weights(key, dims, layer), h,
                 _Numerics(precision), dt_bc_norm=dt_bc_norm)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(dims, key, h, positions, precision):
    num = _Numerics(precision)
    hw = {k: num.w(k, v) for k, v in head_weights(key, dims).items()}
    x = jnp.take(h, positions, axis=1)
    x = (_rms(x, dims.layer_norm_epsilon)
         * hw["final_norm"].astype(F32)).astype(num.act)
    return jnp.matmul(x, hw["lm_head"], preferred_element_type=F32)


def logits(dims: Dims, seed: int, tokens, positions, *,
           precision: str = "float32", dt_bc_norm: bool = True):
    """Teacher-forced logits (n, len(positions), vocab), float32, of the
    rows of ``tokens`` (n, T) at ``positions``: one layer at a time, each
    layer's weights drawn again from the seed, so that only one layer's
    weights and the residual are on the device at once."""
    key = seed_key(seed)
    with jax.default_matmul_precision("highest"):
        h = _embed(dims, key, jnp.asarray(tokens, jnp.int32), precision)
        for layer in range(dims.num_hidden_layers):
            h = _layer(dims, key, layer, h, precision, dt_bc_norm)
        return _head(dims, key, h,
                     jnp.asarray(np.asarray(positions), jnp.int32), precision)
