"""Witnesses: the program with falcon-mamba's numerics put into its Mamba-1
layer, for the readings the serving check's limit is set from. Never part
of a benchmark run.

The program's layer leaves out the weightless RMS norms (eps
``mixer_rms_eps``, 1e-6) on dt, B and C, and keeps the residual stream in
the activation dtype where the source keeps it in float32
(``residual_in_fp32``). Each witness patches the program's own functions
in place, through ``setattr`` (``monkeypatch.setattr`` in a test):

- ``faithful``: the norms and a float32 residual, as the source states;
  the norms' outputs and each layer's norm are cast to the activation
  dtype, as the source casts them before its projections;
- ``bf16_residual``: the norms alone, the residual left in bfloat16 (the
  second of the program's departures, planted in a program that is
  otherwise faithful).

    python3 chipbench/witness.py <witness> chipbench/calibrate.py --workload ...

runs a benchmark script with the witness installed.
"""
from __future__ import annotations

import runpy
import sys
from pathlib import Path

MIXER_RMS_EPS = 1e-6


def _norms(setattr):
    import jax
    import jax.numpy as jnp
    from repro.models import mamba

    def rms(t):
        t = t.astype(jnp.float32)
        return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                 + MIXER_RMS_EPS)

    def dt_bc(cfg, p, x):
        r, n = cfg.dt_rank_, cfg.ssm_state
        proj = x @ p["x_proj"]
        dt_r, b, c = (rms(t) for t in (proj[..., :r], proj[..., r:r + n],
                                       proj[..., r + n:]))
        dt = jax.nn.softplus(dt_r @ p["dt_proj"].astype(jnp.float32)
                             + p["dt_bias"])
        return dt, b.astype(x.dtype), c.astype(x.dtype)

    setattr(mamba, "_dt_bc", dt_bc)


def _float32_residual(setattr):
    """The residual stream in float32: the embedding comes out in float32,
    every norm on the stream returns the activation dtype, and each block's
    ``x + a`` promotes to float32."""
    import jax.numpy as jnp
    from repro.models import layers
    from repro.models import transformer as tfm

    def embed(cfg, p, tokens, rules):
        return layers.embed(cfg, p, tokens, rules).astype(jnp.float32)

    def rmsnorm(cfg, p, x):
        return layers.rmsnorm(cfg, p, x).astype(layers.adt(cfg))

    setattr(tfm, "embed", embed)
    setattr(tfm, "rmsnorm", rmsnorm)


def faithful(setattr=setattr):
    _norms(setattr)
    _float32_residual(setattr)


def bf16_residual(setattr=setattr):
    _norms(setattr)


WITNESSES = {"faithful": faithful, "bf16_residual": bf16_residual}


def main() -> None:
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    name, script = sys.argv[1], sys.argv[2]
    WITNESSES[name]()
    sys.argv = sys.argv[2:]
    runpy.run_path(script, run_name="__main__")


if __name__ == "__main__":
    main()
