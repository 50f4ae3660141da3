"""Adjoint Tomography — the paper's evaluation application (§4), for real.

A 3D acoustic wave-equation solver (2nd-order leapfrog finite differences,
``kernels/leapfrog``) plus the four AT steps from the paper. The solver
takes one of two paths, chosen from its input: on a TPU in float32, two
Pallas kernels that keep the wavefields in VMEM for the whole time loop,
the forward solve and its discrete adjoint, joined by a ``custom_vjp``;
elsewhere (the CPU, float64, meshes too large for VMEM) a ``lax.scan``
over timesteps with rematerialization. The steps:

  1. build starting model, compute synthetic seismograms       (local)
  2. misfit between synthetics and observations                (remotable)
  3. Fréchet kernel — gradient of misfit w.r.t. the model      (remotable)
     (the "adjoint" computation: the adjoint-state method, run by the
     adjoint kernel on the VMEM path and by reverse-mode AD through the
     scan on the XLA path)
  4. model update                                              (remotable)

Steps 2–4 carry the paper's ``remotable`` annotation; iterating the
workflow "until the seismograms match wiggle by wiggle" is the driver loop
in ``examples/adjoint_tomography.py``. Mesh sizes used by the paper's
figures — 104x23x24 (Fig 11) and 208x44x46 (Fig 12) — are both supported;
benchmarks default to scaled-down time axes so CPU runs stay snappy.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.workflow import Workflow
from repro.kernels import leapfrog


@dataclass(frozen=True)
class ATConfig:
    nx: int = 104
    ny: int = 23
    nz: int = 24
    nt: int = 200
    dx: float = 100.0          # m
    dt: float = 0.008          # s  (CFL: c*dt/dx <= 1/sqrt(3))
    c0: float = 3000.0         # background velocity m/s
    f0: float = 4.0            # Ricker peak frequency, Hz
    n_receivers: int = 16
    lr: float = 0.4            # model-update step (normalized gradient)

    @property
    def mesh_name(self) -> str:
        return f"{self.nx}x{self.ny}x{self.nz}"


FIG11 = ATConfig(nx=104, ny=23, nz=24)
FIG12 = ATConfig(nx=208, ny=44, nz=46)


# ---------------------------------------------------------------------------
# Wave physics
# ---------------------------------------------------------------------------

def _ricker(cfg: ATConfig) -> jnp.ndarray:
    t = jnp.arange(cfg.nt) * cfg.dt - 1.0 / cfg.f0
    a = (math.pi * cfg.f0) ** 2 * t ** 2
    return (1 - 2 * a) * jnp.exp(-a)


def _receiver_idx(cfg: ATConfig) -> Tuple[jnp.ndarray, int, int]:
    xs = jnp.linspace(4, cfg.nx - 5, cfg.n_receivers).astype(jnp.int32)
    return xs, cfg.ny // 2, 2


@partial(jax.jit, static_argnums=(1,))
def simulate(c: jnp.ndarray, cfg: ATConfig) -> jnp.ndarray:
    """Leapfrog acoustic FD; returns seismograms (nt, n_receivers)."""
    return leapfrog.seismograms(
        c, _ricker(cfg), dt=cfg.dt, dx=cfg.dx,
        source=(cfg.nx // 2, cfg.ny // 2, 2), receivers=_receiver_idx(cfg))


def starting_model(cfg: ATConfig) -> jnp.ndarray:
    return jnp.full((cfg.nx, cfg.ny, cfg.nz), cfg.c0)


def true_model(cfg: ATConfig) -> jnp.ndarray:
    """Twin-experiment target: background + two gaussian velocity anomalies."""
    x, y, z = jnp.meshgrid(jnp.arange(cfg.nx), jnp.arange(cfg.ny),
                           jnp.arange(cfg.nz), indexing="ij")

    def blob(cx, cy, cz, r, amp):
        d2 = ((x - cx) ** 2 + (y - cy) ** 2 + (z - cz) ** 2) / r ** 2
        return amp * jnp.exp(-d2)

    c = starting_model(cfg)
    c = c + blob(cfg.nx * 0.35, cfg.ny * 0.5, cfg.nz * 0.5, cfg.nx * 0.08, 250.0)
    c = c - blob(cfg.nx * 0.7, cfg.ny * 0.4, cfg.nz * 0.6, cfg.nx * 0.06, 200.0)
    return c


# ---------------------------------------------------------------------------
# The four AT steps (paper §4), as workflow step functions. Each closure
# bears its step's name, which jit gives its program (``jit_forward``...).
# ---------------------------------------------------------------------------

def step_forward(cfg: ATConfig):
    def forward(model):
        return {"syn": simulate(model, cfg)}
    return forward


def step_misfit(cfg: ATConfig):
    def misfit(syn, obs):
        r = syn - obs
        return {"chi": 0.5 * jnp.sum(r * r)}
    return misfit


def step_kernel(cfg: ATConfig):
    def kernel(model, obs):
        def chi_of(m):
            r = simulate(m, cfg) - obs
            return 0.5 * jnp.sum(r * r)
        return {"grad": jax.grad(chi_of)(model)}
    return kernel


def step_update(cfg: ATConfig):
    def update(model, grad):
        g = grad / (jnp.max(jnp.abs(grad)) + 1e-20)
        return {"model": model - cfg.lr * g * 20.0}
    return update


def _sim_flops(cfg: ATConfig) -> float:
    return float(cfg.nx * cfg.ny * cfg.nz) * cfg.nt * 15.0


def build_workflow(cfg: ATConfig, *, remotable=(2, 3, 4)) -> Workflow:
    """One AT iteration as an Emerald workflow (paper: steps 2–4 remotable)."""
    wf = Workflow(f"AT-{cfg.mesh_name}")
    wf.var("model").var("obs")
    n = cfg.nx * cfg.ny * cfg.nz
    wf.step("forward", step_forward(cfg), inputs=("model",), outputs=("syn",),
            remotable=1 in remotable, flops_hint=_sim_flops(cfg),
            bytes_hint=8.0 * n)
    wf.step("misfit", step_misfit(cfg), inputs=("syn", "obs"),
            outputs=("chi",), remotable=2 in remotable,
            flops_hint=3.0 * cfg.nt * cfg.n_receivers, bytes_hint=8.0)
    wf.step("kernel", step_kernel(cfg), inputs=("model", "obs"),
            outputs=("grad",), remotable=3 in remotable,
            flops_hint=3.0 * _sim_flops(cfg), bytes_hint=8.0 * n)
    wf.step("update", step_update(cfg), inputs=("model", "grad"),
            outputs=("model",), remotable=4 in remotable,
            flops_hint=4.0 * n, bytes_hint=8.0 * n)
    return wf


def make_observations(cfg: ATConfig) -> jnp.ndarray:
    return simulate(true_model(cfg), cfg)
