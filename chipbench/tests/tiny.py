"""Tiny cells for the CPU: the real drivers, data files and readers at
sizes a test run holds."""
from __future__ import annotations

import copy

import jax

from chipbench import harness

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12,
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def cell(workload: str, **traffic_overrides):
    """(spec, cell, config, traffic) of ``workload`` cut to a tiny size."""
    sp = harness.spec()
    c, cfg, traffic = harness.cell_parts(sp, workload)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    # few receivers, none on the source cell, as at the real size
    cfg.update(nx=40, ny=12, nz=12, nt=80, n_receivers=6)
    traffic.update(iterations_per_inversion=3, events=2, min_start_misfit=2.0)
    traffic.update(traffic_overrides)
    return sp, c, cfg, traffic


def run(workload: str, *, seed: int = 3, seconds: float = 2.0,
        trace: bool = False, control: bool = False,
        **traffic_overrides) -> dict:
    from chipbench import run as runner
    sp, c, cfg, traffic = cell(workload, **traffic_overrides)
    return runner.run_cell(sp, c, cfg, traffic, seed=seed, seconds=seconds,
                           trace=trace, devs=jax.devices()[:1], peaks=PEAKS,
                           control=control)
