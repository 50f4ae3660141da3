"""Operations and bytes the algorithms need, from shapes alone.

These are the numerators of the benchmark's roofline and utilisation
shares. They count the work the algorithm requires, never what a compiled
program happens to do, so a change that drops work cannot read as more
efficient. Each function states its model.
"""
from __future__ import annotations


def at_iteration_bytes(nx: int, ny: int, nz: int, nt: int,
                       n_receivers: int, itemsize: int = 4) -> float:
    """Compulsory HBM bytes of one inversion iteration.

    What no schedule of the adjoint-state method can avoid moving: the
    model read once and the updated model written once, the observed
    seismograms (nt x n_receivers) read once, and the forward wavefield
    that the adjoint pass needs, one field per time step, written once and
    read once. Every intermediate field of a leapfrog step is taken to stay
    on the chip, so a program that keeps its working fields in VMEM across
    steps still reads at or under 100%.
    """
    field = nx * ny * nz * itemsize
    return float(2 * field + nt * n_receivers * itemsize + 2 * nt * field)


def mamba1_token_flops(cfg: dict, *, head: bool) -> float:
    """Model FLOPs of one token through a Mamba-1 LM (source config keys).

    Per layer: 2 x the matmul parameters (``in_proj`` d x 2di, ``x_proj``
    di x (r + 2N), ``dt_proj`` r x di, ``out_proj`` di x d), 2 k di for the
    depthwise convolution, and 6 di N for the scan: ``Delta A``, its
    exponential times the state, ``(Delta x) B``, the sum, the product with
    ``C`` and its sum over N, each counted once per state element. Norms,
    activations and the gate are left out, so the count is a floor. With
    ``head``, the LM head's 2 d V.
    """
    d, di = cfg["hidden_size"], cfg["intermediate_size"]
    n, r, k = cfg["state_size"], cfg["time_step_rank"], cfg["conv_kernel"]
    matmul = d * 2 * di + di * (r + 2 * n) + r * di + di * d
    layer = 2 * matmul + 2 * k * di + 6 * di * n
    total = cfg["num_hidden_layers"] * layer
    if head:
        total += 2 * d * cfg["vocab_size"]
    return float(total)
