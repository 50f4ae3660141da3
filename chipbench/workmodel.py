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
