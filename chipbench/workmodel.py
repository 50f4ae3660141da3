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


DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def mamba1_scan_bytes(cfg: dict, batch: int, length: int) -> float:
    """Compulsory HBM bytes of the selective scan over one prefill call of
    ``batch`` sequences of ``length`` positions, through every layer.

    Per layer: the convolved input ``x``, the step ``Delta`` (batch x
    length x di each) and ``B`` and ``C`` (batch x length x N each) read
    once, the output ``y`` (batch x length x di) written once, all at the
    configuration's activation width; the state read once (``h0``) and
    written once (``h_last``), batch x di x N in float32; ``A`` (di x N)
    and ``D`` (di) read once, in float32. The state of every step stays on
    the chip, and ``Delta`` is counted at the activation width though a
    program may keep it in float32, so the count is a floor: a kernel's
    share of its roofline by these bytes cannot pass 100%.
    """
    di, n = cfg["intermediate_size"], cfg["state_size"]
    act = DTYPE_BYTES[cfg["torch_dtype"]]
    tokens = batch * length
    layer = (act * tokens * (3 * di + 2 * n)
             + 4 * (2 * batch * di * n + di * n + di))
    return float(cfg["num_hidden_layers"] * layer)
