"""Tiny cells for the CPU: the real drivers, data files and readers at
sizes a test run holds."""
from __future__ import annotations

import copy

import jax

from chipbench import harness

CHAT = "tiny-chat"
# The entries that the PR adding the first serving cell declares in
# ``BENCHMARK.json``, with that cell's name in their ``workloads``.
CHAT_END_TO_END = [
    {"name": "serve_tok_s", "unit": "tok/s", "better": "higher",
     "source": "host_clock"},
    {"name": "itl_p90_ms", "unit": "ms", "better": "lower",
     "source": "host_clock"},
]
CHAT_PER_LAYER = [
    {"name": "serve_mfu", "unit": "%", "better": "higher",
     "source": "host_clock", "layer": "whole step", "moves": "serve_tok_s"},
    {"name": "prefill_exec_ms.chat", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "execution", "moves": "serve_tok_s"},
    {"name": "decode_exec_ms.chat", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "execution", "moves": "itl_p90_ms"},
    {"name": "decode_submit_ms.chat", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "runtime", "moves": "itl_p90_ms"},
    {"name": "deliver_ms.chat", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "serving", "moves": "itl_p90_ms"},
    {"name": "device_idle.chat", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "device", "moves": "itl_p90_ms"},
    {"name": "scan_roofline.chat", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernel", "moves": "serve_tok_s"},
]

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


def chat_cell(**traffic_overrides):
    """(spec, cell, config, traffic) of a serving cell of falcon-mamba's
    layer at tiny widths (``tests/data/tiny-mamba.json``) under the
    ``chat-azure-conv`` mix cut to a few slots and tokens. The spec
    declares the serving metrics for the cell, as the PR that adds a
    serving cell declares them."""
    sp = copy.deepcopy(harness.spec())
    sp["end_to_end"] += [dict(m, workloads=[CHAT]) for m in CHAT_END_TO_END]
    sp["per_layer"] += [dict(m, workloads=[CHAT]) for m in CHAT_PER_LAYER]
    c = {"name": CHAT, "config": "tiny-mamba", "traffic": "chat-azure-conv",
         "chips": 1}
    cfg = harness.load_json(harness.BENCH / "tests" / "data"
                            / "tiny-mamba.json")
    traffic = harness.load_json(harness.BENCH / "traffic"
                                / "chat-azure-conv.json")
    traffic.update(slots=4, prompt_len=24, output_len=6, check_requests=3)
    traffic.update(traffic_overrides)
    return sp, c, cfg, traffic


def run(workload: str, *, seed: int = 3, seconds: float = 2.0,
        trace: bool = False, control=False, **traffic_overrides) -> dict:
    from chipbench import run as runner
    if workload == CHAT:
        sp, c, cfg, traffic = chat_cell(**traffic_overrides)
    else:
        sp, c, cfg, traffic = cell(workload, **traffic_overrides)
    return runner.run_cell(sp, c, cfg, traffic, seed=seed, seconds=seconds,
                           trace=trace, devs=jax.devices()[:1], peaks=PEAKS,
                           control=control)
