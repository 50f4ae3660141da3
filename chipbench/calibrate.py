"""Readings that the limits of ``correct`` are set from; not part of a run.

    python3 chipbench/calibrate.py --workload <name> --seeds 1 2 3 ... [--seconds s]

In one process, for each seed: the cell's set-up and its timed path at the
cell's own size and load for a window of ``--seconds``, then the numbers
the run compares, from what the program served and from the driver's
control (the plain reference in the precision below the configuration's,
in the program's place), each judged as a run judges it, with the driver's notes
on each comparison. One JSON line per seed.

The readings of a witness (the program with a fault of its own mended,
``witness.py``) come from the same script run under it.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness                                # noqa: E402


def readings(drv, checks: dict, failed: int) -> dict:
    return {"correct": harness.judge(checks, failed),
            **{k: v for k, (v, _) in checks.items()},
            "notes": dict(drv.check_notes)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    import jax
    sp = harness.spec()
    cell, cfg, traffic = harness.cell_parts(sp, args.workload)
    devs = harness.chips(cell["chips"])
    harness.enable_compile_cache()
    driver = importlib.import_module(f"chipbench.drivers.{traffic['kind']}")
    for seed in args.seeds:
        t0 = time.perf_counter()
        drv = driver.Driver(cell, cfg, traffic, seed)
        with jax.default_device(devs[0]):
            drv.setup()
            drv.window(harness.Window(args.seconds), lambda: None)
            peak = harness.device_info(devs)["memory_peak_bytes"]
            drv.free()
            program = readings(drv, drv.check(), drv.failed)
            control = readings(drv, drv.check(control=True), 0)
        print(json.dumps({"seed": seed, "program": program,
                          "control": control,
                          "memory_peak_bytes": peak,
                          "seconds": time.perf_counter() - t0}), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
