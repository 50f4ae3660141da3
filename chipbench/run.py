"""Run one benchmark cell once, on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name through
``BENCHMARK.json``; ``drivers/<traffic kind>.py`` builds the program from
the configuration, warms every shape the traffic uses (set-up), drives it
for ``--seconds`` (the window), frees it, and compares what it served with
the plain reference. ``--trace 1`` traces the window with the profiler and
reports the per-layer metrics instead of the end-to-end ones.

Without an accelerator, or with fewer chips than the cell asks for, it
exits with code 3 and prints no result. The last line of standard output
is the result, as one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import importlib                                             # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
from pathlib import Path                                     # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness                                # noqa: E402
from chipbench import trace_reduce                           # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


class Profiler:
    """Traces the first ``seconds`` of the window (all of it at most)."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.active = False

    def start(self):
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        self.mark = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_MARK)
        self.mark.__enter__()
        self.t0 = time.perf_counter()
        self.active = True

    def tick(self):
        if self.active and time.perf_counter() >= self.t0 + self.seconds:
            self.stop()

    def stop(self):
        if not self.active:
            return
        import jax
        self.t1 = time.perf_counter()
        self.mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def summary(self) -> trace_reduce.Summary:
        s = trace_reduce.reduce(trace_reduce.newest_xplane(str(TRACE_DIR)))
        s.perf0, s.t0, s.t1 = self.t0, self.t0, self.t1
        return s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sp = harness.spec()
    cell, cfg, traffic = harness.cell_parts(sp, args.workload)
    try:
        devs = harness.chips(cell["chips"])
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    pk = harness.peaks(devs[0].device_kind)
    cache = harness.enable_compile_cache()
    result = run_cell(sp, cell, cfg, traffic, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devs=devs, peaks=pk)
    result["notes"]["compile_cache"] = cache
    harness.emit(**result)
    return 0


def run_cell(sp, cell, cfg, traffic, *, seed: int, seconds: float,
             trace: bool, devs, peaks: dict, control: bool = False) -> dict:
    """Set-up, window, per-layer or end-to-end metrics, and the check, on
    ``devs``; returns the keyword arguments of ``harness.emit``.

    ``control`` judges the driver's control (the plain reference in a
    lower precision, in the program's place) instead of what the program
    served; the benchmark's own runs never set it."""
    import jax
    compiles = harness.CompileCounter()
    driver = importlib.import_module(f"chipbench.drivers.{traffic['kind']}")
    drv = driver.Driver(cell, cfg, traffic, seed)
    with jax.default_device(devs[0]):
        drv.setup()
        setup_s = time.perf_counter() - T_START
        c_setup = compiles.compiles
        prof = Profiler(traffic.get("trace_seconds", seconds)) \
            if trace else None
        if prof:
            prof.start()
        win = harness.Window(seconds)
        drv.window(win, prof.tick if prof else (lambda: None))
        if prof:
            prof.stop()
        c_window = compiles.compiles - c_setup
        device = harness.device_info(devs)
        e2e = drv.end_to_end(win)
        obs = harness.Observation(cell=cell, config=cfg, traffic=traffic,
                                  window=win, calls=drv.calls,
                                  spans=drv.spans(), peaks=peaks,
                                  work=drv.work())
        drv.free()
        t_check = time.perf_counter()
        checks = drv.check(control=control)
        check_s = time.perf_counter() - t_check

    name = cell["name"]
    breakdown = None
    if prof:
        from repro.obs.tracing import wall_of
        obs.trace = prof.summary()
        metrics = harness.read_metrics(obs, sp["per_layer"])
        device["busy_s"] = obs.trace.busy_s
        device["window_s"] = obs.trace.window_s
        breakdown = trace_reduce.breakdown(obs.trace, obs.calls, obs.spans,
                                           wall_of(0.0))
    else:
        metrics = {}
        for m in sp["end_to_end"]:
            if name not in m.get("workloads", [name]):
                continue
            v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    notes = {"setup_s": setup_s, "compiles_in_setup": c_setup,
             "compiles_in_window": c_window,
             "cache_hits": compiles.cache_hits,
             "memory_peak_bytes": device["memory_peak_bytes"],
             "calls_in_window": len(obs.calls_of(drv.labels[-1])),
             "check_s": check_s, **drv.check_notes}
    return {"correct": harness.judge(checks, drv.failed),
            "attempted": drv.attempted(win), "failed": drv.failed,
            "metrics": metrics, "device": device, "checks": checks,
            "breakdown": breakdown, "notes": notes}


if __name__ == "__main__":
    sys.exit(main())
