"""What every cell shares: the spec, the compile cache, the device, the
clock, the per-layer metric readers and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found here by the name that
``BENCHMARK.json`` gives it:

    configs/<config>.json      sizes, source, cut, limits of the check
    traffic/<traffic>.json     parameters read by drivers/<kind>.py
    metrics/<metric>.py        ``read(obs) -> float | None``
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_parts(sp: dict, workload: str):
    """(cell, config file, traffic file) of ``workload``."""
    cells = {w["name"]: w for w in sp["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in sp["configs"]}[cell["config"]]
    cfg = load_json(ROOT / conf["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return cell, cfg, traffic


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` says. Every program is persisted, however
    quickly it compiled, so that a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA programs compiled, and those loaded from the persistent
    cache. JAX times a load from the cache as a backend compile, so a
    compile is a request that no cache hit answered."""

    def __init__(self):
        from jax import monitoring
        self.requests = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    @property
    def compiles(self) -> int:
        return self.requests - self.cache_hits

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def chips(n: int) -> list:
    """The first ``n`` accelerator devices; NoChip where there are fewer."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator, only the CPU")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_info(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(table)}")
    return table[kind]


class Window:
    """The measured window on the host clock."""

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + self.seconds

    def open(self) -> bool:
        return time.perf_counter() < self.t_end

    def within(self, t: float) -> bool:
        return self.t0 <= t <= self.t_end


@dataclass
class Call:
    """One call the harness timed around the program's entry."""
    label: str
    t0: float
    t1: float
    run_id: str = ""

    @property
    def wall(self) -> float:
        return self.t1 - self.t0


@dataclass
class Observation:
    """What a traced run hands to the per-layer metric readers."""
    cell: dict
    config: dict
    traffic: dict
    window: Window
    calls: List[Call] = field(default_factory=list)
    spans: list = field(default_factory=list)      # the runtime's Span list
    trace: Any = None                               # trace_reduce.Summary
    peaks: Dict[str, float] = field(default_factory=dict)
    work: Dict[str, Any] = field(default_factory=dict)

    def calls_of(self, label: str, traced: bool = False) -> List[Call]:
        out = [c for c in self.calls if c.label == label
               and self.window.within(c.t1)]
        if traced and self.trace is not None:
            out = [c for c in out if self.trace.t0 <= c.t0
                   and c.t1 <= self.trace.t1]
        return out

    def _spans_of(self, run_id: str, names) -> list:
        if not hasattr(self, "_by_run"):
            self._by_run = {}
            for s in self.spans:
                self._by_run.setdefault(s.trace_id, []).append(s)
        return [s for s in self._by_run.get(run_id, ()) if s.name in names]

    def span_seconds(self, run_id: str, names) -> float:
        """Summed duration of a run's spans (work on concurrent lanes adds)."""
        return sum(s.dur_s for s in self._spans_of(run_id, names))

    def span_cover(self, run_id: str, names) -> float:
        """Seconds of wall time covered by at least one of a run's spans."""
        total, end = 0.0, float("-inf")
        for a, b in sorted((s.t0_wall, s.t0_wall + s.dur_s)
                           for s in self._spans_of(run_id, names)):
            if b > end:
                total += b - max(a, end)
                end = b
        return total


def read_metrics(obs: Observation, per_layer: List[dict]) -> dict:
    """Run each per-layer reader this cell lists; a reader that finds
    nothing returns None and its metric is left out."""
    out = {}
    for m in per_layer:
        if obs.cell["name"] not in m.get("workloads", [obs.cell["name"]]):
            continue
        path = BENCH / "metrics" / f"{m['name']}.py"
        mod_spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        value = mod.read(obs)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(checks: Dict[str, tuple], failed: int) -> bool:
    """``correct``: something was compared, nothing failed, and every
    compared number is within its limit."""
    return bool(checks) and failed == 0 and all(
        v <= lim for v, lim in checks.values())


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checks: Dict[str, tuple], breakdown=None,
         notes: Optional[dict] = None):
    """The result: diagnostics and each compared number beside its limit
    on standard error, then one JSON line, last, on standard output."""
    for k, v in (notes or {}).items():
        print(f"note {k}: {v}", file=sys.stderr)
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    print(json.dumps(line), flush=True)
