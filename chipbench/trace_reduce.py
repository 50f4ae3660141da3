"""The profiler trace of a run reduced to what the per-layer metrics read.

The harness traces part of its window with ``jax.profiler`` and marks the
traced part with a host annotation, ``chipbench:window``. This module
reads the ``.xplane.pb`` the profiler wrote (``jax.profiler.ProfileData``,
nothing else) and gives, within that mark:

* ``busy_s``: the union of the intervals in which an operation ran on the
  device, averaged over the devices traced; ``window_s``, the mark's span;
* each device operation's self time (its duration less that of the
  operations nested in it) and count, by HLO instruction name, with the
  event's full text (the HLO instruction, which names a Pallas kernel's
  ``custom_call_target``) and its stats as a description to match on;
* the idle gaps between device operations, each with its start, so that
  ``breakdown`` can name it by what the host was doing then.

Device planes are those named ``/device:<ACCEL>:<n>``; their operations are
the events of the line ``XLA Ops``. A TPU's clock in the trace runs about a
millisecond and a half from the host's (a recorded v5e trace), so each
device plane is shifted onto the host clock: its k-th program (``XLA
Modules``) cannot start before the host launched the k-th execution
(``tpu::System::Execute``), and the tightest such bound is taken. Where the
two counts differ the plane is left as it is.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW_MARK = "chipbench:window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_LAUNCH = "tpu::System::Execute"


@dataclass
class Summary:
    window_s: float
    busy_s: float
    n_devices: int
    t0_ns: float                                   # mark start, trace clock
    op_self_s: Dict[str, float] = field(default_factory=dict)
    op_count: Dict[str, int] = field(default_factory=dict)
    op_desc: Dict[str, str] = field(default_factory=dict)
    gaps: List[Tuple[float, float]] = field(default_factory=list)  # ns
    perf0: float = 0.0          # host perf_counter at the mark's start
    t0: float = 0.0             # traced window on the host perf_counter
    t1: float = 0.0

    def matching(self, patterns) -> Tuple[int, float]:
        """(count, self seconds) of device ops whose name or description
        holds any of ``patterns``."""
        n, s = 0, 0.0
        for name, secs in self.op_self_s.items():
            text = name + " " + self.op_desc.get(name, "")
            if any(p in text for p in patterns):
                n += self.op_count[name]
                s += secs
        return n, s


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def _self_times(events):
    """Self time of each (name, start, dur) event: nested events (one
    inside another on the same line) are subtracted from their parent."""
    out = []
    stack = []                      # [name, start, end, child_time]
    for name, a, d in sorted(events, key=lambda e: (e[1], -e[2])):
        b = a + d
        while stack and stack[-1][2] <= a:
            out.append(stack.pop())
        if stack:
            stack[-1][3] += min(b, stack[-1][2]) - a
        stack.append([name, a, b, 0.0])
    out += stack
    return [(n, (b - a) - c) for n, a, b, c in out]


def _desc(ev) -> str:
    parts = []
    for k, v in ev.stats:
        if isinstance(v, bytes):
            v = v[:4000].decode(errors="replace")
        if isinstance(v, str):
            parts.append(f"{k}={v[:4000]}")
    return " ".join(parts)


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name \
        and "CUSTOM" not in plane_name and "NON_CORE" not in plane_name


def reduce(path: str, mark: str = WINDOW_MARK) -> Summary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    win = None
    launches = []
    dev_lines = []                     # (ops line, skew of its plane)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == mark:
                        win = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name == HOST_LAUNCH:
                        launches.append(ev.start_ns)
        elif _is_device(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                starts = [ev.start_ns for ev in lines[MODULES_LINE].events] \
                    if MODULES_LINE in lines else []
                dev_lines.append((lines[OPS_LINE], starts))
    launches.sort()
    dev_lines = [(line, min((d - h for d, h in zip(sorted(st), launches)),
                            default=0.0) if len(st) == len(launches) else 0.0)
                 for line, st in dev_lines]
    if win is None:
        raise ValueError(f"no host event {mark!r} in {path}")
    a0, a1 = win
    s = Summary(window_s=(a1 - a0) * 1e-9, busy_s=0.0,
                n_devices=len(dev_lines), t0_ns=a0)
    busy = 0.0
    for line, skew in dev_lines:
        evs = []
        for ev in line.events:
            a, d = ev.start_ns - skew, ev.duration_ns
            if a + d <= a0 or a >= a1:
                continue
            a, b = max(a, a0), min(a + d, a1)
            name = ev.name.split(" = ", 1)[0]
            evs.append((name, a, b - a))
            if name not in s.op_desc:
                s.op_desc[name] = ev.name + " " + _desc(ev)
        busy += _union((a, a + d) for _, a, d in evs)
        for name, secs in _self_times(evs):
            s.op_self_s[name] = s.op_self_s.get(name, 0.0) + secs * 1e-9
            s.op_count[name] = s.op_count.get(name, 0) + 1
        end = a0
        for _, a, d in sorted(evs, key=lambda e: e[1]):
            if a > end:
                s.gaps.append((end, a - end))
            end = max(end, a + d)
        if a1 > end:
            s.gaps.append((end, a1 - end))
    s.busy_s = busy * 1e-9 / max(len(dev_lines), 1)
    return s


def breakdown(s: Summary, calls, spans, wall_minus_perf: float,
              top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps named by what the host was doing in them: the harness call
    (``chipbench:<label>``) and the innermost runtime span open then."""
    ops = sorted(s.op_self_s.items(), key=lambda kv: -kv[1])[:top]

    def host_at(t_ns: float) -> str:
        p = s.perf0 + (t_ns - s.t0_ns) * 1e-9
        label = next((c.label for c in calls if c.t0 <= p <= c.t1), "")
        inner = None
        for sp in spans:
            a = sp.t0_wall - wall_minus_perf
            if a <= p <= a + sp.dur_s and (inner is None
                                           or sp.dur_s < inner.dur_s):
                inner = sp
        name = label or "harness"
        if inner is not None:
            step = inner.attrs.get("step")
            name += f"/{inner.name}" + (f":{step}" if step else "")
        return name

    gaps = sorted(s.gaps, key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, v] for n, v in ops],
            "idle_gaps": [[host_at(a + d / 2), d * 1e-9] for a, d in gaps]}
