"""The runtime's own spans in the profiler trace of a traced run, and the
device's idle time split by the host layer that was open over it.

The program's tracer (``repro.obs.tracing``) opens a profiler host
annotation, ``emerald:<name>``, around each of its spans and phases; the
driver opens ``chipbench:at_iter`` around each iteration. This module reads
both from the ``.xplane.pb`` the traced run wrote (``run.TRACE_DIR``), on
every host thread, within the ``chipbench:window`` mark, and caches what it
found on the ``Observation``. A program that opens no such annotation
leaves nothing to read: every reader then returns None.

"Per iteration" divides by the ``chipbench:at_iter`` annotations that end
inside the mark; "per call" by the harness calls of one label that lie
wholly inside the traced window. Durations are clipped to the mark.
Device idle time is ``obs.trace.gaps``, already on the host clock; each
idle instant goes to the layer of the latest-started ``emerald:``
annotation open then on any host thread, or to ``unspanned`` where none is
open. The four layers therefore split exactly the idle time that
``device_idle.at`` measures.
"""
from __future__ import annotations

import bisect
import heapq
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from chipbench import trace_reduce

PREFIX = "emerald:"
ITER_MARK = "chipbench:at_iter"
MDSS = ("ship", "install", "d2h", "hash")
EXEC = ("exec",)
# every other emerald: name is the runtime's own path: submit, verify,
# materialize, drive, dispatch, place, reintegrate
LAYERS = ("mdss", "exec", "runtime", "unspanned")


def layer_of(name: str) -> str:
    if name in MDSS:
        return "mdss"
    if name in EXEC:
        return "exec"
    return "runtime"


@dataclass
class HostSpans:
    t0_ns: float                                   # the mark, trace clock
    t1_ns: float
    iterations: int
    # (name without the prefix, start ns, end ns)
    events: List[Tuple[str, float, float]] = field(default_factory=list)
    idle: Optional[Dict[str, float]] = None        # ns, by layer

    def clipped(self, names) -> float:
        """Nanoseconds of the named events inside the mark, summed."""
        return sum(min(b, self.t1_ns) - max(a, self.t0_ns)
                   for n, a, b in self.events
                   if n in names and b > self.t0_ns and a < self.t1_ns)


def read_xplane(path: str) -> Optional[HostSpans]:
    """The marked window's iterations and ``emerald:`` events; None where
    the trace holds no mark or no such event."""
    from jax.profiler import ProfileData
    win, ends, events = None, [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    events.append((name[len(PREFIX):], ev.start_ns,
                                   ev.start_ns + ev.duration_ns))
                elif name == ITER_MARK:
                    ends.append(ev.start_ns + ev.duration_ns)
                elif name == trace_reduce.WINDOW_MARK:
                    win = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if win is None:
        return None
    a0, a1 = win
    n = sum(1 for t in ends if a0 < t <= a1)
    events = [e for e in events if e[2] > a0 and e[1] < a1]
    if not events:
        return None
    return HostSpans(a0, a1, n, events)


def idle_split(spans, gaps, t0: float, t1: float) -> Dict[str, float]:
    """Idle time by layer. ``spans`` are ``(start, end, layer)`` on any
    threads, ``gaps`` ``(start, duration)`` of device idle time, all on one
    clock within ``[t0, t1]``: each idle instant goes to the layer of the
    latest-started span open then, or to ``unspanned``."""
    spans = sorted((max(a, t0), min(b, t1), lay) for a, b, lay in spans
                   if b > a and b > t0 and a < t1)
    points = sorted({t0, t1} | {a for a, _, _ in spans}
                    | {b for _, b, _ in spans})
    starts, segs = [], []                     # piecewise-constant layer
    heap, i = [], 0
    for a, b in zip(points, points[1:]):
        while i < len(spans) and spans[i][0] <= a:
            s0, s1, lay = spans[i]
            heapq.heappush(heap, (-s0, s1, lay))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        lay = heap[0][2] if heap else "unspanned"
        if segs and segs[-1][2] == lay:
            segs[-1][1] = b
        else:
            starts.append(a)
            segs.append([a, b, lay])
    out = dict.fromkeys(LAYERS, 0.0)
    for g0, d in gaps:
        g1 = g0 + d
        k = max(bisect.bisect_right(starts, g0) - 1, 0)
        while k < len(segs) and segs[k][0] < g1:
            a, b, lay = segs[k]
            if b > g0:
                out[lay] += min(b, g1) - max(a, g0)
            k += 1
    return out


def load(obs) -> Optional[HostSpans]:
    """The traced run's host spans, read once per Observation."""
    if obs.trace is None:
        return None
    if not hasattr(obs, "_host_spans"):
        from chipbench.run import TRACE_DIR
        try:
            path = trace_reduce.newest_xplane(str(TRACE_DIR))
        except FileNotFoundError:
            obs._host_spans = None
        else:
            obs._host_spans = read_xplane(path)
    return obs._host_spans


def span_ms(obs, names) -> Optional[float]:
    """Milliseconds of the named annotations per iteration."""
    hs = load(obs)
    if hs is None or hs.iterations == 0 \
            or not any(e[0] in names for e in hs.events):
        return None
    return 1e-6 * hs.clipped(names) / hs.iterations


def per_call_ms(obs, names, label: str) -> Optional[float]:
    """Milliseconds of the named annotations in the traced window per
    ``label`` call wholly inside it."""
    hs = load(obs)
    calls = len(obs.calls_of(label, traced=True))
    if hs is None or calls == 0 \
            or not any(e[0] in names for e in hs.events):
        return None
    return 1e-6 * hs.clipped(names) / calls


def idle_ms(obs, layer: str) -> Optional[float]:
    """Device idle milliseconds per iteration spent under ``layer``."""
    hs = load(obs)
    tr = obs.trace
    if hs is None or hs.iterations == 0 or tr.n_devices == 0:
        return None
    if hs.idle is None:
        hs.idle = idle_split(
            [(a, b, layer_of(n)) for n, a, b in hs.events], tr.gaps,
            hs.t0_ns, hs.t1_ns)
    return 1e-6 * hs.idle[layer] / tr.n_devices / hs.iterations
