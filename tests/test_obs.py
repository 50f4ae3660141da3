"""Observability layer: tracing, metrics registry, introspection, events.

Acceptance surface of the telemetry PR:

  * span context survives a REAL fabric round-trip — the worker
    subprocess's recv/exec/send phases come back as spans whose ancestry
    reaches the driver-side dispatch span,
  * ``introspect()`` is serially consistent under concurrent tenants —
    a step is never simultaneously in-flight and completed, and
    completion is absorbing across repeated snapshots,
  * the Chrome trace-event export is structurally valid (X events with
    microsecond ts/dur, M metadata naming every track, explicit
    parent_id linkage in args),
  * previously-orphaned counters (broker.tasks_cancelled, warm/idle
    worker counts, MDSS eviction bytes) surface in the metrics snapshot,
  * every ``emit(`` call site in src/ uses a kind registered in
    EVENT_SCHEMA (lint), and events carry a cross-process-comparable
    wall timestamp next to the monotonic one,
  * ``telemetry=False`` turns the whole layer into no-ops.
"""
import json
import os
import re
import threading
import time

import numpy as np
import pytest

from repro.core import (CostModel, EmeraldRuntime, MDSS, MigrationManager,
                        Workflow, default_tiers)
from repro.obs.events import EVENT_SCHEMA, validate_event
from repro.obs.introspect import render
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracing import Tracer, chrome_trace, wall_now

SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def emerald():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    return MigrationManager(tiers, mdss, cm)


def sleeper(out, seconds=0.0):
    def fn(**kw):
        (val,) = kw.values()
        if seconds:
            time.sleep(seconds)
        return {out: np.float64(float(val) + 1.0)}
    return fn


def chain_wf(name, depth, step_s=0.0):
    wf = Workflow(name)
    wf.var("x")
    src = "x"
    for i in range(depth):
        out = f"y{i + 1}"
        wf.step(f"s{i + 1}", sleeper(out, step_s), inputs=(src,),
                outputs=(out,), remotable=True, jax_step=False)
        src = out
    return wf


# ------------------------------------------------------------- tracer unit
def test_tracer_tls_parenting_and_ctx():
    tr = Tracer()
    with tr.span("outer", track="t") as outer:
        assert tr.current_ctx() == outer.ctx
        with tr.span("inner", track="t") as inner:
            assert inner.span.parent_id == outer.span.span_id
        # explicit parent overrides TLS
        with tr.span("routed", parent=("tid", 99)) as routed:
            assert routed.span.parent_id == 99
            assert routed.span.trace_id == "tid"
    assert tr.current_ctx() is None
    names = {s.name for s in tr.spans()}
    assert names == {"outer", "inner", "routed"}


def test_tracer_attach_propagates_to_helper_thread():
    tr = Tracer()
    got = {}
    with tr.span("dispatch") as d:
        ctx = d.ctx

        def helper():
            with tr.attach(ctx):
                with tr.span("child") as c:
                    got["parent"] = c.span.parent_id
        t = threading.Thread(target=helper)
        t.start()
        t.join()
    assert got["parent"] == ctx[1]


def test_tracer_ring_is_bounded_and_counts_drops():
    tr = Tracer(cap=4)
    for i in range(10):
        tr.add_span("t", f"s{i}", wall_now(), 0.0)
    assert len(tr.spans()) == 4
    assert tr.dropped == 6
    assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("x") as sp:
        assert sp.ctx is None
    assert tr.add_span("t", "x", 0.0, 0.0) is None
    assert tr.spans() == [] and tr.current_ctx() is None


# ------------------------------------------------------------ metrics unit
def test_metrics_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.inc("a.count")
    reg.inc("a.count", 4)
    reg.gauge("a.gauge", lambda: 7)
    reg.gauge("a.bad", lambda: 1 / 0)          # sampling never throws
    reg.observe("a.hist", 0.003)
    reg.observe("a.hist", 99.0)
    snap = reg.snapshot()
    assert snap["a.count"] == 5
    assert snap["a.gauge"] == 7
    assert snap["a.bad"] is None
    h = snap["a.hist"]
    assert h["count"] == 2 and h["min"] == 0.003 and h["max"] == 99.0
    assert h["buckets"]["+inf"] == 1
    # last-wins gauge re-registration (idempotent attach_fabric wiring)
    reg.gauge("a.gauge", lambda: 8)
    assert reg.snapshot()["a.gauge"] == 8


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    reg.inc("x")
    reg.observe("y", 1.0)
    assert reg.snapshot() == {}


# ------------------------------------------------- fabric span round-trip
def test_worker_spans_parent_under_driver_dispatch():
    """Acceptance: a registry step through a real worker subprocess comes
    back with recv/exec/send child spans whose ancestry chain reaches the
    driver-side dispatch span of the same trace."""
    Fabric = pytest.importorskip("repro.cloud").Fabric
    wf = Workflow("traced")
    wf.var("x")
    wf.step("grow", None, inputs=("x",), outputs=("y",), remotable=True,
            jax_step=False, remote_impl="add_one")
    with Fabric(workers=1) as fabric:
        with EmeraldRuntime(emerald(), max_workers=2) as rt:
            rt.attach_fabric(fabric)
            h = rt.submit(wf, {"x": np.float64(4.0)})
            assert float(h.result(60)["y"]) == 5.0
            spans = rt.tracer.spans(h.trace_id)
            by_id = {s.span_id: s for s in spans}
            worker = [s for s in spans if s.track.startswith("worker:")]
            assert {s.name for s in worker} >= {"recv", "exec", "send"}
            wpid = worker[0].pid
            assert wpid not in (0, os.getpid()), \
                "worker spans must carry the worker subprocess pid"
            for ws in worker:
                chain = []
                cur = by_id.get(ws.parent_id)
                while cur is not None:
                    chain.append(cur.name)
                    cur = by_id.get(cur.parent_id)
                assert "dispatch" in chain, (ws.name, chain)
                assert chain[-1] == "run", (ws.name, chain)
            # satellite (b): the orphaned fabric counters are in the
            # unified registry snapshot
            snap = rt.metrics.snapshot()
            for key in ("broker.tasks_cancelled", "broker.idle_workers",
                        "broker.num_workers_with_warm",
                        "broker.queue_depth", "pool.spawned_total",
                        "mdss.eviction_bytes", "wire.bytes_sent"):
                assert key in snap, key
            assert snap["broker.tasks_cancelled"] == \
                fabric.broker.tasks_cancelled
            assert snap["pool.spawned_total"] >= 1
            assert snap["wire.bytes_sent"] > 0

            # the exported Chrome trace carries the worker-side spans with
            # their explicit parent linkage
            doc = rt.tracer.export(h.trace_id)
            xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
            wx = [e for e in xs if e["pid"] == wpid]
            assert wx, "no worker-process events in the export"
            ids = {e["args"]["span_id"] for e in xs}
            for e in wx:
                assert e["args"]["parent_id"] in ids


# ------------------------------------------------------ introspect / emtop
def test_introspect_consistent_under_concurrent_tenants():
    """Hammer introspect() from two reader threads while two tenants
    execute: per-step states are single-valued (never both in-flight and
    completed), counts add up, and completion is absorbing."""
    with EmeraldRuntime(emerald(), max_workers=2, local_workers=2) as rt:
        h1 = rt.submit(chain_wf("alpha", 6, 0.02), {"x": np.float64(0.0)})
        h2 = rt.submit(chain_wf("beta", 6, 0.02), {"x": np.float64(10.0)})
        per_thread = [[], []]
        errs = []

        def reader(out):
            try:
                while not (h1.done() and h2.done()):
                    out.append(rt.introspect(timeout=10))
            except Exception as e:   # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=reader, args=(out,))
                   for out in per_thread]
        for t in threads:
            t.start()
        h1.result(60)
        h2.result(60)
        for t in threads:
            t.join(30)
        assert not errs
        assert any(per_thread), "no snapshots taken while runs were live"
        # snapshot order is only meaningful per reader thread (each call
        # blocks until the driver answers, so a thread's sequence is the
        # driver's order; across threads the appends interleave)
        for snaps in per_thread:
            completed_seen = {}              # (run_id, step) -> True
            for snap in snaps:
                for run in snap["runs"]:
                    states = run["steps"]
                    counts = {"pending": 0, "ready": 0, "inflight": 0,
                              "completed": 0}
                    for nm, st in states.items():
                        counts[st] += 1
                        if completed_seen.get((run["run_id"], nm)):
                            assert st == "completed", \
                                f"{nm} regressed from completed to {st}"
                        if st == "completed":
                            completed_seen[(run["run_id"], nm)] = True
                    assert sum(counts.values()) == len(states)
                    assert counts["completed"] == run["completed"]
        # post-run: the final snapshot renders (emtop's code path) and
        # survives a JSON round-trip (emtop's file input path)
        final = rt.introspect()
        text = render(json.loads(json.dumps(final)))
        assert "LANES" in text and "METRICS" in text


def test_introspect_after_close_and_disabled_telemetry():
    rt = EmeraldRuntime(emerald(), max_workers=2, telemetry=False)
    try:
        h = rt.submit(chain_wf("quiet", 3), {"x": np.float64(1.0)})
        assert float(h.result(30)["y3"]) == 4.0
        assert rt.tracer.spans() == [], "telemetry=False must trace nothing"
        assert rt.metrics.snapshot() == {}
        snap = rt.introspect()
        assert snap["runtime"]["telemetry"] is False
    finally:
        rt.close()
    # driver gone: introspect falls back to the direct read
    snap = rt.introspect(timeout=0.5)
    assert snap["runtime"]["closed"] is True


# ------------------------------------------------------------ trace export
def test_chrome_trace_export_validates(tmp_path):
    with EmeraldRuntime(emerald(), max_workers=2) as rt:
        h = rt.submit(chain_wf("exported", 3), {"x": np.float64(0.0)})
        h.result(30)
        path = rt.export_trace(str(tmp_path / "trace.json"),
                               run_id=h.trace_id)
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert xs and ms
    names = {e["name"] for e in xs}
    # "place" appears only under a locality policy; this run exercises
    # the default should_offload path
    assert {"run", "dispatch", "exec", "install", "submit",
            "reintegrate"} <= names
    span_ids = set()
    for e in xs:
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        a = e["args"]
        assert a["trace_id"] == h.trace_id
        assert a["span_id"] not in span_ids, "span ids must be unique"
        span_ids.add(a["span_id"])
    for e in xs:
        assert e["args"]["parent_id"] == 0 \
            or e["args"]["parent_id"] in span_ids
    # every (pid, tid) row is named by an M thread_name record
    named = {(e["pid"], e["tid"]) for e in ms if e["name"] == "thread_name"}
    assert {(e["pid"], e["tid"]) for e in xs} <= named
    # one track per lane and one per run on separate tids
    tracks = {e["args"]["name"] for e in ms if e["name"] == "thread_name"}
    assert "driver" in tracks and f"run:{h.trace_id}" in tracks


def test_chrome_trace_sanitises_non_json_attrs():
    tr = Tracer()
    tr.add_span("t", "x", wall_now(), 0.01, obj=object(), ok=1)
    doc = chrome_trace(tr.spans())
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
    json.dumps(doc)                         # must be serialisable
    assert isinstance(x["args"]["obj"], str) and x["args"]["ok"] == 1


# ---------------------------------------------------------- profiler bridge
class _Closes:
    def __init__(self, rec):
        self.rec = rec

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.rec[2] = True
        return False


class Annotations:
    """Stands in for a profiler's host-annotation factory and keeps what
    was opened, with its attributes and whether it was closed."""

    def __init__(self):
        self.opened = []
        self._lock = threading.Lock()

    def __call__(self, name, **attrs):
        rec = [name, attrs, False]
        with self._lock:
            self.opened.append(rec)
        return _Closes(rec)

    def names(self):
        return {name for name, _, _ in self.opened}


def tiny_at():
    """The paper's 4-step AT workflow at a mesh the CPU runs in a blink,
    with its initial variables."""
    import jax.numpy as jnp

    from repro.apps.adjoint_tomography import (ATConfig, build_workflow,
                                               starting_model)
    cfg = ATConfig(nx=12, ny=6, nz=6, nt=8, n_receivers=3)
    return build_workflow(cfg), {
        "model": starting_model(cfg),
        "obs": jnp.zeros((cfg.nt, cfg.n_receivers), jnp.float32)}


def test_tracer_bridges_spans_and_phases_to_annotations():
    ann = Annotations()
    tr = Tracer()
    tr.annotation = ann
    with tr.span("exec", step="forward"):
        with tr.phase("hash", bytes=64):
            pass
    with tr.span("submit"):
        pass
    tr.add_span("t", "run", wall_now(), 0.0, step="forward")
    assert ann.opened == [["emerald:exec", {"step": "forward"}, True],
                          ["emerald:hash", {"bytes": 64}, True],
                          ["emerald:submit", {}, True]]
    # the ring holds the spans, not the phase; add_span is ring-only
    assert [s.name for s in tr.spans()] == ["exec", "submit", "run"]


def test_one_at_iteration_records_nineteen_spans():
    """run + dispatch/ship/exec/install of each of the 4 steps + submit +
    reintegrate, all in the run's own trace."""
    wf, init = tiny_at()
    with EmeraldRuntime(emerald(), max_workers=2) as rt:
        h = rt.submit(wf, init, fetch=("chi",))
        h.result(120)
        spans = rt.tracer.spans()
    assert sorted(s.name for s in spans) == sorted(
        ["run", "submit", "reintegrate"]
        + ["dispatch", "ship", "exec", "install"] * 4)
    assert {s.trace_id for s in spans} == {h.trace_id}
    root = next(s for s in spans if s.name == "run")
    assert next(s for s in spans if s.name == "reintegrate").parent_id \
        == root.span_id
    assert {s.attrs["step"] for s in spans if s.name == "exec"} \
        == {"forward", "misfit", "kernel", "update"}


@pytest.mark.parametrize("telemetry", [True, False])
def test_annotations_open_exactly_when_telemetry_is_on(telemetry):
    """Under memoization the store computes the digests of the AT
    workflow's device-resident values, so its ``d2h`` and ``hash`` phases
    open too."""
    ann = Annotations()
    tracer = Tracer(enabled=telemetry)
    tracer.annotation = ann
    wf, init = tiny_at()
    with EmeraldRuntime(emerald(), max_workers=2, telemetry=telemetry,
                        tracer=tracer, memoize=True) as rt:
        assert rt.mdss.tracer is tracer
        rt.submit(wf, init, fetch=("chi",)).result(120)
    if not telemetry:
        assert ann.opened == [] and tracer.spans() == []
        return
    assert all(closed for _, _, closed in ann.opened)
    assert {"emerald:" + n for n in (
        "submit", "verify", "materialize", "drive", "dispatch", "ship",
        "exec", "install", "d2h", "hash", "reintegrate")} <= ann.names()
    assert {a["msg"] for n, a, _ in ann.opened if n == "emerald:drive"} \
        >= {"submit", "done"}


def stat(ev, key):
    return next(v for k, v in ev.stats if k == key)


def test_cpu_profiler_trace_holds_the_runtime_spans(tmp_path):
    """One run under the real profiler (CPU): the runtime's spans and
    phases are host events on the profiler's clock, and the ``bytes`` of
    its ``hash`` phases add up to the store's own count. Memoization
    demands the digests of the workflow's device-resident values."""
    import jax
    from jax.profiler import ProfileData
    wf, init = tiny_at()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with EmeraldRuntime(emerald(), max_workers=2, memoize=True) as rt:
            rt.submit(wf, init, fetch=("chi",), prefetch=False).result(120)
            hashed = rt.metrics.snapshot()["mdss.bytes_hashed"]
    finally:
        jax.profiler.stop_trace()
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("emerald:")]
    names = {ev.name for ev in events}
    assert {"emerald:" + n for n in (
        "submit", "verify", "materialize", "drive", "d2h", "hash", "exec",
        "install", "reintegrate")} <= names
    assert hashed > 0
    assert sum(stat(ev, "bytes") for ev in events
               if ev.name == "emerald:hash") == hashed
    assert {stat(ev, "step") for ev in events
            if ev.name == "emerald:exec"} \
        == {"forward", "misfit", "kernel", "update"}


def test_at_run_without_memoization_hashes_nothing():
    """With no digest demanded, the AT workflow's device-resident values
    are never copied to the host or hashed: no ``d2h`` or ``hash`` phase
    opens, and every install of one is counted as deferred."""
    ann = Annotations()
    tracer = Tracer()
    tracer.annotation = ann
    wf, init = tiny_at()
    with EmeraldRuntime(emerald(), max_workers=2, tracer=tracer) as rt:
        rt.submit(wf, init, fetch=("chi",)).result(120)
        snap = rt.metrics.snapshot()
    assert {"emerald:install", "emerald:ship"} <= ann.names()
    assert not {"emerald:d2h", "emerald:hash"} & ann.names()
    assert snap["mdss.bytes_hashed"] == 0
    assert snap["mdss.manifests_on_demand"] == 0
    # model and obs at submit, then syn, chi, grad and the new model
    assert snap["mdss.manifests_deferred"] == 6


def test_at_step_programs_bear_their_step_names():
    import jax
    wf, init = tiny_at()
    for name in ("forward", "misfit", "kernel", "update"):
        assert wf.steps[name].fn.__name__ == name
    lowered = jax.jit(wf.steps["forward"].fn).lower(init["model"])
    assert "jit_forward" in lowered.as_text()


# ----------------------------------------------------------- event schema
def test_every_emit_call_site_is_registered(tmp_path):
    """Wrapper over the promoted self-lint rules (repro.analysis.selfcheck,
    also reachable as ``emlint --self``): every emit( kind and dotted
    metric name in src/ must be registered in its schema/catalogue."""
    from repro.analysis import selfcheck
    findings = selfcheck.check_source(SRC_DIR)
    assert not findings, "\n".join(str(f) for f in findings)
    # canary: the lint actually detects drift (else a regex rot would
    # make the assertion above pass vacuously)
    bad = tmp_path / "drift.py"
    bad.write_text('run.emit("bogus_kind", s)\n'
                   'metrics.inc("bogus.metric")\n')
    rules = {f.rule for f in selfcheck.check_source(str(tmp_path))}
    assert rules == {"L001", "L002"}


def test_dynamic_metric_names_are_linted(tmp_path):
    """Drift canary for the dynamic-name extension: dotted metric / event
    names built with f-strings or ``+`` concatenation are checked against
    the registries as prefix patterns, not skipped."""
    from repro.analysis.selfcheck import check_snippet
    # a dynamic pattern whose prefix matches no catalogued metric drifts
    bad = ('def f(metrics, run, k):\n'
           '    metrics.inc(f"nosuch.{k}_total")\n'
           '    run.emit(f"bogus_{k}", object())\n')
    rules = {f.rule for f in check_snippet(bad)}
    assert rules == {"L001", "L002"}
    # patterns under a registered family are accepted, either spelling
    ok = ('def f(metrics, kind):\n'
          '    metrics.inc(f"emcheck.{kind}")\n'
          '    metrics.inc("fanout." + kind)\n')
    assert check_snippet(ok) == []
    # and the same contract holds through the file-tree entry point
    drift = tmp_path / "dyn.py"
    drift.write_text('def f(metrics, k):\n'
                     '    metrics.observe(f"nosuch.{k}.seconds", 1.0)\n')
    from repro.analysis import selfcheck
    assert {f.rule for f in selfcheck.check_source(str(tmp_path))} == {"L002"}


def test_validate_event():
    validate_event("offload", {"seconds": 0.1, "bytes_in": 3})
    with pytest.raises(ValueError, match="unregistered"):
        validate_event("nonsense", {})
    with pytest.raises(ValueError, match="missing required"):
        validate_event("offload", {})
    with pytest.raises(ValueError, match="undeclared"):
        validate_event("offload", {"seconds": 0.1, "surprise": 1})


def test_runtime_events_conform_to_schema_and_carry_wall_clock():
    """Satellite (a): every Event now records a wall-clock timestamp
    (cross-process comparable) next to the monotonic one, and live event
    payloads validate against the registered schema."""
    t_before = time.time()
    with EmeraldRuntime(emerald(), max_workers=2) as rt:
        h = rt.submit(chain_wf("walled", 3, 0.01), {"x": np.float64(0.0)})
        h.result(30)
        events = list(h.events)
    t_after = time.time()
    assert events
    for e in events:
        validate_event(e.kind, e.info)
        assert t_before - 1.0 <= e.t_wall <= t_after + 1.0, \
            (e.kind, e.t_wall)
    # wall ordering must agree with monotonic ordering within the run
    ts = [(e.t, e.t_wall) for e in events]
    for (t0, w0), (t1, w1) in zip(ts, ts[1:]):
        if t1 > t0:
            assert w1 >= w0 - 1e-3
