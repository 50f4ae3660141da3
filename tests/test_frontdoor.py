"""Serving front door: admission parking, continuous batching, and
SLO preemption.

Covers the acceptance surface of the front-door refactor:

  * park-instead-of-refuse — over-capacity submissions return a
    ``parked`` handle immediately and drain oldest-deadline-first as
    run slots free; ``queue_full`` is the only hard refusal,
  * symmetric release — refused/parked/finalized paths all leave the
    reservation table and the run-slot count at zero (the regression
    the refuse path used to leak), hammered concurrently,
  * cancel-while-parked and close-with-parked semantics,
  * :class:`BatchCoalescer` — window / full / deadline flush reasons,
    per-key isolation, 1/k fair-share charging, error fan-out,
  * broker checkpoint-abort — ``preempt_longest`` requeues the victim
    attempt-free and the task still completes,
  * the driver's SLO guard fires exactly once per threatened run,
  * explorer ``frontdoor`` model: clean is exhaustively hazard-free,
    planted bugs surface H125/H126.
"""
import threading
import time

import numpy as np
import pytest

from repro.analysis import explorer
from repro.core import (AdmissionRefused, CostModel, EmeraldRuntime, MDSS,
                        MigrationManager, RunCancelled, RuntimeClosed,
                        Workflow, default_tiers)
from repro.core.batching import BatchCoalescer, CoalesceError


def emerald():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    return MigrationManager(tiers, mdss, cm)


def sleeper_wf(name, seconds=0.0):
    def fn(x):
        if seconds:
            time.sleep(seconds)
        return {"y": np.float64(float(x) + 1.0)}
    wf = Workflow(name)
    wf.var("x")
    wf.step("s", fn, inputs=("x",), outputs=("y",), remotable=False,
            jax_step=False)
    return wf


# ------------------------------------------------------------- admission
def test_park_drains_oldest_deadline_first():
    with EmeraldRuntime(emerald(), max_workers=2, max_active_runs=1,
                        park_limit=4, telemetry=False) as rt:
        head = rt.submit(sleeper_wf("head", 0.25), {"x": 0.0})
        # loose deadline parked first, tight deadline second: admission
        # must reorder them (oldest deadline first), not FIFO
        loose = rt.submit(sleeper_wf("loose"), {"x": 10.0}, park=True,
                          deadline_s=60.0)
        tight = rt.submit(sleeper_wf("tight"), {"x": 20.0}, park=True,
                          deadline_s=1.0)
        assert loose.state == "parked" and tight.state == "parked"
        snap = rt.introspect()["frontdoor"]
        assert snap["depth"] == 2 and snap["queue_limit"] == 4
        assert [p["run_id"] for p in snap["parked"]] == \
            [tight.run_id, loose.run_id]           # deadline order

        assert head.result(10)["y"] == 1.0
        assert tight.result(10)["y"] == 21.0
        assert loose.result(10)["y"] == 11.0
        assert tight.state == "done" and loose.state == "done"
        admit_t = {}
        for h in (tight, loose):
            (ev,) = [e for e in h.events if e.kind == "admit"]
            admit_t[h.run_id] = ev.t
            assert any(e.kind == "park" for e in h.events)
        assert admit_t[tight.run_id] <= admit_t[loose.run_id]
        assert rt.admitted_total == 2 and rt.parked_total == 2


def test_queue_full_is_the_only_refusal_and_release_is_symmetric():
    with EmeraldRuntime(emerald(), max_workers=2, max_active_runs=1,
                        park_limit=2, telemetry=False) as rt:
        head = rt.submit(sleeper_wf("head", 0.4), {"x": 0.0})
        parked = [rt.submit(sleeper_wf(f"p{i}"), {"x": float(i)}, park=True)
                  for i in range(2)]
        # the head run is still sleeping, so the queue is full now
        with pytest.raises(AdmissionRefused, match="queue_full"):
            rt.submit(sleeper_wf("overflow"), {"x": 9.0}, park=True)
        # non-parking submission over the run-slot cap refuses outright
        with pytest.raises(AdmissionRefused, match="run slots"):
            rt.submit(sleeper_wf("refused"), {"x": 9.0})
        head.result(10)
        for i, h in enumerate(parked):
            assert h.result(10)["y"] == i + 1.0
        # every path released its state: nothing reserved, nothing live
        with rt._runs_lock:
            assert not rt._reserved and rt._live == 0 and not rt._parked


def test_park_validation_runs_before_queueing():
    from repro.analysis import WorkflowRejected
    with EmeraldRuntime(emerald(), max_workers=2, max_active_runs=1,
                        telemetry=False) as rt:
        head = rt.submit(sleeper_wf("head", 0.2), {"x": 0.0})
        bad = Workflow("bad")
        bad.var("missing")          # declared but never provided: W002
        bad.step("s", lambda missing: {}, inputs=("missing",),
                 outputs=("y",), jax_step=False)
        with pytest.raises(WorkflowRejected):
            rt.submit(bad, {}, park=True)
        # the rejected submission never landed in the queue
        assert rt.introspect()["frontdoor"]["depth"] == 0
        head.result(10)
        with rt._runs_lock:
            assert not rt._reserved and rt._live == 0


@pytest.mark.parametrize("path", ["direct", "parked"])
def test_submission_options_reach_the_run_on_either_path(path):
    """Every per-submission option reaches the materialised run unchanged,
    whether the run is admitted at once or parked and drained later."""
    wf = Workflow("opts")
    wf.var("x")
    wf.step("s", lambda x: {"y": np.float64(x + 1.0), "z": np.float64(x)},
            inputs=("x",), outputs=("y", "z"), remotable=False,
            jax_step=False)
    with EmeraldRuntime(emerald(), max_workers=2, max_active_runs=1,
                        telemetry=False) as rt:
        runs = []
        materialize = rt._materialize

        def spy(*a, **kw):
            run = materialize(*a, **kw)
            runs.append(run)
            return run

        rt._materialize = spy
        head = rt.submit(sleeper_wf("head", 0.2), {"x": 0.0}) \
            if path == "parked" else None
        t0 = time.perf_counter()
        h = rt.submit(wf, {"x": 1.0}, fetch=["y"], weight=2.5, priority=3,
                      speculate_after=4.0, prefetch=False, park=True,
                      deadline_s=30.0, slo_ms=250.0)
        t1 = time.perf_counter()
        assert (h.state == "parked") == (path == "parked")
        assert h.result(10) == {"y": 2.0}
        if head is not None:
            head.result(10)
        assert any(e.kind == "park" for e in h.events) == (path == "parked")
        (sub,) = [r.sub for r in runs if r.run_id == h.run_id]
        assert sub.fetch == ["y"]
        assert (sub.weight, sub.priority) == (2.5, 3)
        assert sub.speculate_after == 4.0 and sub.prefetch is False
        assert sub.slo_ms == 250.0
        assert t0 + 30.0 <= sub.deadline_perf <= t1 + 30.0


def test_cancel_while_parked():
    with EmeraldRuntime(emerald(), max_workers=2, max_active_runs=1,
                        telemetry=False) as rt:
        head = rt.submit(sleeper_wf("head", 0.3), {"x": 0.0})
        h = rt.submit(sleeper_wf("victim"), {"x": 1.0}, park=True)
        assert h.state == "parked"
        h.cancel()
        with pytest.raises(RunCancelled):
            h.result(10)
        assert h.state == "cancelled"
        head.result(10)
        assert rt.admitted_total == 0


def test_close_fails_parked_with_runtime_closed():
    rt = EmeraldRuntime(emerald(), max_workers=2, max_active_runs=1,
                        telemetry=False)
    head = rt.submit(sleeper_wf("head", 0.2), {"x": 0.0})
    h = rt.submit(sleeper_wf("stuck"), {"x": 1.0}, park=True)
    head.result(10)
    rt.close()
    if h.state == "done":         # admitted before close won the race
        assert h.result(0)["y"] == 2.0
    else:
        with pytest.raises(RuntimeClosed):
            h.result(10)


def test_concurrent_park_refuse_finalize_hammer():
    """Park, refuse, and finalize racing from many threads must never
    leak a reservation or a run slot (the symmetric-release bugfix)."""
    with EmeraldRuntime(emerald(), max_workers=4, max_active_runs=2,
                        park_limit=3, telemetry=False) as rt:
        handles, refused = [], []
        lock = threading.Lock()

        def tenant(i):
            for j in range(4):
                try:
                    h = rt.submit(sleeper_wf(f"t{i}.{j}", 0.01),
                                  {"x": float(i)}, park=(j % 2 == 0),
                                  deadline_s=5.0)
                    with lock:
                        handles.append(h)
                    if j % 2:
                        h.result(30)
                except AdmissionRefused:
                    with lock:
                        refused.append((i, j))

        threads = [threading.Thread(target=tenant, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for h in handles:
            assert "y" in h.result(30)
        deadline = time.time() + 10.0
        while time.time() < deadline:
            with rt._runs_lock:
                if not rt._reserved and rt._live == 0 and not rt._parked:
                    break
            time.sleep(0.01)
        with rt._runs_lock:
            assert not rt._reserved and rt._live == 0 and not rt._parked


# -------------------------------------------------------------- coalescer
def test_coalescer_window_flush_and_rows():
    got = []

    def fuse(key, stacked, k):
        got.append((key, stacked.shape, k))
        return stacked * 2

    c = BatchCoalescer(fuse, window_s=0.03, max_batch=8)
    try:
        tickets = [c.submit("k", np.full((2,), i)) for i in range(3)]
        rows = [t.result(5.0) for t in tickets]
        assert len(got) == 1 and got[0] == ("k", (3, 2), 3)
        for i, row in enumerate(rows):
            np.testing.assert_array_equal(row, np.full((2,), i * 2))
        (flush,) = [e for e in c.events if e.kind == "flush"]
        assert flush.info["reason"] == "window" and flush.info["batch"] == 3
    finally:
        c.close()


def test_coalescer_full_flush_is_immediate():
    c = BatchCoalescer(lambda key, stacked, k: stacked, window_s=10.0,
                       max_batch=4)
    try:
        t0 = time.perf_counter()
        tickets = [c.submit("k", np.float64(i)) for i in range(4)]
        for t in tickets:
            t.result(5.0)
        assert time.perf_counter() - t0 < 5.0      # did not wait the window
        (flush,) = [e for e in c.events if e.kind == "flush"]
        assert flush.info["reason"] == "full"
    finally:
        c.close()


def test_coalescer_deadline_forces_early_flush():
    c = BatchCoalescer(lambda key, stacked, k: stacked, window_s=30.0,
                       max_batch=8)
    try:
        t = c.submit("k", np.float64(1.0), deadline_s=0.05)
        t.result(5.0)
        (flush,) = [e for e in c.events if e.kind == "flush"]
        assert flush.info["reason"] == "deadline"
        assert flush.info["waited_s"] < 5.0
    finally:
        c.close()


def test_coalescer_keys_never_fuse_and_charges_are_fair():
    shares = []
    c = BatchCoalescer(lambda key, stacked, k: stacked, window_s=0.02,
                       max_batch=8)
    try:
        a = [c.submit("ka", np.float64(i), charge=shares.append)
             for i in range(3)]
        b = c.submit("kb", np.float64(9.0))
        for t in a:
            t.result(5.0)
        b.result(5.0)
        assert c.flushes == 2                       # one per key
        # the three ka participants each paid the same 1/3 share
        assert len(shares) == 3 and len({round(s, 12) for s in shares}) == 1
    finally:
        c.close()


def test_coalescer_error_fans_out_to_every_ticket():
    def boom(key, stacked, k):
        raise ValueError("fused failure")

    c = BatchCoalescer(boom, window_s=0.02, max_batch=8)
    try:
        tickets = [c.submit("k", np.float64(i)) for i in range(2)]
        for t in tickets:
            with pytest.raises(CoalesceError, match="fused failure"):
                t.result(5.0)
    finally:
        c.close()


# ------------------------------------------------------------- preemption
def test_broker_preempt_longest_is_attempt_free():
    from repro.cloud import Fabric
    with Fabric(workers=1) as fabric:
        t = fabric.broker.submit(step="sleep", kwargs={"seconds": 1.0},
                                 preemptible=True)
        deadline = time.time() + 10.0
        while time.time() < deadline and not fabric.broker._inflight:
            time.sleep(0.01)
        victim = fabric.broker.preempt_longest()
        assert victim is t
        assert t.preempted == 1
        assert fabric.broker.tasks_preempted == 1
        # the requeued task completes on the replacement worker, and the
        # preempted placement was refunded: exactly one charged attempt
        t.result(60)
        assert t.attempts == 1


def test_broker_preempt_longest_skips_non_preemptible():
    from repro.cloud import Fabric
    with Fabric(workers=1) as fabric:
        fabric.broker.submit(step="sleep", kwargs={"seconds": 0.3})
        time.sleep(0.05)
        assert fabric.broker.preempt_longest() is None


def test_slo_guard_fires_once_per_threatened_run():
    class FakeTask:
        task_id = 7
        step = "bat"

    class FakeBroker:
        def __init__(self):
            self.calls = 0

        def preempt_longest(self):
            self.calls += 1
            return FakeTask()

    class FakeFabric:
        def __init__(self):
            self.broker = FakeBroker()

    with EmeraldRuntime(emerald(), max_workers=2, max_active_runs=1,
                        telemetry=False) as rt:
        rt._fabric = FakeFabric()
        head = rt.submit(sleeper_wf("head", 0.3), {"x": 0.0})
        h = rt.submit(sleeper_wf("urgent"), {"x": 1.0}, park=True,
                      deadline_s=0.05, slo_ms=10_000.0)
        assert h.result(10)["y"] == 2.0
        head.result(10)
        assert rt._fabric.broker.calls == 1      # once, despite many ticks
        assert any(e.kind == "preempt" for e in h.events)


# ---------------------------------------------------------------- emcheck
def test_frontdoor_model_clean_is_exhaustively_hazard_free():
    res = explorer.explore(explorer.build_model("frontdoor"))
    assert res.exhaustive and res.hazard_count == 0


def test_frontdoor_model_finds_parked_starvation():
    res = explorer.explore(
        explorer.build_model("frontdoor", bugs=["parked_starved"]),
        max_hazards=1)
    assert "H125" in res.hazard_rules()


def test_frontdoor_model_finds_preemption_burning_progress():
    res = explorer.explore(
        explorer.build_model("frontdoor", bugs=["preempt_lost_step"]),
        max_hazards=1)
    assert "H126" in res.hazard_rules()


def test_frontdoor_reproducer_roundtrip(tmp_path):
    model = explorer.build_model("frontdoor", bugs=["parked_starved"])
    res = explorer.explore(model, max_hazards=1)
    sched, findings = res.hazards[0]
    small = explorer.minimize(model, sched)
    path = str(tmp_path / "repro.json")
    explorer.save_reproducer(path, model, small, findings)
    doc = explorer.load_reproducer(path)
    replayed, retriggered = explorer.replay_reproducer(doc)
    assert retriggered and "H125" in {f.rule for f in replayed}
