"""Locality-aware dispatch + namespace residency budgets.

Covers the data-locality scheduling signal and its bounds:

  * ``LocalityPolicy`` placement units on synthetic residency maps —
    warm-on-cloud inputs flip a compute-favoured-local step to the
    offload lane and vice versa, with tie-breaks and the annotate
    fallback,
  * runtime integration: ``policy="locality"`` dispatches by per-tier
    (exec + transfer) score and emits the chosen-tier rationale as a
    ``place`` event,
  * per-(namespace, tier) residency budgets: incremental resident-byte
    accounting, LRU eviction with write-back to local, background
    enforcement, eviction vs. fence epochs (an evicted-then-redropped
    entry refuses a stale write-back),
  * admission control at ``submit`` against the store capacity ceiling,
  * autoscaler churn pressure from the evicted-bytes counter.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import (AdmissionRefused, CostModel, EmeraldRuntime,
                        LocalityPolicy, MDSS, MigrationManager, Workflow,
                        default_tiers, nbytes_of)
from repro.cloud.autoscaler import Autoscaler, AutoscalerConfig


def emerald():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    return MigrationManager(tiers, mdss, cm)


def one_step_wf(name="loc", inputs=("a",), remotable=True):
    wf = Workflow(name)
    for u in inputs:
        wf.var(u)
    s = wf.step("s", lambda **kw: {"y": np.float64(0.0)}, inputs=inputs,
                outputs=("y",), remotable=remotable, jax_step=False)
    return wf, s


# ------------------------------------------------------- placement units
def test_locality_prefers_tier_holding_the_data():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    pol = LocalityPolicy(cm, mdss, "cloud")
    _, s = one_step_wf()
    big = np.ones((1024, 512), np.float64)            # 4 MiB
    # raw compute favours local...
    cm.stats_for("s").measured_s.update(local=0.002, cloud=0.003)
    # ...but the input is warm on cloud only
    mdss.put("a", big, tier="cloud")
    d = pol.place(s)
    assert d.offload and d.tier == "cloud"
    assert d.scores["cloud"] < d.scores["local"]
    assert d.stale_bytes["local"] == big.nbytes
    assert d.stale_bytes["cloud"] == 0
    # once the data is staged home, compute-favoured local wins again
    mdss.ensure(["a"], "local")
    d2 = pol.place(s)
    assert not d2.offload and d2.tier == "local"
    assert d2.stale_bytes["local"] == 0


def test_locality_keeps_local_data_local_despite_faster_cloud():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    pol = LocalityPolicy(cm, mdss, "cloud")
    _, s = one_step_wf()
    # the cloud chip is faster, but not by enough to pay for staging
    cm.stats_for("s").measured_s.update(local=0.004, cloud=0.003)
    mdss.put("a", np.ones((2048, 512), np.float64), tier="local")  # 8 MiB
    d = pol.place(s)
    assert not d.offload, \
        "residency-blind choice: staged 8 MiB to chase a 1 ms exec win"


def test_locality_fallbacks_and_tie_breaks():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    pol = LocalityPolicy(cm, mdss, "cloud")
    # no data, no estimates -> the paper's annotate default (offload)
    _, s = one_step_wf()
    d = pol.place(s)
    assert d.offload and "annotate" in d.reason
    # non-remotable is never offloaded, whatever the residency map says
    _, s2 = one_step_wf("loc2", remotable=False)
    mdss.put("a", np.ones(1024), tier="cloud")
    d2 = pol.place(s2)
    assert not d2.offload and d2.reason == "not remotable"
    # warm-on-cloud data with no exec estimates: the transfer component
    # alone decides
    d3 = pol.place(s)
    assert d3.offload and d3.reason == "exec+transfer score"
    # equal modeled seconds but unequal residency (a cost model that
    # charges nothing for the wire): resident bytes break the tie
    class _FreeWire(CostModel):
        def transfer_time(self, nbytes, src, dst):
            return 0.0

    pol2 = LocalityPolicy(_FreeWire(tiers), mdss, "cloud")
    d4 = pol2.place(s)
    assert d4.offload and d4.reason == "resident-bytes tie-break"


def test_runtime_locality_dispatch_emits_rationale():
    mgr = emerald()
    cm = mgr.cost_model
    big = np.ones((1024, 512), np.float64)            # 4 MiB
    with EmeraldRuntime(mgr, policy="locality", max_workers=2) as rt:
        rt.publish("C", big, tier="cloud")            # cloud-resident only
        cm.stats_for("use").measured_s.update(local=0.002, cloud=0.003)
        wf = Workflow("warmloc")
        wf.var("C")
        wf.step("use", lambda C: {"out": np.float64(C.sum())},
                inputs=("C",), outputs=("out",), remotable=True,
                jax_step=False)
        h = rt.submit(wf, {})
        out = h.result(30)
        assert float(out["out"]) == big.sum()
        places = [e for e in h.events if e.kind == "place"]
        assert places and places[0].tier == "cloud"
        assert places[0].info["scores"]["cloud"] \
            < places[0].info["scores"]["local"]
        assert places[0].info["stale_bytes"]["cloud"] == 0
        # the step really took the offload lane, and staged nothing
        off = [e for e in h.events if e.kind == "offload"]
        assert off and off[0].info["code_only"] is True
        dones = [e for e in h.events if e.kind == "step_done"]
        assert len(dones) == 1 and dones[0].info["offloaded"] is True


# --------------------------------------------------- budgets and eviction
def test_budget_eviction_is_lru_with_writeback():
    tiers = default_tiers()
    base = MDSS(tiers, cost_model=CostModel(tiers))
    arr = np.ones(1024, np.float64)                   # 8 KiB each
    for name in ("a", "b", "c", "d"):
        base.put(f"job/{name}", arr, tier="cloud")
    assert base.namespace_tier_bytes("job", "cloud") == 4 * arr.nbytes
    base.get("job/a", "cloud")                        # refresh a's LRU slot
    budget = int(2.5 * arr.nbytes)
    base._budgets[("job", "cloud")] = budget          # no auto-kick: direct
    evicted_n, evicted_b = base.enforce_budget("job", "cloud")
    assert evicted_n == 2 and evicted_b == 2 * arr.nbytes
    assert base.namespace_tier_bytes("job", "cloud") <= budget
    # LRU: the two oldest-untouched entries (b, c) went; a survived its
    # refresh and d is the most recent write
    assert base.has_latest("job/a", "cloud")
    assert base.has_latest("job/d", "cloud")
    # write-back: evicted entries stay fully readable from local
    for name in ("b", "c"):
        assert base.has_latest(f"job/{name}", "local")
        np.testing.assert_array_equal(base.get(f"job/{name}", "local"), arr)
    assert base.evictions == 2 and base.eviction_bytes == 2 * arr.nbytes
    assert len(base.eviction_events) == 2
    # counters stayed consistent with a full scan
    assert base.namespace_resident_bytes("job") == sum(
        nbytes_of(v) for u in base.namespace_entries("job")
        for _, v in base._entries[u].copies.values())


def test_over_budget_put_triggers_background_eviction():
    tiers = default_tiers()
    base = MDSS(tiers, cost_model=CostModel(tiers))
    arr = np.ones(1024, np.float64)
    base.set_namespace_budget("job", "cloud", 2 * arr.nbytes)
    for i in range(6):
        base.put(f"job/x{i}", arr, tier="cloud")
    deadline = time.monotonic() + 5
    while base.namespace_tier_bytes("job", "cloud") > 2 * arr.nbytes \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert base.namespace_tier_bytes("job", "cloud") <= 2 * arr.nbytes, \
        "background eviction never brought the namespace under budget"
    # nothing was lost: every entry still has a latest replica somewhere
    for i in range(6):
        val, ver = base.peek_latest(f"job/x{i}")
        assert ver == 1 and val is not None


def test_eviction_respects_fence_epochs_on_redrop():
    """An evicted-then-redropped namespace entry must refuse a stale
    write-back: eviction's write-back is replica movement (no version
    bump, no entry creation), and a draining step's fenced publish still
    carries the pre-drop epoch."""
    tiers = default_tiers()
    base = MDSS(tiers, cost_model=CostModel(tiers))
    view = base.namespaced("job", shared="shared")
    arr = np.ones(2048, np.float64)
    view.put("u", arr, tier="cloud")
    # an in-flight step snapshots its fence before eviction/drop
    tokens = view.fence_tokens(["u"])
    base._budgets[("job", "cloud")] = 0
    base.enforce_budget("job", "cloud")               # evict: cloud -> local
    assert not base.has_latest("job/u", "cloud")
    assert base.has_latest("job/u", "local")          # write-back landed
    assert base.version("job/u") == 1, "eviction bumped a version"
    base.drop_namespace("job")                        # run released
    # the straggler's write-back: stale epoch, must be refused
    assert view.put_many({"u": np.zeros(8)}, tier="local",
                         expect_versions=tokens) is None
    assert base.namespace_entries("job") == [], \
        "stale write-back resurrected an evicted-then-dropped namespace"
    # budgets died with the namespace
    assert base.namespace_budget("job", "cloud") is None
    # eviction on the dropped namespace is a clean no-op
    base._budgets[("job", "cloud")] = 0
    assert base.enforce_budget("job", "cloud") == (0, 0)


def test_submit_residency_budget_bounds_run_namespace():
    mgr = emerald()
    mdss = mgr.mdss
    chunk = np.ones((512, 256), np.float64)           # 1 MiB outputs
    wf = Workflow("hot")
    wf.var("x")
    for i in range(6):
        wf.step(f"w{i}", (lambda i=i: lambda x: {f"b{i}": chunk + i})(),
                inputs=("x",), outputs=(f"b{i}",), remotable=True,
                jax_step=False)
    budget = 2 * chunk.nbytes
    with EmeraldRuntime(mgr, max_workers=2) as rt:
        h = rt.submit(wf, {"x": np.float64(0.0)},
                      residency_budget={"cloud": budget})
        assert mdss.namespace_budget(h.namespace, "cloud") == budget
        out = h.result(60)
        assert len([k for k in out if k.startswith("b")]) == 6
        deadline = time.monotonic() + 5
        while mdss.namespace_tier_bytes(h.namespace, "cloud") > budget \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert mdss.namespace_tier_bytes(h.namespace, "cloud") <= budget
        assert mdss.evictions > 0
        # un-namespaced submissions cannot carry a budget
        with pytest.raises(ValueError, match="namespaced"):
            rt.submit(wf, {}, namespace="", residency_budget={"cloud": 1})
        # local is the write-back tier: a budget there would silently
        # never evict, so it is rejected up front (x is provided: the
        # verifier runs first and would reject a workflow missing it)
        with pytest.raises(ValueError, match="write-back"):
            rt.submit(wf, {"x": np.float64(0.0)},
                      residency_budget={"local": 1})


def _join_evictors():
    for t in threading.enumerate():
        if t.name == "mdss-evict":
            t.join(5)


def test_rejected_submission_sets_no_budget_and_evicts_nothing(monkeypatch):
    """The verifier runs before a submission's namespace budgets land, so
    a warm resubmission it rejects cannot evict that namespace's data."""
    from repro.analysis import WorkflowRejected
    from repro.analysis import verifier
    mgr = emerald()
    mdss = mgr.mdss
    arr = np.ones(1024, np.float64)
    for name in ("a", "b"):
        mdss.put(f"warm/{name}", arr, tier="cloud")
    cloud_before = mdss.namespace_tier_bytes("warm", "cloud")
    resident_before = mdss.namespace_resident_bytes("warm")
    real_verify = verifier.verify

    def verify_after_evictions(*a, **kw):
        # any eviction the submission has already started lands before
        # the verdict, so the outcome does not depend on thread timing
        _join_evictors()
        return real_verify(*a, **kw)

    monkeypatch.setattr(verifier, "verify", verify_after_evictions)
    bad = Workflow("bad")
    bad.var("a")
    bad.var("missing")              # declared but never provided: W002
    bad.step("s", lambda a, missing: {"y": a}, inputs=("a", "missing"),
             outputs=("y",), jax_step=False)
    with EmeraldRuntime(mgr, telemetry=False) as rt:
        with pytest.raises(WorkflowRejected):
            rt.submit(bad, {}, namespace="warm",
                      residency_budget={"cloud": arr.nbytes // 2})
        _join_evictors()
        assert mdss.namespace_budget("warm", "cloud") is None
        assert mdss.namespace_tier_bytes("warm", "cloud") == cloud_before
        assert mdss.namespace_resident_bytes("warm") == resident_before
        assert mdss.evictions == 0
        with rt._runs_lock:
            assert not rt._reserved and rt._live == 0


def test_admission_control_refuses_near_capacity():
    mgr = emerald()
    mgr.mdss.capacity_bytes = 1_000_000
    wf, _ = one_step_wf("adm", inputs=("x",))
    with EmeraldRuntime(mgr) as rt:
        rt.publish("blob", np.ones(150_000, np.float64))   # 1.2 MB resident
        assert mgr.mdss.over_capacity(rt.admission_headroom)
        with pytest.raises(AdmissionRefused, match="capacity"):
            rt.submit(wf, {"x": np.float64(1.0)})
        # freeing residency re-opens the front door
        mgr.mdss.drop_namespace(rt.shared_namespace)
        h = rt.submit(wf, {"x": np.float64(1.0)})
        h.result(30)


# ------------------------------------------------------- autoscaler churn
class _StubBroker:
    def __init__(self):
        self.workers = 1

    def queue_depth(self):
        return 0

    def num_workers(self, include_warm=False):
        return self.workers

    def inflight(self):
        return 0

    def avg_task_seconds(self):
        return None

    def add_worker(self):
        self.workers += 1

    def retire_worker(self):
        self.workers -= 1
        return "w"

    def reap_warm(self, ttl):
        return 0


def test_autoscaler_churn_pressure_scales_up_and_blocks_retire():
    churn = {"total": 0}
    cfg = AutoscalerConfig(min_workers=1, max_workers=4, queue_high=100.0,
                           idle_scale_down_s=0.0,
                           churn_high_bytes_per_s=1e6)
    broker = _StubBroker()
    sc = Autoscaler(broker, cfg, churn_fn=lambda: churn["total"])
    sc.tick(now=0.0)                         # first tick only marks
    act = sc.tick(now=1.0)
    assert act["added"] == 0                 # no churn, no growth
    churn["total"] = 64_000_000              # 64 MB evicted in 1 s: thrash
    act = sc.tick(now=2.0)
    assert act["added"] == 1 and broker.workers == 2, \
        "eviction churn did not grow the pool"
    # nonzero (sub-threshold) churn still blocks the idle retire path
    churn["total"] += 1000
    act = sc.tick(now=3.0)
    assert act["retired"] == 0 and broker.workers == 2
    # churn gone: idle scale-down resumes
    act = sc.tick(now=10.0)
    act = sc.tick(now=20.0)
    assert broker.workers == 1
