"""Multi-tenant EmeraldRuntime: concurrent submissions over one scheduler.

Covers the acceptance surface of the multi-tenant refactor:

  * N concurrent heterogeneous workflows over one runtime, with per-run
    MDSS namespace isolation (same variable names, no cross-run
    corruption) and namespace teardown,
  * cross-run fair share — a small interactive run finishes while a wide
    batch run is still executing (no starvation), and aggregate
    throughput of concurrent submissions beats back-to-back serial runs,
  * warm resubmission — the second submission of an identical workflow is
    code-only (shared-namespace data already cloud-resident) and hits the
    shared compile cache,
  * run handles: non-blocking submit, cancel, release,
  * satellites: deterministic speculation backup tier, bounded
    in-flight-transfer waits surfacing as MDSSTransferError/StepFailure,
    CostModelPolicy.explain reporting, put_many fencing on absent
    entries, broker priority classes, autoscaler aggregate backlog.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import (CostModel, CostModelPolicy, EmeraldExecutor,
                        EmeraldRuntime, FairShare, MDSS, MDSSTransferError,
                        MigrationManager, RunCancelled, StepFailure, Workflow,
                        default_tiers, nbytes_of, partition)
from repro.core.tiers import Tier


def emerald():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    return MigrationManager(tiers, mdss, cm)


def sleeper(name, seconds, out, factor=2.0):
    def fn(**kw):
        (val,) = kw.values()
        time.sleep(seconds)
        return {out: np.float64(float(val) * factor)}
    return fn


def chain_wf(name, depth, step_s, factor=2.0, prefix=""):
    """x -> y1 -> ... -> y_depth, each step multiplying by ``factor``.
    ``prefix`` namespaces the URIs manually — needed only for executors
    sharing one base store un-namespaced (the compat mode)."""
    wf = Workflow(name)
    wf.var(prefix + "x")
    src = prefix + "x"
    for i in range(depth):
        out = f"{prefix}y{i + 1}"
        wf.step(f"s{i + 1}", sleeper(f"{name}.s{i}", step_s, out, factor),
                inputs=(src,), outputs=(out,), remotable=True, jax_step=False)
        src = out
    return wf


def wide_wf(name, width, step_s):
    wf = Workflow(name)
    wf.var("x")
    for i in range(width):
        wf.step(f"w{i}", sleeper(f"{name}.w{i}", step_s, f"y{i}"),
                inputs=("x",), outputs=(f"y{i}",), remotable=True,
                jax_step=False)
    return wf


# ------------------------------------------------------------ concurrency
def test_three_concurrent_runs_namespace_isolation():
    """3 heterogeneous workflows using the SAME variable names execute
    concurrently over one runtime; every run sees only its own data."""
    with EmeraldRuntime(emerald(), max_workers=6) as rt:
        # heterogeneous: different depths and factors, identical URIs
        handles = []
        for depth, factor, x in ((2, 2.0, 1.0), (3, 3.0, 2.0), (4, 5.0, 3.0)):
            wf = chain_wf("tenant", depth, 0.03, factor)
            handles.append((rt.submit(wf, {"x": np.float64(x)}),
                            x * factor ** depth, depth))
        for h, expect, depth in handles:
            out = h.result(30)
            assert float(out[f"y{depth}"]) == expect
        # isolation is structural: each run's URIs live under its own
        # namespace in the shared store
        namespaces = {h.namespace for h, _, _ in handles}
        assert len(namespaces) == 3
        base = rt.mdss
        for h, _, depth in handles:
            entries = base.namespace_entries(h.namespace)
            assert f"{h.namespace}/y{depth}" in entries
        # teardown: release drops exactly that run's data
        h0 = handles[0][0]
        dropped, freed = h0.release()
        assert dropped >= 3 and freed > 0          # x + y1 + y2 replicas
        assert base.namespace_entries(h0.namespace) == []
        assert base.namespace_entries(handles[1][0].namespace)  # untouched


def test_fair_share_small_run_not_starved_by_wide_run():
    """A 4-step interactive chain submitted after a 16-step wide batch
    run must finish while the wide run is still executing — under FIFO it
    would queue behind the whole backlog."""
    with EmeraldRuntime(emerald(), max_workers=2, local_workers=2) as rt:
        hw = rt.submit(wide_wf("batch", 16, 0.05), {"x": np.float64(1.0)})
        hs = rt.submit(chain_wf("inter", 4, 0.005), {"x": np.float64(1.0)})
        out = hs.result(30)
        assert float(out["y4"]) == 16.0
        assert not hw.done(), \
            "wide batch run finished first: small run was starved"
        hw.result(60)


def test_fair_share_weight_buys_share():
    fs = FairShare()
    fs.add("a", weight=1.0)
    fs.add("b", weight=3.0)
    grants = {"a": 0, "b": 0}
    for _ in range(40):
        rid = fs.pick(["a", "b"])
        grants[rid] += 1
        fs.charge(rid, 1.0)
    assert grants["b"] == 30 and grants["a"] == 10
    # a latecomer starts at the current minimum share, not at zero
    fs.add("c", weight=1.0)
    assert fs.share_of("c") == fs.share_of("a")
    fs.remove("b")
    assert fs.pick(["b"]) == "b"        # unknown ids still resolvable


def test_concurrent_throughput_beats_serial():
    """3 chain workflows (poor intra-run parallelism) through one runtime:
    concurrent submission must beat back-to-back runs, because idle lanes
    of one run absorb ready work from another."""
    mk = lambda i: chain_wf(f"tp{i}", 4, 0.05)
    # serial: one run at a time over the same shared runtime
    with EmeraldRuntime(emerald(), max_workers=8) as rt:
        t0 = time.perf_counter()
        for i in range(3):
            rt.submit(mk(i), {"x": np.float64(1.0)}).result(60)
        serial = time.perf_counter() - t0
    with EmeraldRuntime(emerald(), max_workers=8) as rt:
        t0 = time.perf_counter()
        hs = [rt.submit(mk(i), {"x": np.float64(1.0)}) for i in range(3)]
        for h in hs:
            h.result(60)
        concurrent = time.perf_counter() - t0
    assert serial / concurrent > 1.5, \
        f"no inter-workflow parallelism: serial {serial:.3f}s vs " \
        f"concurrent {concurrent:.3f}s"


def test_cancel_stops_pending_steps():
    ran = []
    lock = threading.Lock()

    def step(i):
        def fn(x):
            with lock:
                ran.append(i)
            time.sleep(0.05)
            return {f"y{i}": np.float64(i)}
        return fn

    wf = Workflow("cancelme")
    wf.var("x")
    for i in range(12):
        wf.step(f"s{i}", step(i), inputs=("x",), outputs=(f"y{i}",),
                remotable=True, jax_step=False)
    with EmeraldRuntime(emerald(), max_workers=2) as rt:
        h = rt.submit(wf, {"x": np.float64(0.0)})
        time.sleep(0.08)              # let a couple of steps start
        h.cancel()
        with pytest.raises(RunCancelled):
            h.result(30)
        assert h.state == "cancelled"
    assert len(ran) < 12, "cancel did not stop pending dispatch"


def test_executors_share_one_runtime():
    """Two classic executors over one shared runtime (the serve.py shape):
    both workflows run, events stay per-executor, nothing is torn down
    between runs. Compat executors address the base store un-namespaced
    (shared URIs are a *feature* there — serve's decode reads the cache
    prefill wrote), so co-tenant fronts use distinct URI names."""
    mgr = emerald()
    with EmeraldRuntime(mgr, max_workers=4) as rt:
        wf1 = chain_wf("front1", 2, 0.01, prefix="a_")
        wf2 = chain_wf("front2", 3, 0.01, factor=3.0, prefix="b_")
        ex1 = EmeraldExecutor(partition(wf1), mgr, runtime=rt)
        ex2 = EmeraldExecutor(partition(wf2), mgr, runtime=rt)
        h1 = ex1.submit({"a_x": np.float64(1.0)})
        h2 = ex2.submit({"b_x": np.float64(1.0)})
        assert float(h1.result(30)["a_y2"]) == 4.0
        assert float(h2.result(30)["b_y3"]) == 27.0
        assert {e.step for e in ex1.events if e.kind == "offload"} \
            == {"s1", "s2"}
        assert {e.step for e in ex2.events if e.kind == "offload"} \
            == {"s1", "s2", "s3"}
        # second run on the same executor still works (runtime persists)
        assert float(ex1.run({"a_x": np.float64(2.0)})["a_y2"]) == 8.0


# ------------------------------------------------------- warm resubmission
def test_second_submission_is_code_only_and_warm():
    mgr = emerald()
    mdss = mgr.mdss
    big = np.ones((64, 1024), np.float64)          # 512 KiB shared constant

    def build():
        wf = Workflow("warmjob")
        wf.var("params")
        wf.step("use", lambda params: {"out": np.float64(params.sum())},
                inputs=("params",), outputs=("out",), remotable=True,
                jax_step=False)
        return wf

    with EmeraldRuntime(mgr) as rt:
        rt.publish("params", big)
        out1 = rt.submit(build(), {}).result(30)
        shared_moved = mdss.namespace_bytes(rt.shared_namespace)
        assert shared_moved >= nbytes_of(big)      # first run staged params
        hits_before = mgr.compile_cache_hits
        h2 = rt.submit(build(), {})
        out2 = h2.result(30)
        assert float(out1["out"]) == float(out2["out"])
        # code-only: the shared data was already cloud-resident...
        off = [e for e in h2.events if e.kind == "offload"]
        assert off and off[0].info["code_only"] is True
        assert mdss.namespace_bytes(rt.shared_namespace) == shared_moved
        # ...and pre-compiled + pre-measured from the first submission
        assert mgr.compile_cache_hits > hits_before
        assert "cloud" in mgr.cost_model.stats_for("use").measured_s


def test_runtime_checkpoint_resume_in_namespace(tmp_path):
    state = {"crash": True}

    def mid(y1):
        if state["crash"]:
            raise StepFailure("injected: power loss")
        return {"z": np.float64(y1) * 10}

    def build():
        wf = Workflow("ckns")
        wf.var("x")
        wf.step("a", lambda x: {"y1": np.float64(x) + 1}, inputs=("x",),
                outputs=("y1",), remotable=True, jax_step=False)
        wf.step("b", mid, inputs=("y1",), outputs=("z",), remotable=True,
                jax_step=False, retries=0)
        return wf

    with EmeraldRuntime(emerald(), checkpoint_dir=str(tmp_path)) as rt:
        h = rt.submit(build(), {"x": np.float64(1.0)}, namespace="job")
        with pytest.raises(Exception):
            h.result(30)
        state["crash"] = False
        h2 = rt.submit(build(), {"x": np.float64(1.0)}, namespace="job",
                       resume=True)
        out = h2.result(30)
        assert float(out["z"]) == 20.0
        ran = {e.step for e in h2.events if e.kind == "offload"}
        assert "a" not in ran, "resume re-ran completed step"


def test_compile_cache_never_shared_across_default_arg_variants():
    """Two tenants building steps via the ``def fn(x, k=k)`` default-arg
    idiom share one code object but different bound state; the compile
    cache must not hand tenant B tenant A's executable."""
    def build(k):
        def fn(x, k=k):
            return {"y": np.float64(float(x) * k)}
        wf = Workflow(f"defaults{k}")
        wf.var("x")
        wf.step("mul", fn, inputs=("x",), outputs=("y",), remotable=True,
                jax_step=False)
        return wf

    with EmeraldRuntime(emerald()) as rt:
        h2 = rt.submit(build(2), {"x": np.float64(10.0)})
        h3 = rt.submit(build(3), {"x": np.float64(10.0)})
        assert float(h2.result(30)["y"]) == 20.0
        assert float(h3.result(30)["y"]) == 30.0, \
            "tenant ran another tenant's cached executable"


def test_compile_cache_distinguishes_exec_compiled_bodies():
    """Exec-compiled step fns share '<string>:1' location metadata; the
    cache key must compare code by value AND globals identity, while
    identical code rebuilt in the same environment still hits."""
    from repro.core.migration import step_code_key

    def make(src, env):
        exec(src, env)
        wf = Workflow("execwf")
        wf.var("x")
        return wf.step("f", env["f"], inputs=("x",), outputs=("y",),
                       remotable=True, jax_step=False)

    shared_env = {}
    a = make("def f(x):\n    return {'y': x + 1}\n", shared_env)
    b = make("def f(x):\n    return {'y': x * 2}\n", {})
    a2 = make("def f(x):\n    return {'y': x + 1}\n", shared_env)
    assert step_code_key(a) != step_code_key(b), \
        "different exec'd bodies collided in the compile cache"
    assert step_code_key(a) == step_code_key(a2), \
        "identical code rebuilt in the same environment missed the cache"
    # equal code under DIFFERENT globals can read different module state
    # (e.g. `x * SCALE`) — must be a safe miss, never a shared hit
    ga = make("def f(x):\n    return {'y': x * SCALE}\n", {"SCALE": 2})
    gb = make("def f(x):\n    return {'y': x * SCALE}\n", {"SCALE": 3})
    assert ga.fn.__code__ == gb.fn.__code__       # the trap being tested
    assert step_code_key(ga) != step_code_key(gb), \
        "identical code under different globals shared a cache entry"


def test_close_drains_in_flight_but_does_not_run_the_rest():
    """close() mid-run lets in-flight steps finish but must NOT keep
    unlocking successors; the pending run fails with RuntimeClosed."""
    from repro.core import RuntimeClosed
    rt = EmeraldRuntime(emerald(), max_workers=2)
    h = rt.submit(chain_wf("longchain", 8, 0.15), {"x": np.float64(1.0)})
    time.sleep(0.2)                    # a step or two in flight
    t0 = time.perf_counter()
    rt.close()
    assert time.perf_counter() - t0 < 2.0, \
        "close() ran the whole chain instead of draining"
    with pytest.raises(RuntimeClosed):
        h.result(5)


def test_submit_after_close_never_hangs():
    from repro.core import RuntimeClosed
    rt = EmeraldRuntime(emerald())
    rt.close()
    with pytest.raises(RuntimeClosed):
        rt.submit(chain_wf("late", 1, 0.01), {"x": np.float64(1.0)})


def test_owned_runtime_reaped_without_result_call():
    """A submit() whose caller cancels and never calls result() must not
    leak the executor's private runtime (driver thread + pools)."""
    mgr = emerald()
    ex = EmeraldExecutor(partition(chain_wf("reapme", 3, 0.05)), mgr)
    h = ex.submit({"x": np.float64(1.0)})
    h.cancel()
    assert h.wait(10)

    def driver_alive():
        return any(t.name == "emerald-driver:emerald-reapme"
                   for t in threading.enumerate())

    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and driver_alive():
        time.sleep(0.02)
    assert not driver_alive(), \
        "private runtime leaked after cancel without result()"


def test_overlapping_checkpointed_executor_submits_refused(tmp_path):
    mgr = emerald()
    wf = chain_wf("ckol", 2, 0.2)
    with EmeraldRuntime(mgr) as rt:
        ex = EmeraldExecutor(partition(wf), mgr, runtime=rt,
                             checkpoint_dir=str(tmp_path))
        h = ex.submit({"x": np.float64(1.0)})
        with pytest.raises(RuntimeError, match="overlapping"):
            ex.submit({"x": np.float64(2.0)})
        assert float(h.result(30)["y2"]) == 4.0
        # sequential reuse stays fine
        assert float(ex.run({"x": np.float64(2.0)})["y2"]) == 8.0


def test_checkpoint_write_failure_fails_run_not_runtime(tmp_path):
    """An unwritable checkpoint fails THAT run (durability contract) but
    the driver survives and keeps serving other tenants."""
    from repro.core.runtime import RunCheckpointer

    class BadCkpt(RunCheckpointer):
        def _save_checkpoint(self, completed):
            raise OSError("injected: disk full")

    with EmeraldRuntime(emerald()) as rt:
        wf = chain_wf("ckfail", 2, 0.01)
        ck = BadCkpt(rt.mdss.namespaced("z", shared=rt.shared_namespace),
                     wf, str(tmp_path))
        h = rt.submit(wf, {"x": np.float64(1.0)}, namespace="z",
                      checkpointer=ck)
        with pytest.raises(OSError):
            h.result(30)
        # the runtime is still alive for other tenants
        h2 = rt.submit(chain_wf("fine", 2, 0.01), {"x": np.float64(1.0)})
        assert float(h2.result(30)["y2"]) == 4.0


def test_resume_does_not_privatize_shared_data(tmp_path):
    """Checkpoints must not capture variables resolving to the shared
    namespace: resume would write a private (stale, re-staged) copy of
    data meant to be stored once and read live."""
    mgr = emerald()
    big = np.ones((32, 1024), np.float64)
    state = {"crash": True}

    def build():
        wf = Workflow("sharedck")
        wf.var("C")

        def use(C):
            if state["crash"]:
                raise StepFailure("injected")
            return {"out": np.float64(C.sum())}

        wf.step("use", use, inputs=("C",), outputs=("out",), remotable=True,
                jax_step=False, retries=0)
        return wf

    with EmeraldRuntime(mgr, checkpoint_dir=str(tmp_path)) as rt:
        rt.publish("C", big)
        h = rt.submit(build(), {}, namespace="job")
        with pytest.raises(Exception):
            h.result(30)
        state["crash"] = False
        h2 = rt.submit(build(), {}, namespace="job", resume=True)
        assert float(h2.result(30)["out"]) == big.sum()
        # the run's namespace holds its OWN output, never a private copy
        # of the shared constant
        entries = mgr.mdss.namespace_entries("job")
        assert "job/out" in entries and "job/C" not in entries


# ------------------------------------------------------------- satellites
def test_alternate_tier_picks_lowest_estimated_exec_time():
    tiers = {
        "local": Tier("local", chips=1, peak_flops_per_chip=1e12,
                      hbm_bw_per_chip=1e11),
        "cloud": Tier("cloud", chips=4, peak_flops_per_chip=1e12,
                      hbm_bw_per_chip=1e11),
        "cloudA": Tier("cloudA", chips=2, peak_flops_per_chip=1e12,
                       hbm_bw_per_chip=1e11),
        "cloudB": Tier("cloudB", chips=8, peak_flops_per_chip=1e12,
                       hbm_bw_per_chip=1e11),
    }
    cm = CostModel(tiers)
    mgr = MigrationManager(tiers, MDSS(tiers, cost_model=cm), cm)
    wf = Workflow("alt")
    wf.var("x")
    s = wf.step("s", lambda x: {"y": x}, inputs=("x",), outputs=("y",),
                remotable=True, jax_step=False)
    with EmeraldRuntime(mgr) as rt:
        # no estimates: deterministic declaration order (cloudA first)
        assert rt._alternate_tier(s, "cloud") == "cloudA"
        # measured estimates flip the choice to the fastest backup —
        # dict order would have kept cloudA
        cm.stats_for("s").observe("cloud", 0.3)
        cm.stats_for("s").observe("cloudA", 0.5)
        cm.stats_for("s").observe("cloudB", 0.1)
        assert rt._alternate_tier(s, "cloud") == "cloudB"
        # the straggling tier itself and local are never candidates
        assert rt._alternate_tier(s, "cloudB") == "cloud"
        assert rt._alternate_tier(s, "local") in ("cloud", "cloudA",
                                                  "cloudB")


def test_ensure_bounded_wait_raises_transfer_error():
    tiers = default_tiers()
    m = MDSS(tiers, cost_model=CostModel(tiers))
    m.put("a", np.arange(8), tier="local")
    m.transfer_wait_s = 0.01
    m.max_transfer_waits = 3
    # a peer "transfer" that never completes
    m._inflight[("a", "cloud")] = threading.Event()
    t0 = time.perf_counter()
    with pytest.raises(MDSSTransferError):
        m.ensure(["a"], "cloud")
    assert time.perf_counter() - t0 < 5.0, "retried far past the bound"


def test_stuck_transfer_maps_to_step_failure_and_fallback():
    """A wedged in-flight transfer surfaces as StepFailure at staging, so
    the executor's retry/fallback path finishes the step locally."""
    mgr = emerald()
    mdss = mgr.mdss
    mdss.transfer_wait_s = 0.01
    mdss.max_transfer_waits = 2
    wf = Workflow("stuck")
    wf.var("x")
    wf.step("s", lambda x: {"y": np.float64(x) + 1}, inputs=("x",),
            outputs=("y",), remotable=True, jax_step=False, retries=1)
    ex = EmeraldExecutor(partition(wf), mgr)
    mdss.put("x", np.float64(1.0), tier="local")
    mdss._inflight[("x", "cloud")] = threading.Event()   # never completes
    out = ex.run({"x": np.float64(1.0)})
    assert float(out["y"]) == 2.0
    kinds = [(e.kind, e.tier) for e in ex.events
             if e.step == "s" and e.kind in ("retry", "offload")]
    assert ("retry", "cloud") in kinds
    assert ("offload", "local") in kinds


def test_missing_entry_staging_maps_to_step_failure():
    """A URI vanished from the store (namespace dropped mid-run) must
    surface as StepFailure — owned by retry/fallback — not a raw
    KeyError that bypasses the recovery path."""
    mgr = emerald()
    wf = Workflow("gone")
    wf.var("x")
    s = wf.step("s", lambda x: {"y": x}, inputs=("x",), outputs=("y",),
                remotable=True, jax_step=False)
    with pytest.raises(StepFailure, match="staging inputs"):
        mgr._stage_inputs(s, "cloud", ["x"], mgr.mdss)   # never written


def test_cost_model_policy_explain_reports_bandwidth_source():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    pol = CostModelPolicy(cm, mdss, "cloud")
    wf = Workflow("explain")
    wf.var("x")
    s = wf.step("s", lambda x: {"y": x}, inputs=("x",), outputs=("y",),
                remotable=True, flops_hint=1e15, bytes_hint=8.0)
    big = np.ones(4096, np.float64)
    mdss.put("x", big, tier="local")
    d = pol.explain(s)
    assert d["bw_source"] == "static" and d["bw_bytes_per_s"] is None
    assert d["stale_in_bytes"] == big.nbytes
    assert d["benefit_s"] > 0.0 and pol.should_offload(s)
    # an observed wire sample flips the reported source and feeds the rate
    cm.observe_bandwidth("local", "cloud", nbytes=1e6, seconds=0.001)
    d2 = pol.explain(s)
    assert d2["bw_source"] == "observed"
    assert d2["bw_bytes_per_s"] == pytest.approx(1e9)
    # once staged, the stale footprint the decision charges drops to zero
    mdss.ensure(["x"], "cloud")
    assert pol.explain(s)["stale_in_bytes"] == 0


def test_put_many_fences_absent_entry_with_nonzero_expectation():
    tiers = default_tiers()
    m = MDSS(tiers, cost_model=CostModel(tiers))
    # absent entry + nonzero expectation: stale expectation, must fence
    assert m.put_many({"ghost": np.zeros(2)}, tier="local",
                      expect_versions={"ghost": 3}) is None
    assert m.fenced_puts == 1
    assert m.version("ghost") == 0, "fenced batch mutated the store"
    # absent entry + zero expectation: a legitimate first write
    got = m.put_many({"ghost": np.zeros(2)}, tier="local",
                     expect_versions={"ghost": 0})
    assert got == {"ghost": 1}
    # all-or-nothing: one stale member fences the whole batch
    assert m.put_many({"ghost": np.ones(2), "other": np.ones(2)},
                      tier="local",
                      expect_versions={"ghost": 0, "other": 0}) is None
    assert m.version("ghost") == 1 and m.version("other") == 0


def test_namespaced_fence_tokens_block_cross_boundary_collision():
    """shared/u at v1 and a later private run/u at v1 must not satisfy
    the same fence: a speculation loser snapshotting against the shared
    entry cannot republish over the winner's private copy."""
    tiers = default_tiers()
    base = MDSS(tiers, cost_model=CostModel(tiers))
    base.put("shared/u", np.float64(0.0), tier="local")     # shared v1
    view = base.namespaced("run1", shared="shared")
    tokens = view.fence_tokens(["u"])
    assert tokens["u"] == ("shared/u", 1, 0)
    # the winner publishes: resolution still shared/u v1 -> fence passes
    assert view.put_many({"u": np.float64(1.0)}, tier="local",
                         expect_versions=tokens) is not None
    assert base.version("run1/u") == 1
    # the loser re-fences with the SAME stale tokens: the resolution has
    # moved to the private copy (also v1) — bare numbers would pass here
    assert view.put_many({"u": np.float64(2.0)}, tier="local",
                         expect_versions=tokens) is None
    assert float(view.get("u", "local")) == 1.0, "loser clobbered winner"
    # int compat path still works for in-run WAW fencing
    assert view.put_many({"u": np.float64(3.0)}, tier="local",
                         expect_versions={"u": 1}) is not None


def test_fenced_write_back_cannot_resurrect_dropped_namespace():
    """A draining step's publish after drop_namespace must be refused
    (epoch fence), while a NEW submission reusing the namespace name
    snapshots the new epoch and writes normally."""
    tiers = default_tiers()
    base = MDSS(tiers, cost_model=CostModel(tiers))
    view = base.namespaced("job", shared="shared")
    # an in-flight step snapshots tokens for its never-written output
    tokens = view.fence_tokens(["out"])
    assert tokens["out"] == ("job/out", 0, 0)
    base.drop_namespace("job")                 # release() while draining
    assert view.put_many({"out": np.ones(1024)}, tier="local",
                         expect_versions=tokens) is None
    assert base.namespace_entries("job") == [], \
        "write-back resurrected the dropped namespace"
    # deliberate reuse of the name: fresh tokens carry the new epoch
    fresh = view.fence_tokens(["out"])
    assert fresh["out"] == ("job/out", 0, 1)
    assert view.put_many({"out": np.zeros(2)}, tier="local",
                         expect_versions=fresh) is not None


def test_broker_priority_classes():
    Fabric = pytest.importorskip("repro.cloud").Fabric
    order = []
    with Fabric(workers=1) as fabric:
        blocker = fabric.broker.submit(step="spin",
                                       kwargs={"seconds": 0.3})
        time.sleep(0.05)           # ensure the worker is busy on blocker
        low = fabric.broker.submit(step="spin", kwargs={"seconds": 0.01})
        high = fabric.broker.submit(step="spin", kwargs={"seconds": 0.01},
                                    priority=1)
        low.add_done_callback(lambda t: order.append("low"))
        high.add_done_callback(lambda t: order.append("high"))
        blocker.result(30)
        low.result(30)
        high.result(30)
    assert order == ["high", "low"], \
        "interactive-class task did not overtake the queued batch task"


def test_duplicate_done_does_not_double_decrement_indegrees():
    """Regression: a duplicate "done" harvest (a speculation loser
    surfacing after the winner, or a replayed message) must be ignored —
    before the `_outstanding` guard it double-decremented successor
    in-degrees, dispatching a join step while its slow input was still
    in flight, and corrupted the lane-slot accounting."""
    wf = Workflow("dupdone")
    wf.var("x")
    wf.step("a", sleeper("a", 0.01, "ya"), inputs=("x",), outputs=("ya",),
            remotable=True, jax_step=False)
    wf.step("y", sleeper("y", 0.4, "yy"), inputs=("x",), outputs=("yy",),
            remotable=True, jax_step=False)
    wf.step("z", lambda ya, yy: {"z": np.float64(float(ya) + float(yy))},
            inputs=("ya", "yy"), outputs=("z",), remotable=True,
            jax_step=False)
    rt = EmeraldRuntime(emerald(), max_workers=2)
    try:
        h = rt.submit(wf, {"x": np.float64(1.0)})
        deadline = time.monotonic() + 10
        while not any(e.kind == "step_done" and e.step == "a"
                      for e in list(h.events)):
            assert time.monotonic() < deadline, "step a never completed"
            time.sleep(0.005)
        # replay a's completion while y is still in flight
        rt._inbox.put(("done", h.run_id, "a", None, True))
        out = h.result(30)
        assert float(out["z"]) == 4.0, "join step read a hole"
        dones = [e for e in h.events
                 if e.kind == "step_done" and e.step == "a"]
        assert len(dones) == 1, "duplicate step_done emitted"
    finally:
        rt.close()
    assert rt._busy == {True: 0, False: 0}, \
        "duplicate done corrupted lane-slot accounting"


def test_checkpoint_writes_off_driver_with_completion_fence(tmp_path):
    """Checkpoint pickles run on the dedicated writer lane, the driver
    keeps serving other tenants while a write blocks, and a run's handle
    only resolves after its final checkpoint is durable."""
    from repro.core.runtime import RunCheckpointer

    gate = threading.Event()

    class BlockingCkpt(RunCheckpointer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.threads = []
            self.writes = []

        def _save_checkpoint(self, completed):
            self.threads.append(threading.current_thread().name)
            assert gate.wait(10), "test gate never opened"
            super()._save_checkpoint(completed)
            self.writes.append(set(completed))

    mgr = emerald()
    with EmeraldRuntime(mgr, max_workers=4) as rt:
        wfa = chain_wf("cka", 3, 0.02)
        ck = BlockingCkpt(
            rt.mdss.namespaced("nsa", shared=rt.shared_namespace), wfa,
            str(tmp_path), ckpt_name="nsa.cka")
        h = rt.submit(wfa, {"x": np.float64(1.0)}, namespace="nsa",
                      checkpointer=ck)
        # while A's first write is parked on the gate, another tenant's
        # whole run completes: the driver loop is not serialized by the
        # pickle (it used to be)
        hb = rt.submit(chain_wf("ckb", 3, 0.01), {"x": np.float64(1.0)})
        assert float(hb.result(10)["y3"]) == 8.0
        # let every step of A finish while the first write stays gated,
        # so the dirt provably coalesces into ONE follow-up write
        deadline = time.monotonic() + 10
        while sum(1 for e in list(h.events) if e.kind == "step_done") < 3:
            assert time.monotonic() < deadline, "run A never finished"
            time.sleep(0.005)
        assert not h.done(), "run resolved before its checkpoint landed"
        gate.set()
        assert float(h.result(10)["y3"]) == 8.0
        # completion fence: the last write that hit disk covers the whole
        # run, and it happened on the checkpoint lane, not the driver
        assert ck.writes and ck.writes[-1] == {"s1", "s2", "s3"}
        assert all("ckpt" in t for t in ck.threads), ck.threads
        # coalescing: completions that landed while the writer was
        # blocked merged into one write instead of queueing three
        assert len(ck.writes) < 3
        import pickle as _pickle
        with open(tmp_path / "nsa.cka.wfckpt", "rb") as f:
            state = _pickle.load(f)
        assert set(state["completed"]) == {"s1", "s2", "s3"}


def test_flush_orphaned_inbox_resolves_raced_submit():
    """A submit that raced close() (entry check passed, driver already
    exited) must resolve with RuntimeClosed instead of hanging — the
    dead-driver inbox flush owns it."""
    from types import SimpleNamespace
    from repro.core import RuntimeClosed
    from repro.core.runtime import RunHandle

    rt = EmeraldRuntime(emerald())
    rt.close()
    assert not rt._driver.is_alive()
    handle = RunHandle("raced#1", "", rt, [])
    rt._inbox.put(("submit", SimpleNamespace(handle=handle)))
    rt._flush_orphaned_inbox()
    assert handle.done() and handle.state == "failed"
    with pytest.raises(RuntimeClosed):
        handle.result(1)


def test_autoscaler_sees_runtime_backlog():
    from repro.cloud.autoscaler import Autoscaler, AutoscalerConfig

    class StubBroker:
        def queue_depth(self):
            return 0

        def num_workers(self, include_warm=False):
            return 1

        def avg_task_seconds(self):
            return None

    cfg = AutoscalerConfig(min_workers=1, max_workers=4, queue_high=2.0)
    sc = Autoscaler(StubBroker(), cfg)
    assert sc.desired_workers() == 1          # no pressure anywhere
    sc.backlog_fn = lambda: 10                # cross-run ready offloads
    assert sc.desired_workers() == 4          # aggregate pressure scales up


def test_runtime_offload_backlog_counts_ready_steps():
    with EmeraldRuntime(emerald(), max_workers=2) as rt:
        assert rt.offload_backlog() == 0
        h = rt.submit(wide_wf("backlog", 8, 0.05), {"x": np.float64(0.0)})
        deadline = time.monotonic() + 5
        seen = 0
        while time.monotonic() < deadline:
            now = rt.offload_backlog()
            # capped at lane width: the broker can't be fed more than that
            assert now <= rt.max_workers
            seen = max(seen, now)
            if seen >= 2:
                break
            time.sleep(0.005)
        assert seen >= 2, "ready-but-unlaned steps not visible as backlog"
        h.result(30)
        assert rt.offload_backlog() == 0
