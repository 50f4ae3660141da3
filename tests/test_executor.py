"""Executor tests: lifecycle, parallel offload, fault tolerance, policies,
straggler speculation, workflow checkpoint/resume."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CostModel, EmeraldExecutor, MDSS, MigrationManager,
                        StepFailure, Workflow, WorkflowFailure, default_tiers,
                        partition)


def emerald():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    return MigrationManager(tiers, mdss, cm)


def linear_wf():
    wf = Workflow("lin")
    wf.var("x")
    wf.step("a", lambda x: {"y": x + 1}, inputs=("x",), outputs=("y",))
    wf.step("b", lambda y: {"z": y * 2}, inputs=("y",), outputs=("z",),
            remotable=True)
    wf.step("c", lambda z: {"w": z - 3}, inputs=("z",), outputs=("w",))
    return wf


def test_suspend_offload_resume_alternate():
    mgr = emerald()
    ex = EmeraldExecutor(partition(linear_wf()), mgr)
    out = ex.run({"x": jnp.float32(5.0)})
    assert float(out["w"]) == (5 + 1) * 2 - 3
    kinds = [e.kind for e in ex.events if e.kind in ("suspend", "offload",
                                                     "resume")]
    assert kinds == ["suspend", "offload", "resume"]    # P3: alternation


def test_policy_never_keeps_everything_local():
    mgr = emerald()
    ex = EmeraldExecutor(partition(linear_wf()), mgr, policy="never")
    out = ex.run({"x": jnp.float32(1.0)})
    assert float(out["w"]) == 1.0
    assert all(e.kind != "offload" for e in ex.events)


def test_parallel_steps_offload_concurrently():
    wf = Workflow("par")
    wf.var("x")
    order = []

    def slow(tag):
        def fn(x):
            order.append((tag, "start"))
            time.sleep(0.15)
            order.append((tag, "end"))
            return {f"y{tag}": np.asarray(float(x) + 1)}
        return fn

    wf.step("p1", slow(1), inputs=("x",), outputs=("y1",), remotable=True,
            jax_step=False)
    wf.step("p2", slow(2), inputs=("x",), outputs=("y2",), remotable=True,
            jax_step=False)
    mgr = emerald()
    ex = EmeraldExecutor(partition(wf), mgr)
    t0 = time.perf_counter()
    ex.run({"x": np.float64(0.0)})
    dt = time.perf_counter() - t0
    starts = [i for i, (t, k) in enumerate(order) if k == "start"]
    assert starts[:2] == [0, 1], f"steps did not overlap: {order}"
    assert dt < 0.29, "parallel steps ran sequentially"


def test_retry_then_success():
    fails = {"n": 2}

    def flaky(x):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise StepFailure("injected node failure")
        return {"y": x + 1}

    wf = Workflow("flaky")
    wf.var("x")
    wf.step("s", flaky, inputs=("x",), outputs=("y",), remotable=True,
            jax_step=False, retries=3)
    mgr = emerald()
    ex = EmeraldExecutor(partition(wf), mgr)
    out = ex.run({"x": 1.0})
    assert out["y"] == 2.0
    assert sum(1 for e in ex.events if e.kind == "retry") == 2


def test_fallback_to_local_after_cloud_dead():
    calls = []

    def cloud_dead(x):
        # the migration manager reports the tier via thread context; infer
        # from call count: first attempts are cloud (retries), last is local
        calls.append(1)
        if len(calls) <= 2:
            raise StepFailure("cloud node lost")
        return {"y": x * 10}

    wf = Workflow("dead")
    wf.var("x")
    wf.step("s", cloud_dead, inputs=("x",), outputs=("y",), remotable=True,
            jax_step=False, retries=2)
    mgr = emerald()
    ex = EmeraldExecutor(partition(wf), mgr)
    out = ex.run({"x": 3.0})
    assert out["y"] == 30.0
    offl = [e for e in ex.events if e.kind == "offload"]
    assert offl and offl[-1].tier == "local"     # final success was local


def test_total_failure_raises():
    def always(x):
        raise StepFailure("dead")

    wf = Workflow("dead2")
    wf.var("x")
    wf.step("s", always, inputs=("x",), outputs=("y",), remotable=True,
            jax_step=False, retries=1)
    ex = EmeraldExecutor(partition(wf), emerald())
    with pytest.raises(WorkflowFailure):
        ex.run({"x": 1.0})


def test_straggler_speculation():
    state = {"calls": 0}

    def sometimes_slow(x):
        state["calls"] += 1
        if state["calls"] == 2:          # second call (the straggler) hangs
            time.sleep(1.0)
        return {"y": np.asarray(float(x) + 1)}

    wf = Workflow("strag")
    wf.var("x")
    wf.step("s", sometimes_slow, inputs=("x",), outputs=("y",),
            remotable=True, jax_step=False)
    mgr = emerald()
    ex = EmeraldExecutor(partition(wf), mgr, speculate_after=2.0)
    ex.run({"x": 0.0})                   # seeds the runtime EMA
    t0 = time.perf_counter()
    out = ex.run({"x": 5.0})             # straggles -> speculative duplicate
    dt = time.perf_counter() - t0
    assert out["y"] == 6.0
    assert any(e.kind == "speculate" for e in ex.events)
    assert dt < 0.9, "speculation did not cut straggler latency"


def test_workflow_checkpoint_resume(tmp_path):
    state = {"crash": True}

    def mid(y):
        if state["crash"]:
            raise StepFailure("power loss")
        return {"z": y * 2}

    wf = Workflow("ck")
    wf.var("x")
    wf.step("a", lambda x: {"y": x + 1}, inputs=("x",), outputs=("y",),
            remotable=True)
    wf.step("b", mid, inputs=("y",), outputs=("z",), remotable=True,
            jax_step=False, retries=0)
    wf.step("c", lambda z: {"w": z + 0.5}, inputs=("z",), outputs=("w",))
    mgr = emerald()
    ex = EmeraldExecutor(partition(wf), mgr, checkpoint_dir=str(tmp_path))
    with pytest.raises(WorkflowFailure):
        ex.run({"x": jnp.float32(1.0)})
    # restart: step a's result restored from checkpoint, b now succeeds
    state["crash"] = False
    mgr2 = emerald()
    ex2 = EmeraldExecutor(partition(wf), mgr2, checkpoint_dir=str(tmp_path))
    out = ex2.run({"x": jnp.float32(1.0)}, resume=True)
    assert float(out["w"]) == (1 + 1) * 2 + 0.5
    ran = {e.step for e in ex2.events if e.kind in ("offload", "local")}
    assert "a" not in ran, "completed step re-ran after resume"


def test_cost_model_policy_prefers_local_for_tiny_steps():
    wf = Workflow("tiny")
    wf.var("x")
    wf.step("s", lambda x: {"y": x + 1}, inputs=("x",), outputs=("y",),
            remotable=True, flops_hint=10.0, bytes_hint=8.0)
    ex = EmeraldExecutor(partition(wf), emerald(), policy="cost_model")
    ex.run({"x": jnp.float32(1.0)})
    assert all(e.kind != "offload" for e in ex.events)


def test_cost_model_policy_offloads_heavy_steps():
    wf = Workflow("heavy")
    wf.var("x")
    wf.step("s", lambda x: {"y": x + 1}, inputs=("x",), outputs=("y",),
            remotable=True, flops_hint=1e15, bytes_hint=8.0)
    ex = EmeraldExecutor(partition(wf), emerald(), policy="cost_model")
    ex.run({"x": jnp.float32(1.0)})
    assert any(e.kind == "offload" for e in ex.events)


def test_jax_step_compiles_once_and_records_cost():
    """The first execution's cost analysis reuses the program the step
    runs: two runs of one jitted step compile it once."""
    from jax import monitoring
    compiles = []

    def on_duration(event, secs, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration" \
                and "compile_once_step" in fun_name:
            compiles.append(fun_name)

    def compile_once_step(x):
        return {"y": jnp.sin(x) @ x}

    wf = Workflow("once")
    wf.var("x")
    wf.step("s", compile_once_step, inputs=("x",), outputs=("y",),
            remotable=True)
    mgr = emerald()
    ex = EmeraldExecutor(partition(wf), mgr, policy="annotate")
    xs = [jnp.full((16, 16), float(i)) for i in range(2)]
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        outs = [ex.run({"x": x})["y"] for x in xs]
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(jnp.sin(x) @ x), rtol=1e-6)
    assert len(compiles) == 1, compiles
    assert mgr.cost_model.stats_for("s").flops > 0
