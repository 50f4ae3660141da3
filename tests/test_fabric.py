"""Offload-fabric tests: wire format, process-separated dispatch, real
byte accounting through RPCTransport, worker-crash requeue, elastic
autoscaling with warm-pool reuse."""
import os
import socket
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.cloud import (Autoscaler, AutoscalerConfig, Fabric, FabricError,
                         RemoteStepError, ShipTimeout, WorkerLostError,
                         attach)
from repro.cloud.wire import decode, encode, recv_msg, send_msg
from repro.core import (CostModel, EmeraldExecutor, MDSS, MigrationManager,
                        Workflow, default_tiers, partition)


# --------------------------------------------------------------- wire format
def nested_value():
    return {
        "params": {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
                   "b": np.zeros(4, dtype=np.float64)},
        "meta": ("adam", 3, 0.1, None, b"blob"),
        "history": [np.int32(7), {"nested": [np.ones((2, 2, 2))]}],
        "flag": True,
        "name": "step-0",
    }


def assert_trees_equal(a, b):
    if isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            assert_trees_equal(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b) and type(a) is type(b)
        for x, y in zip(a, b):
            assert_trees_equal(x, y)
    elif isinstance(a, np.ndarray):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    else:
        assert a == b


def test_wire_roundtrip_nested_pytree():
    val = nested_value()
    data = encode(val)
    assert len(data) > sum(a.nbytes for a in (val["params"]["w"],
                                              val["params"]["b"]))
    assert_trees_equal(decode(data), val)


def test_wire_roundtrip_jax_arrays_become_numpy():
    out = decode(encode({"x": jnp.arange(8.0), "s": jnp.float32(2.0)}))
    assert isinstance(out["x"], np.ndarray)
    np.testing.assert_array_equal(out["x"], np.arange(8.0))
    np.testing.assert_array_equal(out["s"], np.float32(2.0))


def test_wire_framing_over_socket():
    a, b = socket.socketpair()
    msgs = [{"op": "x", "v": np.arange(1000)}, {"op": "y"}, [1, 2, 3]]
    sent = []

    def writer():
        for m in msgs:
            sent.append(send_msg(a, m))

    t = threading.Thread(target=writer)
    t.start()
    received = [recv_msg(b) for _ in msgs]
    t.join()
    assert len(sent) == len(msgs)
    for m, n, (got, nread) in zip(msgs, sent, received):
        assert_trees_equal(got, m)
        assert nread == n
    a.close()
    b.close()


# ------------------------------------------------------------ shared fabric
@pytest.fixture(scope="module")
def fabric():
    with Fabric(workers=2) as f:
        yield f


def test_step_runs_in_separate_process(fabric):
    out = fabric.broker.submit(step="pid").result(30)
    assert int(out["pid"]) != os.getpid()
    assert int(out["pid"]) in fabric.broker.worker_pids()


def test_worker_never_takes_the_accelerator(monkeypatch):
    """Workers run jax on the CPU whatever the driver's platform: the
    driver process holds the chip, and a second process cannot take it."""
    import pickle
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with Fabric(workers=1) as f:
        out = f.broker.submit(fn_bytes=pickle.dumps(os.getenv),
                              kwargs={"key": "JAX_PLATFORMS"}).result(30)
    assert out == "cpu"


def test_ship_moves_real_bytes(fabric):
    val = {"a": np.random.rand(1 << 12).astype(np.float32)}
    task = fabric.ship(val)
    np.testing.assert_array_equal(task.value["a"], val["a"])
    assert task.bytes_sent > val["a"].nbytes
    # the echo direction dedups against the request's own chunks: the
    # payload comes back as digest references, not bytes
    assert task.bytes_received < 4096
    assert task.seconds > 0


def test_ship_timeout_cancels_queued_task():
    """A ship that times out while still QUEUED is withdrawn: no worker
    ever receives it, and its future resolves (failed) instead of the
    orphaned result landing in a dead inbox."""
    with Fabric(workers=1) as fabric:
        blocker = fabric.broker.submit(step="sleep",
                                       kwargs={"seconds": 0.5})
        time.sleep(0.05)                     # the only worker is busy
        with pytest.raises(ShipTimeout) as ei:
            fabric.ship({"a": np.arange(4)}, timeout=0.05)
        t = ei.value.task
        assert fabric.broker.queue_depth() == 0, \
            "timed-out ship left an orphan in the queue"
        assert fabric.broker.tasks_cancelled == 1
        with pytest.raises(FabricError, match="cancelled"):
            t.result(1)                      # resolved, not a dead inbox
        blocker.result(30)
        assert fabric.broker.tasks_done == 1, \
            "a worker burned a slot on the cancelled ship"


def test_ship_timeout_inflight_task_stays_harvestable():
    """A ship that times out while IN FLIGHT is not lost: the exception
    carries the task and the eventual worker reply is harvestable."""
    with Fabric(workers=1) as fabric:
        val = {"a": np.random.rand(1 << 22).astype(np.float64)}   # 32 MiB
        # 5 ms: far longer than the idle dispatcher needs to pop the
        # queue, far shorter than a 32 MiB round trip
        with pytest.raises(ShipTimeout) as ei:
            fabric.ship(val, timeout=0.005)
        t = ei.value.task
        if fabric.broker.tasks_cancelled:
            pytest.skip("dispatcher lost the 5 ms race on a loaded box; "
                        "the queued branch is covered above")
        out = t.result(30)                   # the reply still arrives
        np.testing.assert_array_equal(out["a"], val["a"])
        assert fabric.broker.tasks_cancelled == 0


def test_remote_exception_keeps_worker_alive(fabric, tmp_path):
    n_before = fabric.broker.num_workers()
    t = fabric.broker.submit(step="fail_n_times", kwargs={
        "counter_file": str(tmp_path / "fails"), "n_fails": 99, "x": 0.0})
    with pytest.raises(RemoteStepError, match="injected step failure"):
        t.result(30)
    assert fabric.broker.num_workers() == n_before


def test_worker_crash_requeues_task(fabric, tmp_path):
    before = fabric.broker.tasks_requeued
    t = fabric.broker.submit(step="crash_n_times", kwargs={
        "counter_file": str(tmp_path / "crashes"), "n_crashes": 1, "x": 5.0})
    out = t.result(60)
    assert float(out["y"]) == 6.0
    assert fabric.broker.tasks_requeued == before + 1
    assert fabric.broker.workers_lost >= 1


def test_requeue_budget_exhaustion_raises(fabric, tmp_path):
    t = fabric.broker.submit(step="crash_n_times", max_attempts=1, kwargs={
        "counter_file": str(tmp_path / "always"), "n_crashes": 99, "x": 0.0})
    with pytest.raises(WorkerLostError):
        t.result(60)


# ---------------------------------------------------- MDSS / RPC transport
def test_rpc_transport_accounts_real_movement(fabric):
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    transport = attach(tiers, fabric, mdss=mdss, cost_model=cm)
    val = {"w": np.random.rand(256, 16).astype(np.float32)}
    mdss.put("params", val, tier="local")
    assert mdss.stale_bytes(["params"], "cloud") == val["w"].nbytes
    moved = mdss.ensure(["params"], "cloud")
    assert moved == val["w"].nbytes
    np.testing.assert_array_equal(mdss.get("params", "cloud")["w"], val["w"])
    # the value crossed a process boundary: wire counters and observed bw
    assert transport.total_bytes_shipped() > val["w"].nbytes
    assert cm.measured_bw[("local", "cloud")] > 0
    # second ensure is a no-op (fresh replica): nothing moves
    assert mdss.ensure(["params"], "cloud") == 0


def test_cost_model_uses_observed_bandwidth():
    tiers = default_tiers()
    cm = CostModel(tiers)
    static = cm.transfer_time(1e6, "local", "cloud")
    cm.observe_bandwidth("local", "cloud", 1e6, 0.01)   # 100 MB/s observed
    observed = cm.transfer_time(1e6, "local", "cloud")
    assert observed != static
    assert abs(observed - (tiers["local"].link_latency_s + 0.01)) < 1e-6


# --------------------------------------------------- workflow through fabric
def test_workflow_offload_executes_in_worker(fabric):
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    attach(tiers, fabric, mdss=mdss, cost_model=cm)
    mgr = MigrationManager(tiers, mdss, cm)
    wf = Workflow("fab")
    wf.var("x")
    wf.step("grow", None, inputs=("x",), outputs=("y",), remotable=True,
            jax_step=False, remote_impl="add_one")
    wf.step("sq", lambda y: {"z": y * y}, inputs=("y",), outputs=("z",))
    ex = EmeraldExecutor(partition(wf), mgr)
    out = ex.run({"x": np.float64(4.0)})
    assert float(out["z"]) == 25.0
    off = [e for e in ex.events if e.kind == "offload"][0]
    assert off.info["remote"] is True
    assert off.info["worker_pid"] not in (0, os.getpid())
    assert off.info["bytes_in"] > 0 and off.info["bytes_out"] > 0


def test_workflow_survives_worker_crash(fabric, tmp_path):
    """Acceptance: a worker dies mid-step, the broker requeues onto the
    surviving worker, and the workflow completes."""
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    attach(tiers, fabric, mdss=mdss, cost_model=cm)
    mgr = MigrationManager(tiers, mdss, cm)
    wf = Workflow("crashy")
    wf.var("x")
    wf.var("counter_file")
    wf.step("s", None, inputs=("counter_file", "x"), outputs=("y",),
            remotable=True, jax_step=False, remote_impl="crash_n_times")
    before = fabric.broker.tasks_requeued
    ex = EmeraldExecutor(partition(wf), mgr)
    out = ex.run({"x": np.float64(1.0),
                  "counter_file": str(tmp_path / "wfcrash")})
    assert float(out["y"]) == 2.0
    assert fabric.broker.tasks_requeued == before + 1
    off = [e for e in ex.events if e.kind == "offload"][0]
    assert off.info["remote"] is True and off.info["attempt"] == 0, \
        "requeue should be broker-level, invisible to the executor"


# --------------------------------------------------------------- autoscaler
def test_autoscaler_scales_up_down_and_reuses_warm_workers():
    cfg = AutoscalerConfig(min_workers=1, max_workers=3, queue_high=1.0,
                           idle_scale_down_s=0.05, warm_ttl_s=60.0)
    with Fabric(workers=1, autoscaler=cfg) as f:
        a = f.autoscaler
        assert f.broker.num_workers() == 1
        tasks = [f.broker.submit(step="sleep", kwargs={"seconds": 0.2})
                 for _ in range(6)]
        act = a.tick()
        assert act["added"] >= 1 and f.broker.num_workers() > 1
        for t in tasks:
            t.result(30)
        pids_at_peak = set(f.broker.worker_pids())
        # idle dwell -> retire down to min, one per tick
        deadline = time.monotonic() + 10
        while f.broker.num_workers() > 1 and time.monotonic() < deadline:
            time.sleep(0.06)
            a.tick()
        assert f.broker.num_workers() == 1
        assert f.broker.num_workers(include_warm=True) > 1, \
            "scale-down should park workers warm, not kill them"
        # scale-up reuses a warm process: same pid, counted as a warm hit
        hits = f.broker.warm_hits
        f.broker.add_worker()
        assert f.broker.warm_hits == hits + 1
        assert set(f.broker.worker_pids()) <= pids_at_peak
        # warm TTL reap actually kills parked workers
        assert f.broker.reap_warm(0.0) >= 0
        assert f.broker.num_workers(include_warm=True) == \
            f.broker.num_workers()


def test_autoscaler_desired_workers_uses_task_duration():
    with Fabric(workers=1) as f:
        cfg = AutoscalerConfig(min_workers=1, max_workers=8, queue_high=100.0,
                               target_drain_s=0.5)
        a = Autoscaler(f.broker, cfg)
        f.broker.submit(step="sleep", kwargs={"seconds": 0.25}).result(30)
        assert f.broker.avg_task_seconds() is not None
        for _ in range(8):
            f.broker.submit(step="sleep", kwargs={"seconds": 0.25})
        # ~8 queued * 0.25s / 0.5s target -> ~4 workers wanted (cost signal,
        # queue_high alone would never trip at 100)
        assert a.desired_workers() >= 3
