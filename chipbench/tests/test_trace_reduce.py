"""The trace reduction, on hand-made intervals and on recorded traces."""
import time
from pathlib import Path

import pytest

from chipbench import trace_reduce
from chipbench.harness import Call

DATA = Path(__file__).resolve().parent / "data"


def test_union_of_overlapping_intervals():
    assert trace_reduce._union([(0, 10), (5, 15), (20, 25), (21, 22)]) == 20
    assert trace_reduce._union([]) == 0


def test_self_time_subtracts_nested_ops():
    events = [("while", 0, 100), ("fusion", 10, 20), ("fusion", 40, 30),
              ("copy", 200, 5)]
    got = {}
    for name, t in trace_reduce._self_times(events):
        got[name] = got.get(name, 0) + t
    assert got == {"while": 50, "fusion": 50, "copy": 5}


def test_cpu_trace_window_mark(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_MARK):
        time.sleep(0.05)
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    s = trace_reduce.reduce(trace_reduce.newest_xplane(str(tmp_path)))
    assert 0.05 <= s.window_s < 1.0
    assert s.n_devices == 0 and s.busy_s == 0.0      # the CPU is no device


def test_breakdown_names_gaps_by_the_host_call():
    s = trace_reduce.Summary(window_s=1.0, busy_s=0.5, n_devices=1,
                             t0_ns=1e9, op_self_s={"a": 0.3, "b": 0.2},
                             op_count={"a": 3, "b": 1},
                             gaps=[(1.1e9, 0.2e9), (1.5e9, 0.05e9)],
                             perf0=100.0)
    calls = [Call("at_iter", 100.05, 100.4)]
    out = trace_reduce.breakdown(s, calls, spans=[], wall_minus_perf=0.0)
    assert out["device_ops"] == [["a", 0.3], ["b", 0.2]]
    assert out["idle_gaps"] == [["at_iter", 0.2], ["harness", 0.05]]


@pytest.mark.skipif(not (DATA / "tpu_small.xplane.pb").exists(),
                    reason="no recorded TPU trace")
def test_recorded_tpu_trace():
    s = trace_reduce.reduce(str(DATA / "tpu_small.xplane.pb"),
                            mark="bench:call")
    # one v5e chip; a while loop of six steps nests its body's ops
    assert s.n_devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert "%while" in s.op_count
    assert all(not n.count(" ") for n in s.op_count)   # names, not HLO text
    assert s.matching(["while("]) == (s.op_count["%while"],
                                      s.op_self_s["%while"])
