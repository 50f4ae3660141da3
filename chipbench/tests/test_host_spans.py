"""The runtime's host spans read from a trace, and the device's idle time
split by the layer open over it: on hand-made intervals, and on a traced
run of the AT cell on the CPU."""
import pytest

from chipbench import host_spans, trace_reduce
from chipbench.harness import Observation, Window
from chipbench.host_spans import HostSpans, idle_split


def test_latest_started_span_on_any_thread_takes_the_idle_time():
    # thread 1: install 0-100 with a hash inside 20-60; thread 2: drive
    # 40-50 (starts inside the hash), exec 70-90 (inside the install)
    spans = [(0, 100, "mdss"), (20, 60, "mdss"), (40, 50, "runtime"),
             (70, 90, "exec")]
    gaps = [(30, 20), (65, 10), (95, 10)]
    got = idle_split(spans, gaps, 0, 120)
    assert got == {"mdss": 10 + 5 + 5, "exec": 5, "runtime": 10,
                   "unspanned": 5}


def test_idle_with_no_span_open_is_unspanned():
    got = idle_split([(10, 20, "exec")], [(0, 10), (20, 30)], 0, 50)
    assert got == {"mdss": 0.0, "exec": 0.0, "runtime": 0.0,
                   "unspanned": 40}
    assert idle_split([], [(0, 50)], 0, 50)["unspanned"] == 50


def test_four_layers_sum_to_the_idle_time():
    import random
    rng = random.Random(7)
    t1 = 10_000
    spans = []
    for _ in range(300):
        a = rng.uniform(-50, t1)
        spans.append((a, a + rng.expovariate(1 / 40),
                      rng.choice(["mdss", "exec", "runtime"])))
    gaps, t = [], 0.0
    while t < t1:
        t += rng.expovariate(1 / 30)
        d = min(rng.expovariate(1 / 20), t1 - t)
        if d > 0:
            gaps.append((t, d))
        t += d
    got = idle_split(spans, gaps, 0, t1)
    assert sum(got.values()) == pytest.approx(sum(d for _, d in gaps),
                                              rel=1e-12)
    assert all(v > 0 for v in got.values())


def test_readers_per_iteration():
    hs = HostSpans(t0_ns=0, t1_ns=1e9, iterations=4, events=[
        ("submit", -1e6, 3e6), ("hash", 1e6, 2e6), ("hash", 5e6, 6e6),
        ("hash", 999e6, 1001e6)])
    obs = Observation(cell={"name": "at-fig12-inv"}, config={}, traffic={},
                      window=Window(1.0))
    obs.trace = trace_reduce.Summary(window_s=1.0, busy_s=0.9, n_devices=1,
                                     t0_ns=0,
                                     gaps=[(1.5e6, 1e6), (7e6, 2e6)])
    obs._host_spans = hs
    assert host_spans.span_ms(obs, ("submit",)) == pytest.approx(3 / 4)
    assert host_spans.span_ms(obs, ("hash",)) == pytest.approx(3 / 4)
    assert host_spans.span_ms(obs, ("reintegrate",)) is None
    # the hash ends inside the first gap; the submit it nested in is open
    assert host_spans.idle_ms(obs, "mdss") == pytest.approx(0.5 / 4)
    assert host_spans.idle_ms(obs, "runtime") == pytest.approx(0.5 / 4)
    assert host_spans.idle_ms(obs, "unspanned") == pytest.approx(2 / 4)
    assert host_spans.idle_ms(obs, "exec") == 0


def test_per_call_reads_every_annotation_over_whole_calls():
    from chipbench.harness import Call
    win = Window(10.0)
    t0 = win.t0
    obs = Observation(cell={"name": "c"}, config={}, traffic={}, window=win)
    obs.trace = trace_reduce.Summary(window_s=1.0, busy_s=0.9, n_devices=1,
                                     t0_ns=0)
    obs.trace.t0, obs.trace.t1 = t0, t0 + 1.0
    obs.calls = [Call("decode", t0 + 0.1, t0 + 0.2),
                 Call("decode", t0 + 0.3, t0 + 0.4),
                 Call("decode", t0 + 0.9, t0 + 1.1),   # ends after the trace
                 Call("prefill", t0, t0 + 0.05)]
    obs._host_spans = HostSpans(t0_ns=0, t1_ns=1e9, iterations=0, events=[
        ("deliver", 0.0, 2e6), ("deliver", 3e6, 4e6),
        ("deliver", 999e6, 1002e6), ("submit", 0.0, 1e6)])
    assert host_spans.per_call_ms(obs, ("deliver",), "decode") \
        == pytest.approx(4 / 2)
    assert host_spans.per_call_ms(obs, ("dispatch",), "decode") is None
    assert host_spans.per_call_ms(obs, ("deliver",), "at_iter") is None
    # no iteration marks: the per-iteration readers find nothing
    assert host_spans.span_ms(obs, ("submit",)) is None
    assert host_spans.idle_ms(obs, "runtime") is None


def test_cpu_traced_run_reports_the_host_span_metrics():
    """A traced run of the AT cell on the CPU: the program's annotations
    are found, one iteration per call. MDSS hashes nothing in this cell
    (device-resident values keep their manifests until a digest is asked
    for), so no ``d2h`` or ``hash`` phase opens. The CPU is no device, so
    no idle split."""
    from chipbench.run import TRACE_DIR
    from chipbench.tests.tiny import run
    r = run("at-fig12-inv", seconds=1.5, trace=True)
    m = r["metrics"]
    for name in ("submit_ms.at", "reintegrate_ms.at", "mdss_ms.at",
                 "exec_ms.at", "runtime_self_ms.at"):
        assert m[name]["value"] > 0, name
    assert not any(name.startswith("idle_") for name in m)
    hs = host_spans.read_xplane(trace_reduce.newest_xplane(str(TRACE_DIR)))
    calls = r["notes"]["calls_in_window"]
    assert hs.iterations in (calls, calls + 1)
    names = {n for n, _, _ in hs.events}
    assert names >= {"submit", "verify", "materialize", "drive", "dispatch",
                     "ship", "exec", "install", "reintegrate"}
    assert not names & {"d2h", "hash"}
