"""The byte counts, on shapes worked out by hand."""
import pytest

from chipbench import workmodel


def test_at_iteration_bytes_fig12():
    field = 208 * 44 * 46 * 4                       # 1,683,968 bytes
    assert field == 1_683_968
    got = workmodel.at_iteration_bytes(208, 44, 46, 200, 16)
    # model in and out, 200 x 16 observations, 200 saved fields out and in
    assert got == 2 * field + 200 * 16 * 4 + 2 * 200 * field
    assert 0.67e9 < got < 0.68e9


def test_at_iteration_bytes_grow_with_the_record():
    a = workmodel.at_iteration_bytes(8, 4, 4, 10, 2)
    b = workmodel.at_iteration_bytes(8, 4, 4, 20, 2)
    assert b - a == 10 * (2 * 8 * 4 * 4 * 4 + 2 * 4)


def test_mamba1_token_flops_falcon_mamba_7b():
    cfg = {"hidden_size": 4096, "intermediate_size": 8192, "state_size": 16,
           "conv_kernel": 4, "time_step_rank": 256, "vocab_size": 65024,
           "num_hidden_layers": 32}
    # per layer, by hand: in_proj 4096 x 16384 = 67,108,864; x_proj
    # 8192 x 288 = 2,359,296; dt_proj 256 x 8192 = 2,097,152; out_proj
    # 8192 x 4096 = 33,554,432: 105,119,744 matmul parameters
    layer = 2 * 105_119_744 + 2 * 4 * 8192 + 6 * 8192 * 16
    assert layer == 211_091_456
    assert workmodel.mamba1_token_flops(cfg, head=False) == 32 * layer
    assert workmodel.mamba1_token_flops(cfg, head=True) \
        == 32 * layer + 2 * 4096 * 65024


def test_serve_mfu_reads_the_traced_calls():
    import importlib.util
    from chipbench import harness, trace_reduce
    from chipbench.harness import Call, Observation, Window
    path = harness.BENCH / "metrics" / "serve_mfu.py"
    spec = importlib.util.spec_from_file_location("serve_mfu", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    win = Window(10.0)
    t0 = win.t0
    obs = Observation(cell={"name": "c"}, config={}, traffic={}, window=win,
                      peaks={"bf16_flops": 100.0},
                      work={"prefill_flops": 300.0, "decode_flops": 20.0})
    obs.calls = [Call("prefill", t0 + 0.1, t0 + 0.5),
                 Call("decode", t0 + 0.6, t0 + 0.7),
                 Call("decode", t0 + 0.8, t0 + 0.9),
                 Call("decode", t0 + 1.9, t0 + 2.1)]   # ends after the trace
    assert mod.read(obs) is None                       # nothing traced
    obs.trace = trace_reduce.Summary(window_s=2.0, busy_s=1.0, n_devices=1,
                                     t0_ns=0)
    obs.trace.t0, obs.trace.t1 = t0, t0 + 2.0
    assert mod.read(obs) == pytest.approx(100 * (300 + 2 * 20) / (2.0 * 100))
    obs.trace.n_devices = 0                            # the CPU is no device
    assert mod.read(obs) is None
