"""A whole run of a serving cell (set-up, window, check) through
``run_cell`` at tiny widths on the CPU: what it reports, and what its check
judges of a faithful program and of the control."""
import pytest

from chipbench.tests.tiny import CHAT, run


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_faithful_program_is_correct_and_int8_control_is_not(faithful, seed):
    sound = run(CHAT, seed=seed)
    assert sound["correct"], sound["checks"]
    for name in ("serve_tok_s", "itl_p90_ms", "setup_s"):
        assert sound["metrics"][name]["value"] > 0, name
    notes = sound["notes"]
    assert notes["compiles_in_window"] == 0
    assert notes["requests_compared"] == 3
    assert notes["itl_samples"] == 5 * notes["requests_completed"]
    assert sound["attempted"] >= notes["requests_completed"] > 0
    control = run(CHAT, seed=seed, control=True)
    assert not control["correct"]
    rel, limit = control["checks"]["logit_mae_rel"]
    assert rel > limit


def test_program_configured_otherwise_than_the_file_is_refused():
    from chipbench.drivers.chat import Driver
    from chipbench.tests.tiny import chat_cell
    sp, c, cfg, traffic = chat_cell()
    drv = Driver(c, {**cfg, "state_size": cfg["state_size"] + 1}, traffic, 1)
    with pytest.raises(ValueError, match="state_size"):
        drv.setup()


def test_tokens_are_counted_where_they_reach_the_client():
    from chipbench.drivers.chat import Driver
    from chipbench.harness import Window
    from chipbench.tests.tiny import chat_cell
    from repro.launch.serve import Request
    sp, c, cfg, traffic = chat_cell()
    drv = Driver(c, cfg, traffic, 1)
    win = Window(1.0)
    t0 = win.t0
    reqs = []
    for times in ([0.1, 0.2, 0.4], [0.5, 0.7, 1.3]):     # the second ends late
        r = Request(len(reqs), None, tokens=[0] * len(times))
        r.tokens = type("T", (list,), {})(r.tokens)
        r.tokens.times = [t0 + t for t in times]
        reqs.append(r)
    drv.rounds = [reqs]
    got = drv.end_to_end(win)
    assert got["serve_tok_s"] == 5 / 1.0
    # gaps of the completed request only: 0.1 and 0.2 s
    assert got["itl_p90_ms"] == pytest.approx(190.0)
    assert drv.check_notes["requests_completed"] == 1
    assert drv.check_notes["completed_tok_s"] == 3.0


def test_traced_run_reports_the_serving_span_metrics(faithful):
    """A traced run of the tiny serving cell on the CPU: the readers of the
    runtime's ring spans find their calls; the CPU is no device, so the
    readers of device time find nothing, and no ``deliver`` annotation is
    opened by the program yet."""
    r = run(CHAT, seed=4, trace=True)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    for name in ("prefill_exec_ms.chat", "decode_exec_ms.chat",
                 "decode_submit_ms.chat"):
        assert m[name]["value"] > 0, name
    assert m["prefill_exec_ms.chat"]["value"] \
        > m["decode_exec_ms.chat"]["value"]
    for name in ("serve_mfu", "device_idle.chat", "scan_roofline.chat"):
        assert name not in m, name


def test_a_field_the_program_lacks_is_refused_at_setup():
    from chipbench.drivers.chat import Driver
    from chipbench.tests.tiny import chat_cell
    sp, c, cfg, traffic = chat_cell()
    cfg = {**cfg, "registry": {**cfg["registry"],
                               "mixer_rms_eps": "no_such_field"}}
    with pytest.raises(ValueError, match="no_such_field"):
        Driver(c, cfg, traffic, 1).setup()


def test_fm7b_file_is_the_tiny_layout_at_the_published_widths():
    """``configs/fm7b.json`` holds every key of the CPU tests' file, maps
    the same weights and registry keys, and keeps every width as
    falcon-mamba-7b publishes it; only the depth is cut."""
    from chipbench import harness
    tiny = harness.load_json(harness.BENCH / "tests" / "data"
                             / "tiny-mamba.json")
    fm7b = harness.load_json(harness.BENCH / "configs" / "fm7b.json")
    assert set(tiny) <= set(fm7b)
    assert fm7b["params"] == tiny["params"]
    assert tiny["registry"].items() <= fm7b["registry"].items()
    assert fm7b["arch"] == tiny["arch"] == "falcon-mamba-7b"
    published = {"hidden_size": 4096, "intermediate_size": 8192,
                 "state_size": 16, "conv_kernel": 4, "time_step_rank": 256,
                 "vocab_size": 65024, "layer_norm_epsilon": 1e-5,
                 "mixer_rms_eps": 1e-6, "residual_in_fp32": True,
                 "torch_dtype": "bfloat16", "num_hidden_layers": 32}
    assert {k: fm7b[k] for k in published} == published
    assert fm7b["reduced"] == ["num_hidden_layers"]
    assert fm7b["published"] == {"num_hidden_layers": 64}
    assert fm7b["overrides"] == {"n_layers": 32}
