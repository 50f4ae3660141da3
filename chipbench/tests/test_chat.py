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
