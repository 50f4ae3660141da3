"""A whole run (set-up, window, check) past the harness's look for a chip,
with the timed path broken underneath: ``correct`` has to come out false.

The faults the cell can have on one chip: a step that returns its state
unchanged, and an answer altered where it is produced. (It does not
train, so it leaves no half of a batch out, and it spans no chips.)
"""
from chipbench.tests.tiny import run


def test_sound_runs_are_correct():
    assert run("at-fig12-inv")["correct"]


def test_at_update_that_returns_the_model_unchanged(monkeypatch):
    from repro.apps import adjoint_tomography as at

    def stuck(cfg):
        return lambda model, grad: {"model": model + 0 * grad}

    monkeypatch.setattr(at, "step_update", stuck)
    r = run("at-fig12-inv")
    assert not r["correct"]
    assert r["checks"]["model_rel_err"][0] > r["checks"]["model_rel_err"][1]


def test_at_misfit_altered_where_produced(monkeypatch):
    from repro.apps import adjoint_tomography as at
    real = at.step_misfit

    def altered(cfg):
        fn = real(cfg)
        return lambda syn, obs: {"chi": fn(syn, obs)["chi"] * 2.0}

    monkeypatch.setattr(at, "step_misfit", altered)
    r = run("at-fig12-inv")
    assert not r["correct"]
    assert r["checks"]["chi_rel_err"][0] > r["checks"]["chi_rel_err"][1]

