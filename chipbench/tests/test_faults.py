"""A whole run (set-up, window, check) past the harness's look for a chip,
with the timed path broken underneath: ``correct`` has to come out false.

The faults a cell can have on one chip: a step that returns its state
unchanged, and an answer (a token) altered where it is produced; for the
AT cell and for a serving cell. (Neither trains, so neither leaves half of
a batch out, and neither spans chips.)
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


# --------------------------------------------------------------- serving
# Each serving case runs the program with falcon-mamba's numerics put in
# (the ``faithful`` fixture), so that only the planted fault stands
# between it and the reference; test_chat runs it sound.


def chat(**kw):
    from chipbench.tests.tiny import CHAT
    return run(CHAT, **kw)


def test_decode_that_returns_its_state_unchanged(faithful, monkeypatch):
    from repro.models import transformer as tfm
    real = tfm.forward_decode

    def stuck(cfg, run_cfg, params, tokens, cache, rules):
        logits, _ = real(cfg, run_cfg, params, tokens, cache, rules)
        return logits, cache

    monkeypatch.setattr(tfm, "forward_decode", stuck)
    r = chat()
    assert not r["correct"]
    assert r["checks"]["logit_mae_rel"][0] > r["checks"]["logit_mae_rel"][1]


def test_token_altered_where_produced(faithful, monkeypatch):
    import jax.numpy as jnp
    from repro.launch import serve

    class Altered:
        """``jnp`` as the server sees it, with every greedy token moved
        to the next id."""

        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def argmax(x, axis):
            return (jnp.argmax(x, axis) + 1) % x.shape[axis]

    monkeypatch.setattr(serve, "jnp", Altered())
    r = chat()
    assert not r["correct"]
    assert r["checks"]["tokens_unchosen"][0] > 0


def test_requests_cut_short(faithful, monkeypatch):
    from repro.launch import serve
    real = serve.Server.step_batch

    def short(self):
        reqs = real(self)
        for r in reqs:
            del r.tokens[-1]
        return reqs

    monkeypatch.setattr(serve.Server, "step_batch", short)
    r = chat()
    assert not r["correct"]
    assert r["checks"]["tokens_missing"][0] > 0
