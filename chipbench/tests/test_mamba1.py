"""The Mamba-1 reference: its scan against the decode recurrence, its
weights against the program's parameters, and the faults the check has to
see, on the CPU at small sizes."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.reference import mamba1

CFG = harness.load_json(harness.BENCH / "tests" / "data" / "tiny-mamba.json")
DIMS = mamba1.Dims.of(CFG)


def test_scan_matches_the_decode_recurrence_in_float64():
    rng = np.random.default_rng(0)
    n, t, di, ns = 2, 37, 6, 4
    with jax.enable_x64(True):
        x, delta = rng.normal(size=(n, t, di)), rng.uniform(0.001, 0.5,
                                                            (n, t, di))
        B, C = rng.normal(size=(n, t, ns)), rng.normal(size=(n, t, ns))
        A = -np.exp(rng.normal(size=(di, ns)))
        D = rng.normal(size=di)
        got = np.asarray(mamba1.selective_scan(
            *(jnp.asarray(v) for v in (x, delta, A, B, C, D))))
        assert got.dtype == np.float64
    s = np.zeros((n, di, ns))
    for k in range(t):                       # one token at a time
        s = np.exp(delta[:, k, :, None] * A) * s \
            + (delta[:, k] * x[:, k])[..., None] * B[:, k, None, :]
        want = np.einsum("bdn,bn->bd", s, C[:, k]) + D * x[:, k]
        np.testing.assert_allclose(got[:, k], want, rtol=1e-12, atol=1e-12)


def test_one_call_makes_the_weights_each_layer_makes_again():
    seed = 2 ** 33 + 9
    made = mamba1.weights(DIMS, seed)
    key = mamba1.seed_key(seed)
    for layer in range(DIMS.num_hidden_layers):
        again = mamba1.layer_weights(key, DIMS, layer)
        for name, v in again.items():
            assert v.dtype == made["layers"][name].dtype
            assert jnp.array_equal(v, made["layers"][name][layer]), name
    for name, v in mamba1.head_weights(key, DIMS).items():
        assert jnp.array_equal(v, made[name]), name


def test_weights_nest_as_the_program_names_its_parameters():
    from chipbench.drivers.chat import Driver
    from repro.configs import get_config
    from repro.configs.base import RunConfig, SHAPES
    import dataclasses
    from repro.models.model_zoo import Model
    mc = dataclasses.replace(get_config(CFG["arch"]), **CFG["overrides"])
    abstract = Model(RunConfig(model=mc, shape=SHAPES["train_4k"])
                     ).abstract_params()
    drv = Driver({}, CFG, json.loads(
        (harness.BENCH / "traffic" / "chat-azure-conv.json").read_text()), 4)
    tree = drv._params(abstract)
    assert jax.tree.structure(tree) == jax.tree.structure(abstract)
    with pytest.raises(ValueError):
        drv._params({**abstract, "final_norm": {"scale": abstract[
            "embed"]["lm_head"]}})


def _rel_gaps(seed, **faults):
    """Mean absolute logit gap to the float32 reference, over the gap of
    the reference at the configuration's precision, as the check takes it."""
    toks = np.random.default_rng(seed).integers(0, DIMS.vocab_size, (3, 30))
    pos = np.arange(23, 30)
    ref = mamba1.logits(DIMS, seed, toks, pos)

    def mae(**kw):
        got = mamba1.logits(DIMS, seed, toks, pos, **kw)
        return float(jnp.mean(jnp.abs(got - ref)))
    base = mae(precision="bfloat16")
    return {name: mae(**kw) / base for name, kw in faults.items()}


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_the_known_fault_and_the_control_pass_the_limit(seed):
    """Leaving out the weightless norms on dt, B and C moves the logits far
    past the limit, and so does the int8 control."""
    got = _rel_gaps(seed, control={"precision": "int8"},
                    no_norm={"precision": "bfloat16", "dt_bc_norm": False})
    limit = CFG["check_limits"]["logit_mae_rel"]
    assert got["control"] > limit
    assert got["no_norm"] > 10 * limit
