"""Pipeline parallelism over the pod axis: loss equivalence vs plain step.

Subprocess with 8 fake devices (mesh 2x2x2: 2 pipeline stages).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType

    from repro.configs import get_config
    from repro.configs.base import RunConfig, ShapeProfile, reduced
    from repro.data.pipeline import SyntheticLMData
    from repro.models.model_zoo import Model
    from repro.parallel.pipeline import pipeline_train_step

    cfg = reduced(get_config("tinyllama-1.1b"), n_layers=4)
    run = RunConfig(model=cfg, shape=ShapeProfile("t", 16, 8, "train"),
                    remat="none")
    model = Model(run)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = model.opt_init(params)
    batch = SyntheticLMData(cfg, run.shape).batch(0)

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    with jax.set_mesh(mesh):
        step = jax.jit(pipeline_train_step(model, mesh, n_micro=4))
        p2, o2, m = step(params, opt, batch)
        hlo = step.lower(params, opt, batch).compile().as_text()
    ref_p, ref_o, ref_m = jax.jit(model.train_step)(params, opt, batch)
    print("pp xent", float(m["xent"]), "ref", float(ref_m["xent"]))
    assert abs(float(m["xent"]) - float(ref_m["xent"])) < 2e-3
    # params updated equivalently (same grads modulo accumulation order)
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(ref_p)):
        pass
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(ref_p)))
    print("max param delta vs ref step:", err)
    assert err < 5e-2
    assert "collective-permute" in hlo, "pipeline rotation missing from HLO"
    print("PIPELINE_OK")
""")


@pytest.mark.slow
def test_pipeline_matches_plain_step():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT.format(src=src)],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert "PIPELINE_OK" in r.stdout
