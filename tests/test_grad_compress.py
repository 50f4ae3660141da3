"""Cross-pod compressed gradient sync: correctness + wire-format proof.

Runs in a subprocess with 8 fake devices (mesh 2x2x2) — tests in the main
process must keep the default single device.
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
    from jax.sharding import PartitionSpec as P, NamedSharding, AxisType

    from repro.configs import get_config
    from repro.configs.base import RunConfig, ShapeProfile, reduced
    from repro.data.pipeline import SyntheticLMData
    from repro.models.model_zoo import Model
    from repro.optim.grad_compress import multipod_train_step, sync_grads

    cfg = reduced(get_config("tinyllama-1.1b"), n_layers=2)
    run = RunConfig(model=cfg, shape=ShapeProfile("t", 16, 8, "train"),
                    remat="none")
    model = Model(run)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = model.opt_init(params)
    batch = SyntheticLMData(cfg, run.shape).batch(0)

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    results = {{}}
    hlos = {{}}
    with jax.set_mesh(mesh):
        for method in ("none", "bf16", "int8"):
            step = jax.jit(multipod_train_step(model, mesh, method))
            p2, o2, m = step(params, opt, batch)
            results[method] = float(m["loss"])
            hlos[method] = step.lower(params, opt, batch).compile().as_text()

    # baseline: plain single-jit train step on the same global batch
    ref_p, ref_o, ref_m = jax.jit(model.train_step)(params, opt, batch)
    ref = float(ref_m["loss"])
    for method, loss in results.items():
        assert abs(loss - ref) < 1e-3, (method, loss, ref)
    assert "all-gather" in hlos["int8"]
    assert any(("s8[" in l and "all-gather" in l)
               for l in hlos["int8"].splitlines()), "no int8 wire traffic"

    from repro.launch.hlo_analysis import collective_bytes
    b_none = collective_bytes(hlos["none"])["total"]
    b_int8 = collective_bytes(hlos["int8"])["total"]
    print("WIRE none=%d int8=%d" % (b_none, b_int8))
    print("GRAD_COMPRESS_OK")
""")


@pytest.mark.slow
def test_multipod_compressed_sync_subprocess():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT.format(src=src)],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert "GRAD_COMPRESS_OK" in r.stdout


def test_sync_grads_math_single_axis():
    """int8 quantize/dequant roundtrip error is bounded by scale/2."""
    import jax.numpy as jnp
    import numpy as np
    from repro.optim.grad_compress import quantize_int8
    g = jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)) * 0.01,
                    jnp.float32)
    q, scale = quantize_int8(g)
    deq = q.astype(jnp.float32) * scale
    assert float(jnp.max(jnp.abs(deq - g))) <= float(scale) * 0.51
    assert q.dtype == jnp.int8
