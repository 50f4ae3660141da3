"""Checkpointer tests: roundtrip, async, crash-atomicity, elastic re-shard
(subprocess with 8 fake devices), trainer resume equality."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer

# compile-heavy: excluded from the smoke fast lane (-m "not slow"),
# still part of tier-1 (plain pytest runs everything)
pytestmark = pytest.mark.slow


def tree():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                       "b": jnp.ones((4,))},
            "step": jnp.int32(7)}


def test_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    t = tree()
    ck.save("m", 10, t, topology={"mesh": [1]})
    restored, meta = ck.restore("m", jax.eval_shape(lambda: t))
    assert meta["step"] == 10 and meta["topology"] == {"mesh": [1]}
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_tracking(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save("m", 5, tree(), topology={})
    ck.save("m", 9, tree(), topology={})
    assert ck.latest_step("m") == 9


def test_async_save(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=True)
    ck.save("m", 1, tree(), topology={})
    ck.wait()
    restored, _ = ck.restore("m", jax.eval_shape(lambda: tree()))
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.arange(12.0).reshape(3, 4))


def test_partial_file_never_visible(tmp_path):
    """Atomic rename: no *.npz file exists until fully written."""
    ck = Checkpointer(str(tmp_path))
    ck.save("m", 1, tree(), topology={})
    files = os.listdir(tmp_path)
    assert not any(f.endswith(".tmp.npz") for f in files)


def test_shape_mismatch_rejected(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save("m", 1, tree(), topology={})
    bad = {"params": {"w": jnp.zeros((2, 2)), "b": jnp.zeros((4,))},
           "step": jnp.int32(0)}
    with pytest.raises(ValueError):
        ck.restore("m", jax.eval_shape(lambda: bad))


ELASTIC_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.checkpoint import Checkpointer

    phase = sys.argv[1]
    ckdir = sys.argv[2]
    tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
    ck = Checkpointer(ckdir)
    if phase == "save":
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        sh = NamedSharding(mesh, P("data", "model"))
        t = {{"w": jax.device_put(tree["w"], sh)}}
        ck.save("elastic", 1, t, topology={{"mesh": [4, 2]}})
    else:
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        sh = {{"w": NamedSharding(mesh, P("model", "data"))}}
        restored, meta = ck.restore("elastic", jax.eval_shape(lambda: tree),
                                    shardings=sh)
        assert meta["topology"] == {{"mesh": [4, 2]}}
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64.0).reshape(8, 8))
        assert restored["w"].sharding.spec == P("model", "data")
        print("ELASTIC_OK")
""")


def test_elastic_reshard_across_meshes(tmp_path):
    """Save sharded on a (4,2) mesh, restore onto a (2,4) mesh."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = ELASTIC_SCRIPT.format(src=os.path.abspath(src))
    env = dict(os.environ)
    for phase in ("save", "restore"):
        r = subprocess.run([sys.executable, "-c", script, phase,
                            str(tmp_path)], capture_output=True, text=True,
                           env=env, timeout=240)
        assert r.returncode == 0, r.stderr[-2000:]
    assert "ELASTIC_OK" in r.stdout


def test_trainer_resume_bit_identical(tmp_path):
    """Train 6 steps; vs train 3, checkpoint, restart, 3 more."""
    from repro.configs import get_config
    from repro.configs.base import RunConfig, ShapeProfile, reduced
    from repro.launch.train import Trainer

    cfg = reduced(get_config("tinyllama-1.1b"), n_layers=2)
    shape = ShapeProfile("t", 32, 2, "train")
    run = RunConfig(model=cfg, shape=shape, remat="none")

    t1 = Trainer(run, ckpt_dir=str(tmp_path / "a"), ckpt_every=3,
                 async_ckpt=False)
    h1 = t1.fit(6, log_every=0)

    t2 = Trainer(run, ckpt_dir=str(tmp_path / "b"), ckpt_every=3,
                 async_ckpt=False)
    t2.fit(3, log_every=0)
    t3 = Trainer(run, ckpt_dir=str(tmp_path / "b"), ckpt_every=3,
                 async_ckpt=False)
    h3 = t3.fit(3, resume=True, log_every=0)

    np.testing.assert_allclose(h1[-1]["loss"], h3[-1]["loss"], rtol=1e-5)
    assert h3[-1]["step"] == h1[-1]["step"]
