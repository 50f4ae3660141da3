"""The harness's contract with its caller: the spec's shape, finding each
cell's files by name, no result without a chip, and the result line."""
import io
import json
import re
import subprocess
import sys
from contextlib import redirect_stderr, redirect_stdout

import pytest

from chipbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_spec_shape():
    sp = harness.spec()
    assert set(sp) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= sp["run_seconds"] <= 51
    configs = {c["name"] for c in sp["configs"]}
    e2e = {m["name"]: m for m in sp["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in sp["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in sp["workloads"]}
    for w in sp["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        reported = [m for m in sp["end_to_end"] if m["name"] != "setup_s"
                    and w["name"] in m.get("workloads", [w["name"]])]
        assert reported
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in sp["per_layer"])
    for m in sp["end_to_end"] + sp["per_layer"] + sp["configs"] \
            + sp["workloads"]:
        assert NAME.match(m["name"]), m["name"]
    for m in sp["end_to_end"] + sp["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in sp["per_layer"]:
        assert m["moves"] in e2e and m["name"] not in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # every listed cell reports the metric it moves
        for w in m.get("workloads", cells):
            assert w in e2e[m["moves"]].get("workloads", cells)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert len(json.dumps(sp)) < 64 * 1024


def test_every_name_has_its_file():
    sp = harness.spec()
    for w in sp["workloads"]:
        cell, cfg, traffic = harness.cell_parts(sp, w["name"])
        assert (harness.BENCH / "drivers" / f"{traffic['kind']}.py").exists()
        assert "check_limits" in cfg
    for m in sp["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()
    for c in sp["configs"]:
        assert c["file"].startswith("chipbench/")
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert key in cfg.get("published", {})


def test_no_chip_no_result():
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "at-fig12-inv", "--seed", str(2 ** 31 + 7), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode == 3
    assert out.stdout == ""
    assert "no accelerator" in out.stderr


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def test_result_line_last_with_checks_last():
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        harness.emit(correct=True, attempted=3, failed=0,
                     metrics={"x": {"value": 1.5, "unit": "s"}},
                     device={"platform": "tpu"},
                     checks={"gap": (0.25, 0.5)}, breakdown=None,
                     notes={"setup_s": 2.0})
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["checks"] == {"gap": {"value": 0.25, "limit": 0.5}}
    assert err.getvalue().strip().splitlines()[-1] == \
        "check gap: 0.25 (limit 0.5)"


def test_readers_find_nothing_without_a_trace():
    sp = harness.spec()
    for w in sp["workloads"]:
        cell, cfg, traffic = harness.cell_parts(sp, w["name"])
        obs = harness.Observation(cell=cell, config=cfg, traffic=traffic,
                                  window=harness.Window(1.0))
        assert harness.read_metrics(obs, sp["per_layer"]) == {}


def test_large_seeds_make_the_same_inputs():
    from chipbench.drivers.at_inversion import Driver
    sp = harness.spec()
    cell, cfg, traffic = harness.cell_parts(sp, "at-fig12-inv")

    def drawn(seed):
        return Driver(cell, cfg, traffic, seed)._anomalies()
    assert drawn(2 ** 33 + 1) == drawn(2 ** 33 + 1)
    assert drawn(2 ** 33 + 1) != drawn(2 ** 33 + 2)


def test_every_inversion_starts_from_a_model_of_its_own():
    from chipbench.drivers.at_inversion import Driver
    sp = harness.spec()
    cell, cfg, traffic = harness.cell_parts(sp, "at-fig12-inv")
    drv = Driver(cell, cfg, traffic, 5)
    starts = {float(drv.start(k)[0, 0, 0]) for k in range(-1, 400)}
    assert len(starts) == 401
