"""The control: the plain reference put in the program's place, computed
in the precision below the configuration's, has to come out as not
correct through the run's own comparison. Here at sizes a test run holds;
on the chip at the cells' own sizes through ``calibrate.py`` (readings in
PERF.md: the bfloat16 control fails both AT limits there)."""
import pytest

from chipbench.tests.tiny import run


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_at_bfloat16_control_fails_the_limits(seed):
    sound = run("at-fig12-inv", seed=seed, seconds=1.0)
    control = run("at-fig12-inv", seed=seed, seconds=1.0, control=True)
    assert sound["correct"]
    assert not control["correct"]
    chi, chi_limit = control["checks"]["chi_rel_err"]
    assert chi > chi_limit
