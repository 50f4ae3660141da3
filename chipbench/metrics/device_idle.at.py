"""Share of the traced window in which no operation ran on the device,
in the AT cell. Moves ``at_iter_s``."""
from chipbench.metrics_common import idle_pct


def read(obs):
    return idle_pct(obs)
