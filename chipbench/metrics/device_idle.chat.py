"""Share of the traced window in which no operation ran on the device,
in a serving cell. Moves ``itl_p90_ms``."""
from chipbench.metrics_common import idle_pct


def read(obs):
    return idle_pct(obs)
