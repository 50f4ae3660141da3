"""Runtime self time of one AT iteration: its wall time less the time
covered by the ship, exec and install spans of its steps (two steps run
at once) (what verify, partition, placement,
dispatch and re-integration take around the work). Moves ``at_iter_s``."""
from chipbench.metrics_common import runtime_self_ms


def read(obs):
    return runtime_self_ms(obs, "at_iter")
