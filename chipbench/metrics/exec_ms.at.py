"""Execution per AT iteration: the exec spans of its four steps, each
ending in ``block_until_ready``. Moves ``at_iter_s``."""
from chipbench.metrics_common import span_ms


def read(obs):
    return span_ms(obs, "at_iter", ("exec",))
