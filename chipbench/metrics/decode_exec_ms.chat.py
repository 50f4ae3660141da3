"""Execution per decode call of a serving cell: the ring's ``exec`` spans
of the call's run, each ending in ``block_until_ready``. Moves
``itl_p90_ms``."""
from chipbench.metrics_common import span_ms


def read(obs):
    return span_ms(obs, "decode", ("exec",))
