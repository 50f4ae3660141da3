"""Submission per decode call of a serving cell: the ring's ``submit``
span of the call's run (validation, partition, the run's initial puts),
as ``submit_ms.at`` reads it for an AT iteration. Moves ``itl_p90_ms``."""
from chipbench.metrics_common import span_ms


def read(obs):
    return span_ms(obs, "decode", ("submit",))
