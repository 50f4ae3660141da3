"""Submission per AT iteration: the program's ``emerald:submit`` span
(validation, partition, the run's initial puts), read from the profiler
trace. Moves ``at_iter_s``."""
from chipbench.host_spans import span_ms


def read(obs):
    return span_ms(obs, ("submit",))
