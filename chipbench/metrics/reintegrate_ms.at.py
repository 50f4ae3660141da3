"""Re-integration per AT iteration: the program's ``emerald:reintegrate``
span (fetching the run's results to the caller), read from the profiler
trace. Moves ``at_iter_s``."""
from chipbench.host_spans import span_ms


def read(obs):
    return span_ms(obs, ("reintegrate",))
