"""Device-to-host copies MDSS makes before hashing, per AT iteration: the
program's ``emerald:d2h`` phases, summed over threads, read from the
profiler trace. Moves ``at_iter_s``."""
from chipbench.host_spans import span_ms


def read(obs):
    return span_ms(obs, ("d2h",))
