"""MDSS hashing per AT iteration: the program's ``emerald:hash`` phases
(chunk manifests of host copies), summed over threads, read from the
profiler trace. Moves ``at_iter_s``."""
from chipbench.host_spans import span_ms


def read(obs):
    return span_ms(obs, ("hash",))
