"""Bytes MDSS hashes per AT iteration, in units of 1e6: the ``bytes`` the
program's ``emerald:hash`` phases carry, read from the profiler trace.
Moves ``at_iter_s``."""
from chipbench.host_spans import hashed_mb


def read(obs):
    return hashed_mb(obs)
