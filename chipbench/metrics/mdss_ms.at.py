"""MDSS staging per AT iteration: the ship and install spans of its four
steps (input staging, output hashing and publication). Moves
``at_iter_s``."""
from chipbench.metrics_common import span_ms


def read(obs):
    return span_ms(obs, "at_iter", ("ship", "install"))
