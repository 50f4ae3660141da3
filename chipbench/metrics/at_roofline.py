"""Whole AT iteration against the chip's memory bandwidth: the compulsory
HBM bytes of one iteration (``workmodel.at_iteration_bytes``: model in and
out, observations, the saved forward wavefield written and read once) at
peak bandwidth, over the time per iteration in the traced window. Moves
``at_iter_s``."""


def read(obs):
    tr = obs.trace
    if tr is None:
        return None
    n = len(obs.calls_of("at_iter", traced=True))
    if n == 0:
        return None
    least_s = obs.work["at_iter_bytes"] / obs.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s * n / (tr.t1 - tr.t0)
