"""Whole serving step against the chip's bf16 peak: the model FLOPs of the
prefill and decode calls that lie inside the traced window (``obs.work``,
from ``workmodel.<reference>_token_flops``: shapes only), over the traced
window times the peak of the chips it ran on. Moves ``serve_tok_s``."""


def read(obs):
    tr = obs.trace
    if tr is None or tr.n_devices == 0:
        return None
    flops = sum(obs.work[f"{label}_flops"]
                * len(obs.calls_of(label, traced=True))
                for label in ("prefill", "decode"))
    if flops == 0:
        return None
    return 100.0 * flops / ((tr.t1 - tr.t0) * tr.n_devices
                            * obs.peaks["bf16_flops"])
