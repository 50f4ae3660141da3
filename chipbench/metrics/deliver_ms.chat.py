"""Handing a step's tokens to its requests, per decode call of a serving
cell: the program's ``emerald:deliver`` annotations inside the traced
window (``Server.step_batch``'s per-slot loops after prefill and after each
decode), over the decode calls wholly inside it. None where the program
opens no such annotation. Moves ``itl_p90_ms``."""
from chipbench.host_spans import per_call_ms


def read(obs):
    return per_call_ms(obs, ("deliver",), "decode")
