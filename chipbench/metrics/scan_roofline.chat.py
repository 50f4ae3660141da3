"""The Pallas selective-scan kernel's share of its HBM roofline in a
serving cell: the compulsory bytes of the scans of the prefill calls that
lie wholly inside the traced window (``obs.work["prefill_scan_bytes"]``,
from ``workmodel.mamba1_scan_bytes``: shapes only) at the chip's peak
bandwidth, over the device time of the kernel's own operations in the
trace. Time of a call that the window cuts counts and its bytes do not,
so the share errs low. Moves ``serve_tok_s``.

The program's ``pallas_call`` passes no ``name``, so the trace names the
kernel by its target alone (``KERNEL``), as it names every Pallas kernel.
On a Mamba-1 model's serving path the scan is the only one; another
kernel's time would lower the share, never raise it."""

KERNEL = ('custom_call_target="tpu_custom_call"',)


def read(obs):
    tr = obs.trace
    if tr is None or tr.n_devices == 0:
        return None
    calls = len(obs.calls_of("prefill", traced=True))
    _, seconds = tr.matching(KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    least_s = calls * obs.work["prefill_scan_bytes"] \
        / obs.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
