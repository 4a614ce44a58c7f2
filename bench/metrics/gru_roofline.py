"""gru_roofline (%): the least time the GRU recurrences of the window,
forward and backward, could take at the chip's peaks
(``harness.counts.kernel_ops("gru", ...)``, counted by
``harness/ref/backbones/gru.py``), over the device time of the GRU
kernels' events (``gru_fwd``, ``gru_bwd``), summed over chips."""
from harness import counts, peaks


def read(run):
    t = sum(d.kernel_seconds("gru") for d in run.trace.devices)
    if t <= 0:
        return None
    least = sum(peaks.least_seconds(f, b, run.device_kind)
                for f, b in counts.kernel_ops("gru", run.job, run.info))
    return 100.0 * least * run.rounds / t
