"""gru_roofline (%): the least time the GRU recurrences of the window,
forward and backward, could take at the chip's peaks
(``harness.counts.gru_ops``), over the device time of the GRU kernels'
events, summed over chips."""
from harness import counts, peaks


def read(run):
    t = sum(d.kernel_seconds("gru") for d in run.trace.devices)
    if t <= 0:
        return None
    least = sum(peaks.least_seconds(f, b, run.device_kind)
                for f, b in counts.gru_ops(run.job, run.info))
    return 100.0 * least * run.rounds / t
