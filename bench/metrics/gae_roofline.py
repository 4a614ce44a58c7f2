"""gae_roofline (%): the least time the GAE reverse scans of the window
could take at the chip's peaks (``harness.counts.gae_ops``), over the
device time of the GAE kernel's events, summed over chips."""
from harness import counts, peaks


def read(run):
    t = sum(d.kernel_seconds("gae") for d in run.trace.devices)
    if t <= 0:
        return None
    least = sum(peaks.least_seconds(f, b, run.device_kind)
                for f, b in counts.gae_ops(run.job, run.info))
    return 100.0 * least * run.rounds / t
