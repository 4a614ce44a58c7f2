"""sync_idle_ms (ms/round): device idle time of the window (no ``XLA
Ops`` event runs) that lies under a ``dials.sync.*`` host span, the chip
waiting while the host completes a read; averaged over the chips, per
round (``harness.spans``)."""
from harness import spans


def read(run):
    split = spans.idle_split(run.trace)
    return None if split is None else split[0] / run.rounds * 1e3
