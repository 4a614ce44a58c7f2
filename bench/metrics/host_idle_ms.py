"""host_idle_ms (ms/round): the rest of the window's device idle time,
under no ``dials.sync.*`` span: the host dispatching, running Python or
allocating; averaged over the chips, per round (``harness.spans``).
With ``sync_idle_ms`` it sums to ``device_idle_share`` of the window per
round."""
from harness import spans


def read(run):
    split = spans.idle_split(run.trace)
    return None if split is None else split[1] / run.rounds * 1e3
