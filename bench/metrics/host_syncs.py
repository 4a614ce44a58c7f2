"""host_syncs (syncs/round): the drivers' blocking device-to-host reads
per round, counted as the window's ``dials.sync.*`` host spans
(``repro.obs.trace.Tracer.pull``, ``harness.spans``)."""
from harness import spans


def read(run):
    n = len(spans.sync_spans(run.trace))
    return n / run.rounds if n else None
