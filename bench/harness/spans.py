"""The program's own host spans in a reduced trace (``harness.trace``).

Both DIALS drivers open a span ``dials.<phase>`` around each phase of a
round and a span ``dials.sync.<name>`` around each blocking
device-to-host read (``repro.obs.trace``, ``Tracer.pull``). Every span
is a ``jax.profiler.TraceAnnotation``, so it is an event of the host
plane, on the clock of the device's ``XLA Ops`` events. A program
without these spans yields no sync spans here, and the readers built on
this module then return None; the prefix is written here, not imported
from the program, for that reason.
"""
from __future__ import annotations

from harness.trace import merged

SYNC = "dials.sync."


def sync_spans(trace):
    """The window's ``dials.sync.*`` host events as ``(start, end)``
    ns, clipped to the window."""
    return [(max(s, trace.lo), min(e, trace.hi)) for n, s, e in trace.host
            if n.startswith(SYNC)]


def idle_gaps(device, lo: int, hi: int):
    """The intervals of ``[lo, hi]`` in which no operation of
    ``device`` runs: the complement of the union of its ``XLA Ops``."""
    gaps, prev = [], lo
    for s, e in merged((s, e) for _, s, e in device.ops):
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    return gaps


def overlap(a, b) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_split(trace):
    """``(idle seconds under a dials.sync span, other idle seconds)`` of
    the window, each averaged over the chips; None when the window holds
    no sync span."""
    syncs = merged(sync_spans(trace))
    if not syncs:
        return None
    under = rest = 0
    for dev in trace.devices:
        gaps = idle_gaps(dev, trace.lo, trace.hi)
        u = overlap(gaps, syncs)
        under += u
        rest += sum(e - s for s, e in gaps) - u
    n = len(trace.devices) * 1e9
    return under / n, rest / n
