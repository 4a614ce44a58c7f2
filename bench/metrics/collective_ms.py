"""collective_ms (ms/round): time chip 0 spends in collective operations
(all-gather, all-reduce, collective-permute, reduce-scatter, all-to-all;
the union of their intervals) per round, on a mesh of several chips."""


def read(run):
    if len(run.trace.devices) < 2:
        return None
    s = run.trace.devices[0].collective_seconds()
    return s / run.rounds * 1e3 if s > 0 else None
