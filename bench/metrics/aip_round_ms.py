"""aip_round_ms (ms/round): device time of the fused AIP round
``jit_aip_round`` (held-out CE and AIP training; ``core/influence.py``,
``core/dials.py``) per round on the loop path."""


def read(run):
    s = run.trace.devices[0].module_seconds("jit_aip_round")
    return s / run.rounds * 1e3 if s > 0 else None
