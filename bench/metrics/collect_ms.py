"""collect_ms (ms/round): device time of the GS collect program per round
on the loop path, the ring's donating collect ``jit_collect_impl``
(``core/gs.py``, ``core/env_pool.py``)."""


def read(run):
    s = run.trace.devices[0].module_seconds("jit_collect_impl")
    return s / run.rounds * 1e3 if s > 0 else None
