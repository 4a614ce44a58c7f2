"""gs_eval_ms (ms/round): device time of the GS evaluation ``jit_eval_fn``
(``marl/runner.py``) per round on the loop path."""


def read(run):
    s = run.trace.devices[0].module_seconds("jit_eval_fn")
    return s / run.rounds * 1e3 if s > 0 else None
