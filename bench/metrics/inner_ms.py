"""inner_ms (ms/round): device time of the F inner IALS + PPO steps
``jit_train_fn`` (``core/ials.py``, ``marl/ppo.py``) per round on the
loop path."""


def read(run):
    s = run.trace.devices[0].module_seconds("jit_train_fn")
    return s / run.rounds * 1e3 if s > 0 else None
