"""Faults planted in the program under the timed path, to show that the
comparison deciding ``correct`` fails them. Each is a context manager
that patches the program's modules before a trainer is built and
restores them after:

- ``state_unchanged``: the inner IALS + PPO step returns its state
  unchanged;
- ``half_batch``: PPO's loss is the mean over half of each minibatch;
- ``answer_altered``: the GS collect writes the opposite influence bits
  into the dataset it produces;
- ``exchange_dropped``: on a mesh of several chips, the GS collect's
  influence bits that cross chips arrive as zeros: what the dataset
  holds if the exchange between chips is left out. Which bits cross
  chips is the environment's to say: its plain reference
  (``ref/<env>.py``) gives them as ``cross_chip_mask(env_cfg,
  n_shards)``, and an environment without it cannot have this fault
  planted.

Used by ``bench/calibrate.py --fault`` on the chip and by the
benchmark's tests on the CPU; the benchmark's own runs never plant one.
"""
from __future__ import annotations

import contextlib
import functools

import jax

from . import catalog


@contextlib.contextmanager
def _patched(module, name, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def state_unchanged():
    from repro.core import ials

    def make(orig):
        def broken(*a, **kw):
            train = orig(*a, **kw)
            return lambda astate, aip: (astate, train(astate, aip)[1])
        return broken
    return _patched(ials, "make_agent_trainer", make)


def half_batch():
    from repro.marl import ppo

    def make(orig):
        @functools.wraps(orig)
        def half(params, batch, *a):
            b = batch["obs"].shape[0]
            return orig(params, jax.tree.map(lambda x: x[:max(1, b // 2)],
                                             batch), *a)
        return half
    return _patched(ppo, "ppo_loss", make)


def answer_altered():
    from repro.core import gs

    def make(orig):
        def broken(*a, **kw):
            impl, zero_bufs = orig(*a, **kw)

            def flipped(bufs, params, key):
                out = impl(bufs, params, key)
                return {**out, "u": 1.0 - out["u"]}
            return flipped, zero_bufs
        return broken
    return _patched(gs, "_make_collect_impl", make)


def exchange_dropped():
    from repro.core import dials_sharded

    def make(orig):
        def broken(self, *a, **kw):
            orig(self, *a, **kw)
            collect, mask = self.collect, _cross_chip_mask(self)

            def no_exchange(params, key):
                data = collect(params, key)
                return {**data, "u": data["u"] * mask}
            self.collect = no_exchange
        return broken
    return _patched(dials_sharded.ShardedDIALSRunner, "__init__", make)


def _cross_chip_mask(runner):
    env = catalog.module(f"{__package__}.ref", runner.info.name)
    if not hasattr(env, "cross_chip_mask"):
        raise NotImplementedError(
            f"{env.__name__} has no cross_chip_mask(env_cfg, n_shards): "
            f"the exchange between chips cannot be dropped for "
            f"{runner.info.name!r}")
    return env.cross_chip_mask(runner.env_cfg, runner.n_shards)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered,
          "exchange_dropped": exchange_dropped}
