"""The comparison that decides ``correct``.

The timed path is ``DIALSTrainer.run``. During set-up the harness drives
the very trainer the window will use from the seed through its first
round, and ``LoopCapture`` records, from the calls the window itself
makes (the fused AIP round and the inner IALS + PPO step), what the first
steps produced:

- the AIP round of round 0: held-out CE before and after training, and
  the change of every AIP leaf over its 100 Adam updates;
- the first three inner steps: each step's mean PPO loss, Adam's first
  moment after step 1 (the first gradients as the optimizer holds them)
  and the change of every policy leaf after step 3.

Once the window has closed and the program's state is freed, the plain
reference (``bench/harness/ref``, float32 at ``highest`` precision)
follows the same steps from the same seed, and ``readings`` turns the
two into five numbers, each held to its limit in
``bench/limits/<cell>.json``:

- ``aip_ce``: the larger relative gap of the two mean held-out CEs;
- ``aip_update``, ``policy_update``: by the worst leaf, the gap between
  the program's and the reference's norm of a leaf's change, over the
  larger of the reference's norm of that leaf and of the median leaf;
- ``adam_mu``: the same gap for the norm of Adam's first moment;
- ``ppo_loss``: the largest relative gap of a step's mean PPO loss.

On a mesh of several chips, where a whole round is one donated program,
``RoundCapture`` records the first ``ROUND_STEPS`` rounds instead,
``reference_rounds`` follows them on one device, and ``ials_reward``
(the mean reward of a round's last inner step) takes ``ppo_loss``'s
place.

Leaves whose first gradient in the reference is under a thousandth of
the median leaf's (nought to rounding, such as a bias under a softmax)
move under Adam by round-off alone; the two ``*_update`` numbers leave
them out by that rule.

Sampled actions and influence sources may differ between the program and
the reference where rounding moves a logit across a sampling threshold;
the numbers compare norms and means over all agents, which such a flip
moves far less than a wrong computation does.
"""
from __future__ import annotations

import math

import jax
import numpy as np

INNER_STEPS = 3
ROUND_STEPS = 2
NUMBERS = ("aip_ce", "aip_update", "ppo_loss", "adam_mu", "policy_update")
ROUND_NUMBERS = ("aip_ce", "aip_update", "ials_reward", "adam_mu",
                 "policy_update")
_LEAF_RULE = 1e-3


def leaf_norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree.leaves(tree)])


def change_norms(after, before) -> np.ndarray:
    return np.array([
        float(np.linalg.norm(np.asarray(a, np.float64)
                             - np.asarray(b, np.float64)))
        for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))])


def _host(tree):
    return jax.tree.map(np.asarray, tree)


class LoopCapture:
    """Wraps the loop path's AIP round and inner step on one trainer to
    record what its first steps produce; removes itself once it has the
    first ``INNER_STEPS`` inner steps, so the window runs the trainer's
    own calls."""

    def __init__(self, trainer):
        self.trainer = trainer
        self._aip_round, self._ials_train = trainer.aip_round, \
            trainer.ials_train
        trainer.aip_round, trainer.ials_train = self.aip_round, \
            self.ials_train
        self.got = {"losses": []}
        self._p0 = None

    @property
    def done(self) -> bool:
        return len(self.got["losses"]) >= INNER_STEPS

    def aip_round(self, aips, *args):
        out = self._aip_round(aips, *args)
        if "ce_before" not in self.got:
            self.got["aip_update"] = change_norms(_host(out[0]), _host(aips))
            self.got["ce_before"] = float(np.asarray(out[2]).mean())
            self.got["ce_after"] = float(np.asarray(out[3]).mean())
        return out

    def ials_train(self, state, aips):
        new, metrics = self._ials_train(state, aips)
        k = len(self.got["losses"])
        if k < INNER_STEPS:
            if k == 0:
                self._p0 = _host(state["params"])
                self.got["adam_mu"] = leaf_norms(new["opt"]["mu"])
            self.got["losses"].append(float(metrics["loss"]))
            if k + 1 == INNER_STEPS:
                self.got["policy_update"] = change_norms(
                    _host(new["params"]), self._p0)
                self._p0 = None
                self.trainer.aip_round = self._aip_round
                self.trainer.ials_train = self._ials_train
        return new, metrics


def reference_steps(job, key, dtype, precision: str) -> dict:
    """The same first steps computed by the plain reference from ``key``:
    the quantities ``LoopCapture`` records, plus each network's first
    gradient norms per leaf (``g_aip``, ``g_policy``) for the leaf rule."""
    from .ref.core import Ref
    ref = Ref(job, dtype)
    with jax.default_matmul_precision(precision):
        ials, aips = ref.init(key)
        kc, kt, _ = jax.random.split(jax.random.fold_in(key, 0), 3)
        data = ref.collect(ials["params"], kc)
        new_aips, ce_b, ce_a, g_aip = ref.aip_round(
            aips, data, jax.random.split(kt, ref.n))
        out = {"aip_update": change_norms(_host(new_aips), _host(aips)),
               "ce_before": float(np.asarray(ce_b).mean()),
               "ce_after": float(np.asarray(ce_a).mean()),
               "g_aip": np.asarray(g_aip, np.float64), "losses": []}
        del data
        p0, state = _host(ials["params"]), ials
        for k in range(INNER_STEPS):
            state, loss, _, g = ref.ials_step(state, new_aips)
            out["losses"].append(float(loss))
            if k == 0:
                out["adam_mu"] = leaf_norms(state["opt"]["mu"])
                out["g_policy"] = np.asarray(g, np.float64)
        out["policy_update"] = change_norms(_host(state["params"]), p0)
    return out


class RoundCapture:
    """The sharded path's counterpart of ``LoopCapture``: there a whole
    round is one donated program, so its steps are rounds. Records the
    first ``ROUND_STEPS`` rounds: each round's held-out CEs and mean IALS
    reward of its last inner step, the AIP change over round 0, Adam's
    first moment after round 0 and the policy change over all of them."""

    def __init__(self, trainer, n_shards: int):
        self.runner = trainer._sharded_runner(n_shards)
        self._round = self.runner.round
        self.runner.round = self.round
        self.got = {"ce": [], "rewards": []}
        self._a0 = self._p0 = None

    @property
    def done(self) -> bool:
        return len(self.got["rewards"]) >= ROUND_STEPS

    def round(self, carry, *args):
        k = len(self.got["rewards"])
        if k == 0:                       # the call donates its carry
            self._a0 = _host(carry["aips"])
            self._p0 = _host(carry["ials"]["params"])
        new, rec = self._round(carry, *args)
        if k < ROUND_STEPS:
            self.got["ce"].append((float(rec["aip_ce_before"]),
                                   float(rec["aip_ce_after"])))
            self.got["rewards"].append(float(rec["ials_reward"]))
            if k == 0:
                self.got["aip_update"] = change_norms(_host(new["aips"]),
                                                      self._a0)
                self.got["adam_mu"] = leaf_norms(new["ials"]["opt"]["mu"])
            if k + 1 == ROUND_STEPS:
                self.got["policy_update"] = change_norms(
                    _host(new["ials"]["params"]), self._p0)
                self._a0 = self._p0 = None
                self.runner.round = self._round
        return new, rec


def reference_rounds(job, key, dtype, precision: str) -> dict:
    """The first ``ROUND_STEPS`` whole rounds by the plain reference on
    one device: the quantities ``RoundCapture`` records, plus the first
    gradient norms per leaf for the leaf rule."""
    from .ref.core import Ref
    ref = Ref(job, dtype)
    out = {"ce": [], "rewards": []}
    with jax.default_matmul_precision(precision):
        ials, aips = ref.init(key)
        a0, p0 = _host(aips), _host(ials["params"])
        for r in range(ROUND_STEPS):
            kc, kt, _ = jax.random.split(jax.random.fold_in(key, r), 3)
            data = ref.collect(ials["params"], kc)
            aips, ce_b, ce_a, g_aip = ref.aip_round(
                aips, data, jax.random.split(kt, ref.n))
            del data
            out["ce"].append((float(np.asarray(ce_b).mean()),
                              float(np.asarray(ce_a).mean())))
            for f in range(job["aip_refresh"]):
                ials, _, reward, g = ref.ials_step(ials, aips)
                if r == 0 and f == 0:
                    out["g_policy"] = np.asarray(g, np.float64)
            out["rewards"].append(float(reward))
            if r == 0:
                out["aip_update"] = change_norms(_host(aips), a0)
                out["g_aip"] = np.asarray(g_aip, np.float64)
                out["adam_mu"] = leaf_norms(ials["opt"]["mu"])
        out["policy_update"] = change_norms(_host(ials["params"]), p0)
    return out


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else (0.0 if a == b else math.inf)


def _kept(g: np.ndarray) -> np.ndarray:
    return g >= _LEAF_RULE * np.median(g)


def worst_leaf(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    keep = np.ones(len(ref), bool) if keep is None else keep
    floor = float(np.median(ref[keep]))
    gaps = np.abs(prog - ref)[keep] / np.maximum(ref[keep], floor)
    return float(gaps.max())


def readings(prog: dict, ref: dict) -> dict:
    """The numbers compared; ``ppo_loss`` for the loop path's inner
    steps, ``ials_reward`` for the sharded path's rounds."""
    if "ce" in ref:
        ce = max(_rel(a, b) for pa, pb in zip(prog["ce"], ref["ce"])
                 for a, b in zip(pa, pb))
    else:
        ce = max(_rel(prog["ce_before"], ref["ce_before"]),
                 _rel(prog["ce_after"], ref["ce_after"]))
    out = {"aip_ce": ce,
           "aip_update": worst_leaf(prog["aip_update"], ref["aip_update"],
                                    _kept(ref["g_aip"]))}
    if "losses" in ref:
        out["ppo_loss"] = max(_rel(a, b) for a, b in zip(prog["losses"],
                                                         ref["losses"]))
    else:
        out["ials_reward"] = max(_rel(a, b) for a, b in zip(prog["rewards"],
                                                            ref["rewards"]))
    out["adam_mu"] = worst_leaf(prog["adam_mu"], ref["adam_mu"])
    out["policy_update"] = worst_leaf(prog["policy_update"],
                                      ref["policy_update"],
                                      _kept(ref["g_policy"]))
    return out


def verdict(nums: dict, limits: dict) -> bool:
    """Every number the cell's limits name is finite and within its
    limit."""
    return all(k in nums and math.isfinite(nums[k]) and nums[k] <= v
               for k, v in limits.items())


def report(nums: dict, limits: dict) -> dict:
    """``{name: {"value": ..., "limit": ...}}`` for the cell's numbers."""
    return {k: {"value": nums[k], "limit": v} for k, v in limits.items()}
