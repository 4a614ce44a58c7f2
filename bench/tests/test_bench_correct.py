"""The comparison that decides ``correct``, at a size the CPU holds.

The harness's own set-up (``run.measure``) drives the program's trainer
through its first steps; the plain reference follows them. A sound run
reads far inside every cell's limits. The precision control (the
reference in bfloat16, put in the program's place) and each fault
planted in the program under the timed path read outside them, so
``correct`` comes out false. The limits are the chip cells' own.
"""
import jax.numpy as jnp
import pytest

import run
import tiny
from harness import catalog, correct, faults

CELLS = ("traffic10.f50", "warehouse10.f50")
SEED = 2 ** 31 + 11


def _limits():
    return [catalog.workload(c)["limits"] for c in CELLS]


def _readings(job):
    key = run.seed_key(SEED)
    _, got, reference = run.measure(job, key, 0.0, warmup=1)
    assert reference is correct.reference_steps
    return correct.readings(got, reference(job, key, jnp.float32, "highest"))


@pytest.mark.parametrize("make", [tiny.job, tiny.gru_job],
                         ids=["fnn", "gru"])
def test_sound_run_is_correct(make):
    nums = _readings(make())
    assert max(nums.values()) < 1e-5, nums
    for limits in _limits():
        assert correct.verdict(nums, limits)


@pytest.mark.parametrize("make", [tiny.job, tiny.gru_job],
                         ids=["fnn", "gru"])
def test_precision_control_is_not_correct(make):
    job, key = make(), run.seed_key(SEED)
    low = correct.reference_steps(job, key, jnp.bfloat16, "default")
    nums = correct.readings(
        low, correct.reference_steps(job, key, jnp.float32, "highest"))
    for limits in _limits():
        assert not correct.verdict(nums, limits), nums


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_planted_fault_is_not_correct(fault):
    with faults.FAULTS[fault]():
        nums = _readings(tiny.job())
    for limits in _limits():
        assert not correct.verdict(nums, limits), nums


def test_leaf_rule_and_worst_leaf():
    import numpy as np
    ref = np.array([1.0, 2.0, 3.0, 1e-9])
    prog = np.array([1.0, 2.2, 3.0, 5e-9])
    # the gap of leaf 2 over the larger of its norm and the median's
    assert correct.worst_leaf(prog, ref) == pytest.approx(0.2 / 2.0)
    keep = correct._kept(np.array([1.0, 1.0, 1.0, 1e-7]))
    assert keep.tolist() == [True, True, True, False]
    assert correct.worst_leaf(prog, ref, keep) == pytest.approx(0.1)
