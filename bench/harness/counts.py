"""Operations and bytes a DIALS round needs, from the job's shapes alone.

Every count follows ``network_passes``: each pass of the policy or the
influence predictor (AIP) that one outer round of Algorithm 1 makes,
over how many rows and steps, and whether it is differentiated. A
network is an MLP trunk, a backbone and its heads; the backbone's own
counts come from ``bench/harness/ref/backbones/<kind>.py``, found by the
``kind`` the configuration names.

- ``round_matmul_flops(job, info)``: the matmul FLOPs one round
  requires (2 per multiply-add), for ``round_mfu``. Every network pass
  counts once: the policy forward in the GS collect, the IALS rollouts,
  the bootstrap value and the GS eval; the AIP forward in the rollouts
  and in the held-out CE before and after training; PPO and AIP
  training forward and backward (backward = 2 x forward), over their
  real epochs and minibatches. Work a program repeats (each shard
  re-running a replicated GS) or recomputes (a recurrent backward that
  redoes the forward) is not needed, so it does not count.
- ``kernel_ops(kind, job, info)``: the operations of one round's
  kernels of a backbone kind (named ``<kind>_fwd`` / ``<kind>_bwd`` in
  the program), and ``gae_ops``: those of the GAE reverse scan (adv
  from rewards, values, next values and dones), which is no backbone.
  Each is ``(flops, bytes)`` of the operation itself, not of an
  implementation: a kernel that fuses more (the input projection, the
  returns) does more than is counted and reads a lower share, never a
  higher one.

``info`` is an object with ``n_agents, obs_dim, n_actions, n_influence,
alsh_dim, horizon`` (the environment's static facts).
"""
from __future__ import annotations

from .ref import backbones

F32 = 4


def _mlp_flops(din, hidden):
    f, d = 0, din
    for h in hidden:
        f += 2 * d * h
        d = h
    return f, d


def _net_flops(net, din, dout) -> int:
    f, d = _mlp_flops(din, net["hidden"])
    core, d = backbones.get(net["kind"]).fwd_flops(d, net)
    return f + core + 2 * d * dout


def policy_fwd_flops(job, info) -> int:
    """Matmul FLOPs of one policy step for one agent and one stream: the
    trunk, the backbone, and the logits and value heads."""
    return _net_flops(job["policy"], info.obs_dim, info.n_actions + 1)


def aip_fwd_flops(job, info) -> int:
    return _net_flops(job["aip"], info.alsh_dim, info.n_influence)


def _aip_shapes(job):
    s = job["collect_streams"]
    n_eval = max(0, min(job["collect_holdout"], s - 1))
    n_train = s - n_eval if n_eval else s
    batch = min(job["aip_train"]["batch"], n_train)
    n_mb = -(-n_train // batch)
    return (n_eval or s), batch, n_mb


def _ppo_shapes(job):
    e, mbs = job["ials_streams"], job["ppo"]["minibatches"]
    return max(1, e // mbs), mbs


def network_passes(job, info):
    """Every network pass of one round, in the order of the round, as
    ``(network, rows, steps, backward, times)``: ``network`` is
    ``"policy"`` or ``"aip"``, the key of its configuration in the job;
    a single step is a pass of one step."""
    n, tc = info.n_agents, job["collect_steps"]
    e, t, f = job["ials_streams"], job["rollout_steps"], job["aip_refresh"]
    mb, mbs = _ppo_shapes(job)
    n_eval, batch, n_mb = _aip_shapes(job)
    return [
        # GS collect
        ("policy", n * job["collect_streams"], 1, False, tc),
        # IALS rollouts and the bootstrap value, F inner steps
        ("policy", n * e, 1, False, (t + 1) * f),
        # PPO, every minibatch of every epoch of the F inner steps
        ("policy", n * mb, t, True, f * job["ppo"]["epochs"] * mbs),
        # GS eval
        ("policy", n * job["eval_episodes"], 1, False, info.horizon),
        # IALS rollouts
        ("aip", n * e, 1, False, t * f),
        # held-out CE before and after training
        ("aip", n * n_eval, tc, False, 2),
        # AIP training, every minibatch of every epoch
        ("aip", n * batch, tc, True, job["aip_train"]["epochs"] * n_mb),
    ]


def round_matmul_flops(job, info) -> float:
    step = {"policy": policy_fwd_flops(job, info),
            "aip": aip_fwd_flops(job, info)}
    return float(sum(rows * steps * (3 if backward else 1) * times
                     * step[net]
                     for net, rows, steps, backward, times
                     in network_passes(job, info)))


def kernel_ops(kind: str, job, info):
    """The kernel operations of one round of every network whose
    backbone is ``kind``, in the order of ``network_passes``. A kind with
    no file under ``bench/harness/ref/backbones/`` raises."""
    backbone, ops = backbones.get(kind), []
    for net, rows, steps, backward, times in network_passes(job, info):
        if job[net]["kind"] == kind:
            ops += backbone.kernel_ops(job[net], rows, steps,
                                       backward) * times
    return ops


def gae_fwd(b: int, t: int):
    """(flops, bytes) of the GAE reverse scan over b rows of t steps:
    delta = r + g*nv*(1-d) - v (5 flops), adv = delta + g*l*(1-d)*carry
    (3 flops); reads r, v, nv, d and writes adv, float32."""
    return 8.0 * b * t, F32 * 5.0 * b * t


def gae_ops(job, info):
    """The GAE kernel operations of one round: one forward per inner step
    over all agents' streams (PPO does not differentiate through GAE)."""
    b = info.n_agents * job["ials_streams"]
    return [gae_fwd(b, job["rollout_steps"])] * job["aip_refresh"]
