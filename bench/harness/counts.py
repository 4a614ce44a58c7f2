"""Operations and bytes a DIALS round needs, from the job's shapes alone.

Two kinds of count:

- ``round_matmul_flops(job, info)``: the matmul FLOPs one outer round of
  Algorithm 1 requires (2 per multiply-add), for ``round_mfu``. Every
  network pass counts once: the policy forward in the GS collect, the
  IALS rollouts, the bootstrap value and the GS eval; the AIP forward in
  the rollouts and in the held-out CE before and after training; PPO and
  AIP training forward and backward (backward = 2 x forward), over their
  real epochs and minibatches. Work a program repeats (each shard
  re-running a replicated GS) or recomputes (a GRU backward that redoes
  the forward) is not needed, so it does not count.
- ``gae_ops`` / ``gru_ops``: the kernel operations of one round, each as
  ``(flops, bytes)`` of the operation itself: the GAE reverse scan (adv
  from rewards, values, next values and dones) and the GRU recurrence
  (hidden states from precomputed gate inputs ``gi``), forward and
  backward. They are the work of the operation, not of an
  implementation: a kernel that fuses more (the input projection, the
  returns) does more than is counted and reads a lower share, never a
  higher one.

``info`` is an object with ``n_agents, obs_dim, n_actions, n_influence,
alsh_dim, horizon`` (the environment's static facts).
"""
from __future__ import annotations

F32 = 4


def _mlp_flops(din, hidden):
    f, d = 0, din
    for h in hidden:
        f += 2 * d * h
        d = h
    return f, d


def policy_fwd_flops(job, info) -> int:
    """Matmul FLOPs of one policy step for one agent and one stream."""
    p = job["policy"]
    f, d = _mlp_flops(info.obs_dim, p["hidden"])
    if p["kind"] == "gru":
        h = p["gru_hidden"]
        f += 2 * d * 3 * h + 2 * h * 3 * h
        d = h
    return f + 2 * d * (info.n_actions + 1)


def aip_fwd_flops(job, info) -> int:
    a = job["aip"]
    f, d = _mlp_flops(info.alsh_dim, a["hidden"])
    if a["kind"] == "gru":
        h = a["gru_hidden"]
        f += 2 * d * 3 * h + 2 * h * 3 * h
        d = h
    return f + 2 * d * info.n_influence


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


def round_matmul_flops(job, info) -> float:
    n, tc = info.n_agents, job["collect_steps"]
    pol, aip = policy_fwd_flops(job, info), aip_fwd_flops(job, info)
    e, t, f = job["ials_streams"], job["rollout_steps"], job["aip_refresh"]
    collect = job["collect_streams"] * tc * n * pol
    n_eval, batch, n_mb = _aip_shapes(job)
    aip_round = (2 * n_eval * tc * n * aip
                 + job["aip_train"]["epochs"] * n_mb * batch * tc * n
                 * 3 * aip)
    mb, mbs = _ppo_shapes(job)
    inner = f * n * (e * t * (pol + aip) + e * pol
                     + job["ppo"]["epochs"] * mbs * mb * t * 3 * pol)
    evaluate = job["eval_episodes"] * info.horizon * n * pol
    return float(collect + aip_round + inner + evaluate)


# -- kernel operations --------------------------------------------------------
def gae_fwd(b: int, t: int):
    """(flops, bytes) of the GAE reverse scan over b rows of t steps:
    delta = r + g*nv*(1-d) - v (5 flops), adv = delta + g*l*(1-d)*carry
    (3 flops); reads r, v, nv, d and writes adv, float32."""
    return 8.0 * b * t, F32 * 5.0 * b * t


def gru_fwd(b: int, t: int, h: int):
    """(flops, bytes) of the GRU recurrence over b rows and t steps with
    hidden size h: per row and step gh = h_prev @ W_h (2*h*3h) and ~14h
    elementwise (gates, reset mask, update); reads gi (3h), the reset
    flag, W_h, b_h, h0 and writes hs (h)."""
    flops = b * t * (6.0 * h * h + 14.0 * h)
    bytes_ = F32 * (b * t * (3 * h + 1 + h) + 3 * h * h + 3 * h + b * h)
    return flops, bytes_


def gru_bwd(b: int, t: int, h: int):
    """(flops, bytes) of the recurrence's backward: per row and step the
    two adjoint matmuls dh_prev = dgh @ W_h^T and dW_h += h_prev^T dgh
    (2 x 2*h*3h) and ~30h elementwise; the forward it recomputes does not
    count. Reads gi, h_prev, the cotangent, the reset flag and W_h, b_h;
    writes dgi (3h per row and step), dW_h, db_h and dh0."""
    flops = b * t * (12.0 * h * h + 30.0 * h)
    bytes_ = F32 * (b * t * (3 * h + h + h + 1 + 3 * h)
                    + 2 * (3 * h * h + 3 * h) + b * h)
    return flops, bytes_


def gae_ops(job, info):
    """The GAE kernel operations of one round: one forward per inner step
    over all agents' streams (PPO does not differentiate through GAE)."""
    b = info.n_agents * job["ials_streams"]
    return [gae_fwd(b, job["rollout_steps"])] * job["aip_refresh"]


def gru_ops(job, info):
    """The GRU recurrence operations of one round, forward and backward,
    for every network that is a GRU. A single policy or AIP step is a
    recurrence of length 1."""
    n, ops = info.n_agents, []
    p, a = job["policy"], job["aip"]
    e, t, f = job["ials_streams"], job["rollout_steps"], job["aip_refresh"]
    if p["kind"] == "gru":
        hp = p["gru_hidden"]
        ops += [gru_fwd(n * job["collect_streams"], 1, hp)] \
            * job["collect_steps"]
        ops += [gru_fwd(n * e, 1, hp)] * ((t + 1) * f)
        mb, mbs = _ppo_shapes(job)
        k = f * job["ppo"]["epochs"] * mbs
        ops += [gru_fwd(n * mb, t, hp), gru_bwd(n * mb, t, hp)] * k
        ops += [gru_fwd(n * job["eval_episodes"], 1, hp)] * info.horizon
    if a["kind"] == "gru":
        ha, tc = a["gru_hidden"], job["collect_steps"]
        ops += [gru_fwd(n * e, 1, ha)] * (t * f)
        n_eval, batch, n_mb = _aip_shapes(job)
        ops += [gru_fwd(n * n_eval, tc, ha)] * 2
        k = job["aip_train"]["epochs"] * n_mb
        ops += [gru_fwd(n * batch, tc, ha), gru_bwd(n * batch, tc, ha)] * k
    return ops
