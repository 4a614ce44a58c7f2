"""The one general generator: a cell's job from its configuration file
and its traffic file, and the program's objects built from that job.

A configuration file (``bench/configs/<name>.json``) fixes the model:
environment and size, the policy and influence-predictor networks, AIP
training and PPO hyperparameters, dtype and matmul precision. A traffic
file (``bench/traffic/<name>.json``) fixes the training job's schedule:
inner steps per round (F), GS collect streams and steps, IALS streams and
rollout length, GS eval episodes, sync or async collect. The cell's
chips fix the sharding: one chip takes the loop path, more take the
program's own choice of shards. Every number the program needs comes
from these; the seed only chooses the random draws, never a size.
"""
from __future__ import annotations

CONFIG_KEYS = ("env", "side", "policy", "aip", "aip_train", "ppo", "dtype",
               "matmul_precision")
TRAFFIC_KEYS = ("aip_refresh", "collect_streams", "collect_steps",
                "collect_holdout", "ials_streams", "rollout_steps",
                "eval_episodes", "async_collect", "max_aip_staleness",
                "sharded_gs")


def make_job(config: dict, traffic: dict, chips: int) -> dict:
    """One flat dict of everything a run of this cell fixes."""
    missing = [k for k in CONFIG_KEYS if k not in config] + \
        [k for k in TRAFFIC_KEYS if k not in traffic]
    if missing:
        raise KeyError(f"job is missing {missing}")
    job = {k: config[k] for k in CONFIG_KEYS}
    job.update({k: traffic[k] for k in TRAFFIC_KEYS})
    # one chip takes the loop path even where the host holds more
    job["shards"] = 1 if chips == 1 else None
    if job["dtype"] != "float32" or job["matmul_precision"] != "default":
        raise ValueError("the program runs float32 at the default matmul "
                         "precision only; got "
                         f"{job['dtype']} / {job['matmul_precision']}")
    return job


def agent_steps_per_round(job: dict, n_agents: int) -> int:
    """IALS agent env-steps PPO trains on in one round: N x E x T x F."""
    return (n_agents * job["ials_streams"] * job["rollout_steps"]
            * job["aip_refresh"])


def _network_keys(net: dict) -> dict:
    """Every key of a configuration's ``policy`` or ``aip`` object, as
    the program's network config takes it (lists as tuples). A key the
    program's config lacks fails where the config is built."""
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in net.items()}


def program(job: dict, outer_rounds: int):
    """The program's own objects for this job:
    ``(env_mod, env_cfg, policy_cfg, aip_cfg, ppo_cfg, dials_cfg)``.
    Imports the program lazily so the rest of the harness loads without
    it."""
    from repro.core import dials, influence
    from repro.envs import registry
    from repro.marl import policy, ppo

    env_mod, env_cfg = registry.make(job["env"], side=job["side"])
    info = env_cfg.info()
    pc = policy.PolicyConfig(obs_dim=info.obs_dim, n_actions=info.n_actions,
                             **_network_keys(job["policy"]))
    ac = influence.AIPConfig(in_dim=info.alsh_dim,
                             n_sources=info.n_influence,
                             **_network_keys(job["aip"]), **job["aip_train"])
    ppo_cfg = ppo.PPOConfig(**job["ppo"])
    cfg = dials.DIALSConfig(
        aip_refresh=job["aip_refresh"], outer_rounds=outer_rounds,
        collect_envs=job["collect_streams"],
        collect_steps=job["collect_steps"],
        collect_holdout=job["collect_holdout"],
        eval_episodes=job["eval_episodes"], n_envs=job["ials_streams"],
        rollout_steps=job["rollout_steps"],
        max_aip_staleness=job["max_aip_staleness"],
        async_collect=job["async_collect"], shards=job["shards"],
        sharded_gs=job["sharded_gs"])
    return env_mod, env_cfg, pc, ac, ppo_cfg, cfg
