"""Quickstart: train a 4-agent networked system with DIALS in ~2 minutes
on CPU.

The three moving parts of the paper, end to end:
  1. a GLOBAL simulator (GS) used only to collect (ALSH, u) datasets,
  2. per-agent APPROXIMATE INFLUENCE PREDICTORS (AIPs) trained on them,
  3. per-agent LOCAL simulators (IALS) driven by the frozen AIPs, on which
     every agent trains PPO independently (and, in deployment, in
     parallel) for F steps between AIP refreshes.

Any registered environment works — the env resolves by name through
``repro.envs.registry`` (traffic, warehouse, powergrid, supplychain, or
your own).

Run:  PYTHONPATH=src python examples/quickstart.py [--env warehouse]
"""
import argparse

import jax

from repro import compile_cache
from repro.core import dials, influence
from repro.envs import registry
from repro.marl import policy, ppo


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="warehouse", choices=registry.names())
    ap.add_argument("--side", type=int, default=2,
                    help="uniform size knob (side=2 -> 4 agents)")
    args = ap.parse_args()
    compile_cache.enable()

    env_mod, env_cfg = registry.make(args.env, side=args.side, horizon=32)
    info = env_cfg.info()

    policy_cfg = policy.PolicyConfig(
        obs_dim=info.obs_dim, n_actions=info.n_actions, hidden=(64, 64))
    aip_cfg = influence.AIPConfig(
        in_dim=info.alsh_dim, n_sources=info.n_influence,
        kind="fnn", hidden=(32, 32), epochs=10, batch=64, lr=1e-3)

    cfg = dials.DIALSConfig(
        outer_rounds=4,        # collect -> AIP train -> F inner steps, x4
        aip_refresh=20,        # F: PPO iterations between AIP refreshes
        collect_envs=8, collect_steps=64,
        n_envs=8, rollout_steps=16, eval_episodes=8)

    trainer = dials.DIALSTrainer(
        env_mod, env_cfg, policy_cfg, aip_cfg, ppo.PPOConfig(), cfg)

    print(f"training {info.n_agents} {args.env} agents with DIALS "
          f"(F={cfg.aip_refresh} PPO iters/refresh)")
    _, history = trainer.run(jax.random.PRNGKey(0), log=lambda r: print(
        f"  round {r['round']}: GS return {r['gs_return']:.4f}  "
        f"AIP CE {r['aip_ce_before']:.3f}->{r['aip_ce_after']:.3f}  "
        f"({r['wall_s']:.0f}s)"))

    first, last = history[0], history[-1]
    print(f"\nGS return {first['gs_return']:.4f} -> {last['gs_return']:.4f}")
    print("done.")


if __name__ == "__main__":
    main()
