"""Benchmark harness — one function per paper table/figure, plus the
roofline table from the dry-run artifacts.

  fig3_learning      GS vs DIALS vs untrained-DIALS on the 4-agent envs
                     (paper Fig. 3 1a/1b, CPU-scaled).
  fig3_scalability   total runtime vs system size for GS vs DIALS
                     (paper Fig. 3 3a/3b + Tables 1-2, CPU-scaled).
  fig4_f_sweep       AIP refresh frequency F sweep + influence CE
                     (paper Fig. 4).
  table_lemma2       Lemma-2 bound certificate sweep (paper Sec. 4.1.2).
  table_memory       per-process memory split GS vs DIALS (paper Table 3,
                     proxied by simulator state sizes).
  roofline           §Roofline terms for every dry-run cell on disk.

Usage:  PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]
Output: ``name,metric,value`` CSV lines + JSON records in
        experiments/bench/.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _emit(rows, name):
    os.makedirs("experiments/bench", exist_ok=True)
    with open(f"experiments/bench/{name}.json", "w") as f:
        json.dump(rows, f, indent=1, default=float)
    for r in rows:
        for k, v in r.items():
            if k in ("name", "label"):
                continue
            print(f"{name}.{r.get('label', '')},{k},{v}")


# ---------------------------------------------------------------------------
# shared tiny-scale MARL setup (CPU-budget versions of the paper envs).
# Envs resolve through repro.envs.registry, so every registered scenario
# automatically inherits every benchmark below.
# ---------------------------------------------------------------------------
def _env_names():
    from repro.envs import registry
    return registry.names()


def _setup(env_name, n_side, *, horizon=32):
    from repro.core import influence
    from repro.envs import registry
    from repro.marl import policy, ppo
    env_mod, env_cfg = registry.make(env_name, side=n_side, horizon=horizon)
    info = env_cfg.info()
    pc = policy.PolicyConfig(obs_dim=info.obs_dim, n_actions=info.n_actions,
                             hidden=(64, 64))
    ac = influence.AIPConfig(in_dim=info.alsh_dim,
                             n_sources=info.n_influence,
                             kind="fnn", hidden=(32, 32), epochs=10,
                             batch=64, lr=1e-3)
    ppo_cfg = ppo.PPOConfig()
    return env_mod, env_cfg, info, pc, ac, ppo_cfg


def fig3_learning(fast: bool = False, shards=None, async_collect=False,
                  use_kernels="auto", sharded_gs="auto",
                  collect_streams=None):
    """GS vs DIALS vs untrained-DIALS mean return (4-agent envs)."""
    from repro.core import dials
    from repro.launch import variants
    from repro.marl import runner
    rows = []
    rounds = 3 if fast else 10
    inner = 10 if fast else 40
    for env_name in _env_names():
        env_mod, env_cfg, info, pc, ac, ppo_cfg = _setup(env_name, 2)
        # --- DIALS and untrained-DIALS
        for untrained in (False, True):
            cfg = dials.DIALSConfig(
                outer_rounds=rounds, aip_refresh=inner, collect_envs=8,
                collect_steps=64, n_envs=8, rollout_steps=16,
                untrained=untrained, eval_episodes=8,
                use_kernels=use_kernels,
                **variants.dials_variant_for(shards, async_collect,
                                             sharded_gs,
                                             streams=collect_streams))
            tr = dials.DIALSTrainer(env_mod, env_cfg, pc, ac, ppo_cfg, cfg)
            t0 = time.time()
            _, hist = tr.run(jax.random.PRNGKey(0))
            label = ("untrained-DIALS" if untrained else "DIALS") \
                + f"-{env_name}"
            rows.append({"label": label,
                         "final_gs_return": hist[-1]["gs_return"],
                         "best_gs_return": max(h["gs_return"] for h in hist),
                         "aip_ce_final": hist[-1]["aip_ce_after"],
                         "wall_s": time.time() - t0})
        # --- GS baseline: same number of env steps
        init_fn, train_fn, eval_fn = runner.make_gs_trainer(
            env_mod, env_cfg, pc, ppo_cfg,
            runner.RunConfig(n_envs=8, rollout_steps=16))
        state = init_fn(jax.random.PRNGKey(0))
        t0 = time.time()
        for _ in range(rounds * inner):
            state, _m = train_fn(state)
        ret = float(eval_fn(state["params"], jax.random.PRNGKey(1),
                            episodes=8))
        rows.append({"label": f"GS-{env_name}", "final_gs_return": ret,
                     "wall_s": time.time() - t0})
    _emit(rows, "fig3_learning")
    return rows


def fig3_scalability(fast: bool = False):
    """Per-iteration runtime vs number of agents. The paper's claim:
    GS cost grows with system size; DIALS per-agent work is ~flat (the
    agent axis is vmapped/shardable, and between AIP refreshes there is
    zero cross-agent work)."""
    from repro.core import ials as ials_mod, influence
    from repro.marl import runner
    rows = []
    sides = (2, 3) if fast else (2, 3, 4, 5)
    for env_name in _env_names():
        for side in sides:
            env_mod, env_cfg, info, pc, ac, ppo_cfg = _setup(env_name, side)
            n = info.n_agents
            # GS trainer iteration
            init_fn, train_fn, _ = runner.make_gs_trainer(
                env_mod, env_cfg, pc, ppo_cfg,
                runner.RunConfig(n_envs=4, rollout_steps=16))
            state = init_fn(jax.random.PRNGKey(0))
            state, _ = train_fn(state)                  # compile
            t0 = time.time()
            for _ in range(3):
                state, _ = train_fn(state)
            jax.block_until_ready(state["params"])
            gs_it = (time.time() - t0) / 3
            # IALS trainer iteration (the DIALS inner loop)
            iinit, itrain = ials_mod.make_ials_trainer(
                env_mod, env_cfg, pc, ac, ppo_cfg, n_envs=4,
                rollout_steps=16)
            istate = iinit(jax.random.PRNGKey(0))
            aips = jax.vmap(lambda k: influence.aip_init(k, ac))(
                jax.random.split(jax.random.PRNGKey(1), n))
            istate, _ = itrain(istate, aips)            # compile
            t0 = time.time()
            for _ in range(3):
                istate, _ = itrain(istate, aips)
            jax.block_until_ready(istate["params"])
            ials_it = (time.time() - t0) / 3
            rows.append({"label": f"{env_name}-{n}agents",
                         "n_agents": n,
                         "gs_iter_s": gs_it,
                         "dials_iter_s": ials_it,
                         # per-agent: the distributed-deployment number —
                         # one process per agent runs 1/n of this program
                         "dials_iter_per_agent_s": ials_it / n,
                         "speedup_at_scale": gs_it / (ials_it / n)})
    _emit(rows, "fig3_scalability")
    return rows


def fig4_f_sweep(fast: bool = False, shards=None, async_collect=False,
                 use_kernels="auto", sharded_gs="auto"):
    """AIP training frequency F: returns + influence CE (paper Fig. 4)."""
    from repro.core import dials
    from repro.launch import variants
    rows = []
    total_inner = 12 if fast else 60
    sweeps = ((2, 6), (6, 2), (total_inner, 1)) if fast else \
        ((5, 12), (15, 4), (30, 2), (60, 1))
    env_mod, env_cfg, info, pc, ac, ppo_cfg = _setup("warehouse", 2)
    for refresh, rounds in sweeps:
        cfg = dials.DIALSConfig(
            outer_rounds=rounds, aip_refresh=refresh, collect_envs=8,
            collect_steps=64, n_envs=8, rollout_steps=16, eval_episodes=8,
            use_kernels=use_kernels,
            **variants.dials_variant_for(shards, async_collect,
                                             sharded_gs))
        tr = dials.DIALSTrainer(env_mod, env_cfg, pc, ac, ppo_cfg, cfg)
        t0 = time.time()
        _, hist = tr.run(jax.random.PRNGKey(0))
        rows.append({"label": f"F={refresh}x{rounds}",
                     "refresh": refresh,
                     "final_gs_return": hist[-1]["gs_return"],
                     "aip_ce_final": hist[-1]["aip_ce_after"],
                     "wall_s": time.time() - t0})
    _emit(rows, "fig4_f_sweep")
    return rows


def table_lemma2(fast: bool = False):
    """Empirical Lemma-2 certificates: ξ vs |Q1-Q2| vs bound."""
    from repro.core import ialm, theory
    rows = []
    rng = np.random.default_rng(0)
    T1, T2, R, pi2, b0 = ialm.random_system(rng)
    base = ialm.exact_influence(T1, T2, pi2, b0)
    nu = T1.shape[1]
    for eps in (0.0, 0.05, 0.1, 0.2, 0.4):
        pert = theory.perturbed_influence(base, eps, nu)
        cert = theory.lemma2_certificate(
            T1, R, horizon=4, influence1=base, influence2=pert,
            policy=lambda l: np.full((T1.shape[2],), 1 / T1.shape[2]))
        rows.append({"label": f"eps={eps}", "xi": cert["xi"],
                     "lhs_maxQdiff": cert["lhs"], "bound": cert["bound"],
                     "holds": int(cert["holds"])})
    _emit(rows, "table_lemma2")
    return rows


def table_memory(fast: bool = False):
    """Paper Table 3 analogue: state bytes of GS vs per-agent LS."""
    from repro.envs import registry
    rows = []
    for side in (2, 5, 7, 10):
        for env_name in _env_names():
            mod, cfg = registry.make(env_name, side=side)
            gs = mod.gs_init(jax.random.PRNGKey(0), cfg)
            ls = mod.ls_init(jax.random.PRNGKey(0), cfg)
            bytes_of = lambda t: sum(x.size * x.dtype.itemsize
                                     for x in jax.tree.leaves(t))
            n = cfg.n_agents
            rows.append({"label": f"{env_name}-{n}agents",
                         "n_agents": n,
                         "gs_state_bytes": bytes_of(gs),
                         "ls_state_bytes_per_agent": bytes_of(ls),
                         "ls_total_bytes": bytes_of(ls) * n})
    _emit(rows, "table_memory")
    return rows


def roofline_table(fast: bool = False):
    """§Roofline: three terms per dry-run cell on disk (experiments/dryrun)."""
    from benchmarks import roofline
    rows = []
    for fn in sorted(glob.glob("experiments/dryrun/*.json")):
        rec = json.load(open(fn))
        if rec.get("status") != "ok":
            continue
        t = roofline.terms(**roofline.per_device(rec),
                           device_kind=roofline.TARGET_KIND)
        rows.append({"label": os.path.basename(fn)[:-5],
                     "arch": rec["arch"], "shape": rec["shape"],
                     "mesh": rec["mesh"], "variant": rec.get("variant"),
                     **{k: v for k, v in t.items()}})
    _emit(rows, "roofline")
    return rows


BENCHES = {
    "fig3_learning": fig3_learning,
    "fig3_scalability": fig3_scalability,
    "fig4_f_sweep": fig4_f_sweep,
    "table_lemma2": table_lemma2,
    "table_memory": table_memory,
    "roofline": roofline_table,
}


def main() -> None:
    import inspect
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true",
                    help="reduced iteration counts (CI mode)")
    ap.add_argument("--shards", type=int, default=None,
                    help="DIALS runtime shard count (needs that many XLA "
                         "devices; None = auto, 1 = unfused path)")
    ap.add_argument("--async-collect", action="store_true",
                    help="overlap each round's GS collect with the "
                         "previous round's inner steps (one-round "
                         "dataset lag, bounded by max_aip_staleness)")
    ap.add_argument("--use-kernels", default="auto",
                    choices=("auto", "on", "off"),
                    help="Pallas fast paths for the AIP/policy GRU and "
                         "GAE (auto = kernel on TPU, oracle elsewhere; "
                         "on = interpret-mode kernels off-TPU)")
    ap.add_argument("--sharded-gs", default="auto",
                    choices=("auto", "on", "off"),
                    help="region-decomposed GS collect/eval on the mesh "
                         "(auto = whenever the env partition supports "
                         "the shard count)")
    ap.add_argument("--collect-streams", type=int, default=None,
                    help="GS env-stream count S for the DIALS cells "
                         "(wide vmapped collect; None = collect_envs)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture an XLA profiler trace of the whole "
                         "sweep into this directory "
                         "(jax.profiler.start_trace; inspect with "
                         "TensorBoard/xprof — repro.obs.trace spans "
                         "appear as TraceAnnotations)")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    names = [args.only] if args.only else list(BENCHES)
    print("name,metric,value")
    from repro.obs import trace as obs_trace
    with obs_trace.profile(args.profile_dir):
        for n in names:
            fn = BENCHES[n]
            kw = {"fast": args.fast}
            if "shards" in inspect.signature(fn).parameters:
                kw["shards"] = args.shards
            if "async_collect" in inspect.signature(fn).parameters:
                kw["async_collect"] = args.async_collect
            if "use_kernels" in inspect.signature(fn).parameters:
                kw["use_kernels"] = args.use_kernels
            if "sharded_gs" in inspect.signature(fn).parameters:
                kw["sharded_gs"] = args.sharded_gs
            if "collect_streams" in inspect.signature(fn).parameters:
                kw["collect_streams"] = args.collect_streams
            fn(**kw)
    if args.profile_dir:
        print(f"# profiler trace written to {args.profile_dir}")


if __name__ == "__main__":
    main()
