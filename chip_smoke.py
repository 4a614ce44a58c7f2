"""Chip smoke: DIALS training on a TPU through its normal entry points.

Run from the root of a checkout, in one process that owns the chip:

    python3 chip_smoke.py               # one chip: phases (a)-(d)
    python3 chip_smoke.py --four-chips  # four chips: phase (e) only

(a) device       print ``jax.devices()``; anything but a TPU is a failure
                 (there is no CPU fallback).
(b) kernels      the Pallas GAE and GRU kernels compiled for the chip
                 (``interpret=False``), vmapped over agents at the shapes
                 phases (c)/(d) call them with, against the jnp oracles,
                 both at ``highest`` matmul precision.
(c) traffic      ``DIALSTrainer.run`` on traffic side 10 (100
                 intersections, the paper's largest traffic network) with
                 the default FNN policy (256, 128) and FNN AIP (128, 128).
(d) warehouse    ``DIALSTrainer.run`` on warehouse side 5 (25 robots) with
                 a GRU AIP (H=64) and a GRU policy (H=128); prints every
                 GRU launch its programs traced (kernel, agents, agents a
                 grid step, grid steps) and fails if a launch of several
                 agents advances one agent a grid step.
(e) four chips   traffic side 8 (64 agents, 4 row bands) through the
                 agent-sharded fused round with the region-decomposed GS
                 (``shards=4, sharded_gs="on"``) against the same config
                 at ``shards=1`` on one chip, at ``highest`` precision.

Phases (c)-(e) keep the library's widths, agent counts and
``DIALSConfig`` defaults; only the number of outer rounds and of inner
steps per round (F) is cut. Round times are host-clock times between the
trainer's per-round records (each record fetch waits for the round); the
first round includes compilation. They are a smoke check, not a
benchmark.

Any failed check exits non-zero. Only when every phase passed is the
last line of stdout the verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    raise SystemExit("chip_smoke.py must run from a checkout of the "
                     "repository (src/repro not found beside it)")
sys.path.insert(0, str(ROOT / "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro import compile_cache                             # noqa: E402
from repro.core import dials, influence                     # noqa: E402
from repro.envs import registry                             # noqa: E402
from repro.kernels.gae import ops as gae_ops                # noqa: E402
from repro.kernels.gru import kernel as gru_kernel          # noqa: E402
from repro.kernels.gru import ops as gru_ops                # noqa: E402
from repro.marl import gae as gae_mod                       # noqa: E402
from repro.marl import policy, ppo                          # noqa: E402
from repro.nn import gru as gru_mod                         # noqa: E402

# kernel vs oracle: max |kernel - oracle| / (1 + max |oracle|) per output
PARITY_TOL = 1e-4
# sharded vs one-chip run of the same config (phase e). The two run the
# same algorithm as differently fused programs, so they agree to float
# rounding until a rounding difference flips one sampled action; the
# runs then collect slightly different data. Hence: round 0 (same
# data, same init) is held tight; later rounds' gs_return as the CPU
# equivalence suite holds it (tests/_multidevice_check.py); final
# parameters by relative L2 distance. Adam moves a weight whose gradient
# is at rounding level by up to ~lr per step either way, so max-abs
# parameter differences reach rounds x steps x lr (printed, not held);
# a sharding fault (wrong agent slice, lost halo) moves them by O(1).
ROUND0_ATOL = 1e-4
GS_RETURN_ATOL = 5e-2
PARAM_RTOL = 5e-2
EXPECTED_KERNELS = "policy=pallas,aip=pallas,ppo=pallas"
ROUNDS, INNER = 3, 10          # outer rounds and F, cut from (4, 50)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# configurations
# ---------------------------------------------------------------------------
def traffic_setup(side: int = 10, **dials_kw):
    env_mod, env_cfg = registry.make("traffic", side=side)
    info = env_cfg.info()
    pc = policy.PolicyConfig(obs_dim=info.obs_dim, n_actions=info.n_actions)
    ac = influence.AIPConfig(in_dim=info.alsh_dim,
                             n_sources=info.n_influence, kind="fnn")
    cfg = dials.DIALSConfig(outer_rounds=ROUNDS, aip_refresh=INNER,
                            **dials_kw)
    return env_mod, env_cfg, pc, ac, ppo.PPOConfig(), cfg


def warehouse_setup(side: int = 5):
    env_mod, env_cfg = registry.make("warehouse", side=side)
    info = env_cfg.info()
    pc = policy.PolicyConfig(obs_dim=info.obs_dim, n_actions=info.n_actions,
                             kind="gru", gru_hidden=128)
    ac = influence.AIPConfig(in_dim=info.alsh_dim,
                             n_sources=info.n_influence, kind="gru",
                             gru_hidden=64)
    cfg = dials.DIALSConfig(outer_rounds=ROUNDS, aip_refresh=INNER)
    return env_mod, env_cfg, pc, ac, ppo.PPOConfig(), cfg


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------
def device_phase(want_count: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    print(f"(a) devices: {devs}")
    print(f"    platform={d.platform} device_kind={d.device_kind} "
          f"count={len(devs)}")
    check(d.platform == "tpu", f"platform is {d.platform!r}, not 'tpu'")
    check(len(devs) >= want_count,
          f"{len(devs)} device(s), this run needs {want_count}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# (b) kernel parity
# ---------------------------------------------------------------------------
def _rel_err(got, want) -> float:
    errs = []
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        errs.append(np.abs(g - w).max() / (1.0 + np.abs(w).max()))
    return float(max(errs))


def parity_cases(traffic, warehouse):
    """(name, kind, agents, shape) of every kernel call the two trainer
    phases make, read off their configs, plus an odd batch per kernel."""
    _, t_env, _, _, _, t_cfg = traffic
    _, w_env, w_pc, w_ac, w_ppo, w_cfg = warehouse
    n_t, n_w = t_env.info().n_agents, w_env.info().n_agents
    e, t = dials.ials_stream_count(t_cfg), t_cfg.rollout_steps
    we, wt = dials.ials_stream_count(w_cfg), w_cfg.rollout_steps
    aip_seqs = min(w_ac.batch, dials.collect_stream_count(w_cfg)
                   - dials.holdout_sequences(w_cfg))
    pdin, adin = w_pc.hidden[-1], w_ac.hidden[-1]
    return [
        ("gae traffic", "gae", n_t, (e, t)),
        ("gae warehouse", "gae", n_w, (we, wt)),
        ("gae odd batch", "gae", n_w, (6, wt)),
        ("gru_sequence ppo policy", "seq", n_w,
         (we // w_ppo.minibatches, wt, pdin, w_pc.gru_hidden)),
        ("gru_sequence aip train", "seq", n_w,
         (aip_seqs, w_cfg.collect_steps, adin, w_ac.gru_hidden)),
        ("gru_sequence odd batch", "seq", n_w,
         (6, wt, pdin, w_pc.gru_hidden)),
        ("gru_cell ials policy", "cell", n_w, (we, pdin, w_pc.gru_hidden)),
        ("gru_cell ials aip", "cell", n_w, (we, adin, w_ac.gru_hidden)),
        ("gru_cell odd batch", "cell", n_w, (6, pdin, w_pc.gru_hidden)),
    ]


def _gae_pair(key, n, shape):
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (n,) + shape)
    v = jax.random.normal(ks[1], (n,) + shape)
    d = jax.random.bernoulli(ks[2], 0.05, (n,) + shape).astype(jnp.float32)
    lv = jax.random.normal(ks[3], (n, shape[0]))
    g = jax.random.normal(ks[4], (n,) + shape)

    def build(fn):
        def agent(r, v, d, lv, g):
            def loss(r, v, lv):
                adv, ret = fn(r, v, d, lv)
                return (adv * g).sum() + (ret ** 2).sum()
            return fn(r, v, d, lv)[0], jax.grad(loss, (0, 1, 2))(r, v, lv)
        return jax.jit(jax.vmap(agent))

    kernel = build(lambda r, v, d, lv: gae_ops.gae(r, v, d, lv,
                                                   interpret=False))
    oracle = build(lambda r, v, d, lv: gae_mod.gae(r, v, d, lv,
                                                   use_kernels="off"))
    args = (r, v, d, lv, g)
    return kernel(*args), oracle(*args)


def _gru_params(key, n, din, h):
    return jax.vmap(lambda k: gru_mod.gru_init(
        k, gru_mod.GRUConfig(in_dim=din, hidden=h)))(jax.random.split(key, n))


def _seq_pair(key, n, shape):
    b, t, din, h = shape
    ks = jax.random.split(key, 5)
    p = _gru_params(ks[0], n, din, h)
    xs = jax.random.normal(ks[1], (n, b, t, din))
    h0 = 0.5 * jax.random.normal(ks[2], (n, b, h))
    resets = jax.random.bernoulli(ks[3], 0.1, (n, b, t)).astype(jnp.float32)
    g = jax.random.normal(ks[4], (n, b, t, h))

    def build(fwd):
        def agent(p, xs, h0, resets, g):
            def loss(p, xs, h0):
                hs, last = fwd(p, xs, h0, resets)
                return (hs * g).sum() + last.sum()
            return (fwd(p, xs, h0, resets),
                    jax.grad(loss, (0, 1, 2))(p, xs, h0))
        return jax.jit(jax.vmap(agent))

    kernel = build(lambda p, xs, h0, r: gru_ops.gru_sequence(
        p, xs, h0, reset_mask=r, interpret=False))
    oracle = build(lambda p, xs, h0, r: gru_mod.gru_sequence(
        p, xs, h0, reset_mask=r, use_kernels="off"))
    args = (p, xs, h0, resets, g)
    return kernel(*args), oracle(*args)


def _cell_pair(key, n, shape):
    b, din, h = shape
    ks = jax.random.split(key, 3)
    p = _gru_params(ks[0], n, din, h)
    hh = 0.5 * jax.random.normal(ks[1], (n, b, h))
    x = jax.random.normal(ks[2], (n, b, din))
    kernel = jax.jit(jax.vmap(lambda p, hh, x: gru_ops.gru_cell(
        p, hh, x, interpret=False)))
    oracle = jax.jit(jax.vmap(lambda p, hh, x: gru_mod.gru_cell(
        p, hh, x, use_kernels="off")))
    return (kernel(p, hh, x), None), (oracle(p, hh, x), None)


PAIRS = {"gae": _gae_pair, "seq": _seq_pair, "cell": _cell_pair}


def parity_phase(cases, seed: int) -> None:
    print(f"(b) kernel parity vs jnp oracle, highest precision "
          f"(tolerance {PARITY_TOL:g}, error = max|k-o| / (1+max|o|))")
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision("highest"):
        for i, (name, kind, n, shape) in enumerate(cases):
            (k_fwd, k_grad), (o_fwd, o_grad) = PAIRS[kind](
                jax.random.fold_in(key, i), n, shape)
            errs = {"fwd": _rel_err(k_fwd, o_fwd)}
            if k_grad is not None:
                errs["grad"] = _rel_err(k_grad, o_grad)
            print(f"    {name:<26} agents={n:<4} shape={shape}: " +
                  "  ".join(f"{k} err {v!r}" for k, v in errs.items()),
                  flush=True)
            worst = max(errs.values())
            check(bool(np.isfinite(worst)) and worst <= PARITY_TOL,
                  f"kernel parity {name}: error {worst!r} > {PARITY_TOL:g}")


# ---------------------------------------------------------------------------
# (c)/(d) trainer phases
# ---------------------------------------------------------------------------
def _finite_tree(tree) -> bool:
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(tree))


def train_phase(label: str, setup, seed: int, *, n_shards: int = 1,
                expected_kernels: str = EXPECTED_KERNELS):
    """``DIALSTrainer.run`` through its normal entry point; checks every
    round record and the final parameters. Returns (trainer, state,
    history)."""
    env_mod, env_cfg, pc, ac, ppo_cfg, cfg = setup
    info = env_cfg.info()

    def net(c):
        return f"{c.kind} {c.hidden}" + (f" gru {c.gru_hidden}"
                                         if c.kind == "gru" else "")
    print(f"    {label}: {info.n_agents} agents, policy {net(pc)}, "
          f"AIP {net(ac)}, rounds={cfg.outer_rounds} F={cfg.aip_refresh} "
          f"shards={cfg.shards} sharded_gs={cfg.sharded_gs}", flush=True)
    trainer = dials.DIALSTrainer(env_mod, env_cfg, pc, ac, ppo_cfg, cfg)
    stamps = []

    def log(r):
        stamps.append(time.perf_counter())
        print(f"    [{label}] round {r['round']}: "
              f"gs_return={r['gs_return']!r} "
              f"aip_ce={r['aip_ce_before']!r}->{r['aip_ce_after']!r} "
              f"ials_reward={r['ials_reward']!r} "
              f"n_shards={r['n_shards']} kernels={r['kernels']}",
              flush=True)

    t0 = time.perf_counter()
    state, hist = trainer.run(jax.random.PRNGKey(seed), log=log)
    jax.block_until_ready(state)
    round_s = np.diff([t0] + stamps)
    steady = float(np.mean(round_s[1:])) if len(round_s) > 1 else None
    print(f"    [{label}] first round (compile included) "
          f"{float(round_s[0])!r} s, steady round {steady!r} s, "
          f"compile ~{float(round_s[0]) - (steady or 0.0)!r} s "
          f"(chip smoke, not a benchmark)", flush=True)

    check(len(hist) == cfg.outer_rounds,
          f"{label}: {len(hist)} rounds, expected {cfg.outer_rounds}")
    for r in hist:
        vals = [r["gs_return"], r["aip_ce_before"], r["aip_ce_after"],
                r["ials_reward"]]
        check(all(v is not None and np.isfinite(v) for v in vals),
              f"{label} round {r['round']}: non-finite record {vals}")
        check(r["kernels"] == expected_kernels,
              f"{label}: kernels={r['kernels']!r}, expected "
              f"{expected_kernels!r}")
        check(r["n_shards"] == n_shards,
              f"{label}: n_shards={r['n_shards']}, expected {n_shards}")
    check(_finite_tree(state["ials"]["params"]) and
          _finite_tree(state["aips"]),
          f"{label}: non-finite final parameters")
    return trainer, state, hist


def launch_report(before: dict) -> None:
    """The GRU launches traced since ``before`` (a ``launch_stats``);
    each launch of several agents has to advance several a grid step."""
    stats = gru_kernel.launch_stats()
    new = {k: n - before.get(k, 0) for k, n in stats.items()
           if n > before.get(k, 0)}
    for (name, a, blk, steps), n in sorted(new.items()):
        print(f"    {name}: {n} launch(es), {a} agents, {blk} a grid "
              f"step, {steps} grid steps", flush=True)
    check(bool(new), "no GRU launch was traced")
    check(all(blk > 1 for _, a, blk, _ in new if a > 1),
          "a GRU launch of several agents advances one a grid step")


# ---------------------------------------------------------------------------
# (e) four chips
# ---------------------------------------------------------------------------
def _param_diffs(a, b):
    """(max |a - b|, ||a - b||_2 / ||b||_2) over all leaves."""
    xs = [np.asarray(x, np.float64).ravel() for x in jax.tree.leaves(a)]
    ys = [np.asarray(y, np.float64).ravel() for y in jax.tree.leaves(b)]
    d = np.concatenate(xs) - np.concatenate(ys)
    return float(np.abs(d).max()), float(np.linalg.norm(d) /
                                         np.linalg.norm(np.concatenate(ys)))


def sharded_phase(setup, seed: int, *, shards: int = 4,
                  expected_kernels: str = EXPECTED_KERNELS) -> None:
    env_mod, env_cfg, pc, ac, ppo_cfg, cfg = setup
    check(cfg.shards == shards and cfg.sharded_gs == "on",
          "phase (e) needs a shards=N, sharded_gs='on' config")
    print(f"(e) agent-sharded round over {shards} chips vs one chip, "
          f"highest precision", flush=True)
    with jax.default_matmul_precision("highest"):
        tr_n, s_n, h_n = train_phase(f"traffic {shards}-shard", setup, seed,
                                     n_shards=shards,
                                     expected_kernels=expected_kernels)
        runner = tr_n._sharded      # the mesh the round programs ran on
        mesh_ids = sorted({d.id for d in runner.mesh.devices.flat})
        print(f"    mesh devices {mesh_ids}, sharded GS "
              f"{runner.use_sharded_gs}")
        check(runner.n_shards == shards and len(mesh_ids) == shards,
              f"mesh spans {mesh_ids}, expected {shards} distinct devices")
        check(runner.use_sharded_gs, "region-decomposed GS was not active")
        one = (env_mod, env_cfg, pc, ac, ppo_cfg,
               dataclasses.replace(cfg, shards=1, sharded_gs="off"))
        _, s_1, h_1 = train_phase("traffic 1-chip", one, seed,
                                  expected_kernels=expected_kernels)
    keys = ("gs_return", "aip_ce_before", "aip_ce_after", "ials_reward")
    r0_diff = max(abs(h_n[0][k] - h_1[0][k]) for k in keys)
    gs_diff = max(abs(a["gs_return"] - b["gs_return"])
                  for a, b in zip(h_n, h_1))
    aip_max, aip_rel = _param_diffs(s_n["aips"], s_1["aips"])
    pol_max, pol_rel = _param_diffs(s_n["ials"]["params"],
                                    s_1["ials"]["params"])
    print(f"    round 0 max |record diff| {r0_diff!r} (atol {ROUND0_ATOL:g})"
          f"\n    max |gs_return diff| over rounds {gs_diff!r} "
          f"(atol {GS_RETURN_ATOL:g})"
          f"\n    AIP params: rel L2 {aip_rel!r} (rtol {PARAM_RTOL:g}), "
          f"max abs {aip_max!r}"
          f"\n    policy params: rel L2 {pol_rel!r} (rtol {PARAM_RTOL:g}), "
          f"max abs {pol_max!r}")
    check(r0_diff <= ROUND0_ATOL, f"round 0 records differ by {r0_diff!r}")
    check(gs_diff <= GS_RETURN_ATOL, f"gs_return differs by {gs_diff!r}")
    check(aip_rel <= PARAM_RTOL, f"AIP params differ by rel {aip_rel!r}")
    check(pol_rel <= PARAM_RTOL, f"policy params differ by rel {pol_rel!r}")


# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded phase (e)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and data")
    args = ap.parse_args()

    cache_dir = compile_cache.enable()
    print(f"compile cache: {cache_dir}", flush=True)
    try:
        device = device_phase(4 if args.four_chips else 1)
        if args.four_chips:
            sharded_phase(traffic_setup(side=8, shards=4, sharded_gs="on"),
                          args.seed)
        else:
            traffic, warehouse = traffic_setup(), warehouse_setup()
            parity_phase(parity_cases(traffic, warehouse), args.seed)
            print("(c) traffic", flush=True)
            train_phase("traffic", traffic, args.seed)
            print("(d) warehouse", flush=True)
            before = gru_kernel.launch_stats()
            train_phase("warehouse", warehouse, args.seed)
            launch_report(before)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    cache = compile_cache.stats()
    state = ("cold" if not cache["loaded"] else
             "warm" if not cache["written"] else "partly warm")
    print(f"compile cache {cache_dir}: {state} ({cache['loaded']} programs "
          f"loaded, {cache['written']} compiled and written)")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
