"""Kernel benchmark suite — oracle vs Pallas on the DIALS hot spots.

Per-kernel microbenchmarks (gru, gae): forward and forward+backward
wall-clock for the pure-jnp oracle vs the Pallas kernel, swept over
(B, T, H) shapes drawn from the registered scenarios (the AIP-training
minibatch and the PPO rollout recompute of each env, agent axis folded
into the batch the way the vmapped trainers fold it) plus one headline
TPU-sized shape. Each row carries the roofline terms of the target chip
(``roofline.TARGET_KIND``, TPU v5e) for the kernel's analytic FLOP/byte
footprint (``benchmarks/roofline.py``) —
``roofline_fraction`` ≈ 1 means the fused scan would be MXU-bound on the
target, not memory-bound.

End-to-end A/B: a full ``train_aip`` (GRU AIP, grads through the
custom_vjp) and one IALS inner step (``ials_train``: rollout + GAE +
PPO with a GRU policy) with ``use_kernels`` off vs on.

On CPU the kernel columns run in Pallas INTERPRET mode — they measure
the interpreter, not the TPU, and will be slower than the oracle; the
point of the artifact on CPU is the oracle baselines, the roofline
numbers, and CI coverage of the full bench path. On a TPU backend the
same script emits the real A/B.

Usage:  PYTHONPATH=src python -m benchmarks.kernels [--fast]
Output: ``BENCH_kernels.json`` at the repo root (the first root-level
bench artifact) + ``name,metric,value`` CSV lines on stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks import roofline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "BENCH_kernels.json")


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------
def _time(fn, *args, iters: int) -> float:
    out = fn(*args)
    jax.block_until_ready(out)                 # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


# ---------------------------------------------------------------------------
# shape sweep: drawn from the registered scenarios
# ---------------------------------------------------------------------------
def swept_shapes(fast: bool):
    """(label, B, T, in, H) per scenario: the AIP-training minibatch
    (collect_envs × n_agents sequences of collect_steps, trunk width →
    gru_hidden) and the PPO recompute (n_envs × n_agents chunks of
    rollout_steps) — plus one headline TPU-sized shape."""
    from repro.core import dials, influence
    from repro.envs import registry
    from repro.marl import policy
    dcfg = dials.DIALSConfig()
    acfg = influence.AIPConfig(in_dim=1, n_sources=1)
    pcfg = policy.PolicyConfig(obs_dim=1, n_actions=1)
    t_collect = 16 if fast else dcfg.collect_steps
    t_roll = 8 if fast else dcfg.rollout_steps
    shapes = []
    for name in registry.names():
        info = registry.make(name, side=2)[1].info()
        shapes.append((f"{name}-aip", dcfg.collect_envs * info.n_agents,
                       t_collect, acfg.hidden[-1], acfg.gru_hidden))
        shapes.append((f"{name}-policy", dcfg.n_envs * info.n_agents,
                       t_roll, pcfg.hidden[-1], pcfg.gru_hidden))
    shapes.append(("headline", 32 if fast else 256, t_collect,
                   pcfg.hidden[-1], pcfg.gru_hidden))
    if fast:            # CI smoke: one aip + two policy shapes + headline
        shapes = shapes[:2] + shapes[3:4] + shapes[-1:]
    return shapes


# ---------------------------------------------------------------------------
# analytic roofline footprints (per call, fp32)
# ---------------------------------------------------------------------------
def _gru_roofline(b, t, din, h, *, backward: bool):
    inp = 2.0 * b * t * din * 3 * h            # x·W_i for all steps
    rec = 2.0 * b * t * h * 3 * h              # h·W_h, T sequential steps
    elem = 12.0 * b * t * h
    flops = inp + rec + elem
    if backward:
        # recompute gh + two adjoint matmuls per step; dx/dW_i adjoints
        flops += 3 * rec + 2 * inp + 2 * elem
    bytes_ = 4.0 * (b * t * din + din * 3 * h + 2 * b * t * 3 * h
                    + h * 3 * h + b * t * h)
    if backward:
        bytes_ *= 3
    return roofline.terms(flops=flops, bytes_accessed=bytes_,
                          collective_bytes=0.0, n_devices=1,
                          device_kind=roofline.TARGET_KIND)


def _gae_roofline(b, t, *, backward: bool):
    flops = 9.0 * b * t * (2.0 if backward else 1.0)
    bytes_ = 4.0 * 5 * b * t * (2.0 if backward else 1.0)
    return roofline.terms(flops=flops, bytes_accessed=bytes_,
                          collective_bytes=0.0, n_devices=1,
                          device_kind=roofline.TARGET_KIND)


# ---------------------------------------------------------------------------
# microbenchmarks
# ---------------------------------------------------------------------------
def bench_gru(fast: bool):
    from repro.kernels.gru import ops as gru_ops
    from repro.kernels.gru import ref as gru_ref
    from repro.nn import gru as gru_mod
    iters = 2 if fast else 10
    rows = []
    for label, b, t, din, h in swept_shapes(fast):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        params = gru_mod.gru_init(ks[0],
                                  gru_mod.GRUConfig(in_dim=din, hidden=h))
        xs = jax.random.normal(ks[1], (b, t, din), jnp.float32)
        resets = jax.random.bernoulli(ks[2], 0.1, (b, t)) \
            .astype(jnp.float32)

        def fwd(seq_fn):
            return jax.jit(lambda p, x: seq_fn(p, x)[0].sum())

        def fwdbwd(seq_fn):
            return jax.jit(jax.grad(lambda p, x: (seq_fn(p, x)[0] ** 2)
                                    .sum()))

        k_seq = lambda p, x: gru_ops.gru_sequence(p, x, reset_mask=resets)
        r_seq = lambda p, x: gru_ref.gru_sequence(p, x, reset_mask=resets)
        row = {"kernel": "gru", "label": label, "B": b, "T": t,
               "in": din, "H": h,
               "fwd_oracle_s": _time(fwd(r_seq), params, xs, iters=iters),
               "fwd_kernel_s": _time(fwd(k_seq), params, xs, iters=iters),
               "fwdbwd_oracle_s": _time(fwdbwd(r_seq), params, xs,
                                        iters=iters),
               "fwdbwd_kernel_s": _time(fwdbwd(k_seq), params, xs,
                                        iters=iters),
               "roofline_fwd": _gru_roofline(b, t, din, h, backward=False),
               "roofline_fwdbwd": _gru_roofline(b, t, din, h,
                                                backward=True)}
        row["speedup_fwd"] = row["fwd_oracle_s"] / row["fwd_kernel_s"]
        row["speedup_fwdbwd"] = (row["fwdbwd_oracle_s"]
                                 / row["fwdbwd_kernel_s"])
        rows.append(row)
    return rows


def bench_gae(fast: bool):
    from repro.kernels.gae import ops as gae_ops
    from repro.kernels.gae import ref as gae_ref
    iters = 2 if fast else 20
    rows = []
    # GAE only runs on the PPO recompute batch (n_envs × n_agents,
    # rollout_steps) — bench the '-policy' shapes (+ headline), not the
    # AIP-collect shapes it never sees
    shapes = [(lbl, b, t) for lbl, b, t, _, _ in swept_shapes(fast)
              if not lbl.endswith("-aip")]
    for label, b, t in shapes:
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        rw = jax.random.normal(ks[0], (b, t))
        vl = jax.random.normal(ks[1], (b, t))
        dn = jax.random.bernoulli(ks[2], 0.1, (b, t)).astype(jnp.float32)
        lv = jax.random.normal(ks[3], (b,))

        def fwd(gae_fn):
            return jax.jit(lambda r, v: gae_fn(r, v)[0].sum())

        def fwdbwd(gae_fn):
            return jax.jit(jax.grad(lambda r, v: (gae_fn(r, v)[0] ** 2)
                                    .sum(), argnums=(0, 1)))

        k_fn = lambda r, v: gae_ops.gae(r, v, dn, lv)
        r_fn = lambda r, v: gae_ref.gae(r, v, dn, lv)
        row = {"kernel": "gae", "label": label, "B": b, "T": t,
               "fwd_oracle_s": _time(fwd(r_fn), rw, vl, iters=iters),
               "fwd_kernel_s": _time(fwd(k_fn), rw, vl, iters=iters),
               "fwdbwd_oracle_s": _time(fwdbwd(r_fn), rw, vl, iters=iters),
               "fwdbwd_kernel_s": _time(fwdbwd(k_fn), rw, vl, iters=iters),
               "roofline_fwd": _gae_roofline(b, t, backward=False),
               "roofline_fwdbwd": _gae_roofline(b, t, backward=True)}
        row["speedup_fwd"] = row["fwd_oracle_s"] / row["fwd_kernel_s"]
        row["speedup_fwdbwd"] = (row["fwdbwd_oracle_s"]
                                 / row["fwdbwd_kernel_s"])
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# end-to-end A/B: the two inner-loop programs that own the hot spots
# ---------------------------------------------------------------------------
def bench_end_to_end(fast: bool):
    import dataclasses
    from repro.core import ials as ials_mod
    from repro.core import influence
    from repro.envs import registry
    from repro.marl import policy, ppo
    env_mod, env_cfg = registry.make("warehouse", side=2, horizon=32)
    info = env_cfg.info()
    rows = []

    # --- train_aip: GRU AIP, grads through the sequence scan
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    s, t = (8, 16) if fast else (32, 64)
    base_ac = influence.AIPConfig(
        in_dim=info.alsh_dim, n_sources=info.n_influence, kind="gru",
        hidden=(32,), gru_hidden=32, epochs=2 if fast else 10, batch=8)
    data = {"feats": jax.random.normal(ks[0], (s, t, info.alsh_dim)),
            "u": jax.random.bernoulli(
                ks[1], 0.4, (s, t, info.n_influence)).astype(jnp.float32),
            "resets": jax.random.bernoulli(
                ks[2], 0.1, (s, t)).astype(jnp.float32)}
    params = influence.aip_init(ks[3], base_ac)
    times = {}
    for mode in ("off", "on"):
        ac = dataclasses.replace(base_ac, use_kernels=mode)
        fn = jax.jit(lambda p, d, k, _ac=ac: influence.train_aip(
            p, d, k, _ac))
        times[mode] = _time(fn, params, data, jax.random.PRNGKey(3),
                            iters=1 if fast else 3)
    rows.append({"program": "train_aip", "label": f"warehouse-S{s}-T{t}",
                 "oracle_s": times["off"], "kernel_s": times["on"],
                 "speedup": times["off"] / times["on"]})

    # --- one IALS inner step: rollout + GAE + PPO (GRU policy)
    pc_base = policy.PolicyConfig(obs_dim=info.obs_dim,
                                  n_actions=info.n_actions, kind="gru",
                                  hidden=(32,), gru_hidden=16)
    n_envs, roll = (2, 8) if fast else (8, 16)
    times = {}
    for mode in ("off", "on"):
        pc = dataclasses.replace(pc_base, use_kernels=mode)
        ac = dataclasses.replace(base_ac, use_kernels=mode)
        ppo_cfg = ppo.PPOConfig(epochs=1, minibatches=2, use_kernels=mode)
        init_fn, train_fn = ials_mod.make_ials_trainer(
            env_mod, env_cfg, pc, ac, ppo_cfg, n_envs=n_envs,
            rollout_steps=roll)
        state = init_fn(jax.random.PRNGKey(4))
        aips = jax.vmap(lambda k: influence.aip_init(k, ac))(
            jax.random.split(jax.random.PRNGKey(5), info.n_agents))
        times[mode] = _time(lambda s_, a_: train_fn(s_, a_)[0]["params"],
                            state, aips, iters=1 if fast else 3)
    rows.append({"program": "ials_inner_step",
                 "label": f"warehouse-E{n_envs}-T{roll}",
                 "oracle_s": times["off"], "kernel_s": times["on"],
                 "speedup": times["off"] / times["on"]})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced shapes/iters (CI smoke)")
    args = ap.parse_args()

    from repro import compile_cache
    from repro.kernels import dispatch
    compile_cache.enable()
    decision = dispatch.resolve("on")
    record = {
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "roofline_device_kind": roofline.TARGET_KIND,
        "interpret": decision.interpret,
        "note": ("kernel columns ran under the Pallas interpreter "
                 "(non-TPU backend); oracle columns and roofline terms "
                 "are the meaningful numbers here"
                 if decision.interpret else
                 "compiled Pallas kernels"),
        "fast": bool(args.fast),
        "micro": bench_gru(args.fast) + bench_gae(args.fast),
        "end_to_end": bench_end_to_end(args.fast),
    }
    # schema gate before the artifact is written (same typed vocabulary
    # check_bench.py validates against)
    from repro.obs import metrics as obs_metrics
    problems = [p for r in record["micro"]
                for p in obs_metrics.validate_bench_row(
                    r, obs_metrics.KERNELS_MICRO_SCHEMA)]
    problems += [p for r in record["end_to_end"]
                 for p in obs_metrics.validate_bench_row(
                     r, obs_metrics.KERNELS_E2E_SCHEMA)]
    if problems:
        for p in problems:
            print(f"SCHEMA-INVALID {p}")
        raise SystemExit(f"{len(problems)} kernel bench rows violate "
                         f"the KERNELS_* schemas")
    with open(OUT_PATH, "w") as f:
        json.dump(record, f, indent=1, default=float)
    print("name,metric,value")
    for r in record["micro"]:
        for k in ("fwd_oracle_s", "fwd_kernel_s", "fwdbwd_oracle_s",
                  "fwdbwd_kernel_s", "speedup_fwd", "speedup_fwdbwd"):
            print(f"kernels.{r['kernel']}-{r['label']},{k},{r[k]}")
    for r in record["end_to_end"]:
        for k in ("oracle_s", "kernel_s", "speedup"):
            print(f"kernels.{r['program']}-{r['label']},{k},{r[k]}")
    print(f"wrote {OUT_PATH}")


if __name__ == "__main__":
    main()
