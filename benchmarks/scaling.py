"""DIALS scaling benchmark — shard count × scenario sweep.

Measures, for every (registered scenario, shard count) cell:

* wall-clock per outer Algorithm-1 round (post-compilation), with the
  GS collect on the critical path (``round_s``) AND overlapped with the
  inner steps (``round_s_async`` — ``DIALSConfig.async_collect``, the
  double-buffered collect of repro.distributed.async_collect) plus
  their ratio ``overlap_speedup``,
* inner agent-env steps/s (F · n_envs · rollout_steps · N per round),
* speedup of the fused sharded runtime over the unfused python-loop
  path (``shards=1`` — the F+3-syncs-per-round baseline),
* the GS decomposition A/B: one replicated Algorithm-2 collect
  (``collect_s``) vs the region-decomposed ``shard_map``'d collect of
  ``repro.core.gs_sharded`` on the same mesh
  (``collect_s_sharded_gs`` / ``gs_speedup``; null where the env's
  ``region_partition`` cannot tile the shard count, e.g. a 2×2 grid on
  8 shards),
* with ``--streams S1,S2,...``, the large-batch collect curve: the
  loop path at collect width S (``DIALSConfig.collect_streams`` — the
  ring-buffer datasets feeding the fused AIP round), one row per S with
  ``env_steps_per_s = S * collect_steps / collect_s`` from a dedicated
  post-compile collect timing.

The default grid includes the side-4 (16-agent) cells at shards 8/16
(powergrid-ring16 / supplychain-line16 — contiguous-ring topologies that
decompose at every divisor). On forced host devices the shard-scaling
numbers are overhead-dominated (one physical CPU); the fused-vs-unfused
and sharded-GS columns are still meaningful A/Bs of program structure.

Writes ``experiments/bench/BENCH_dials_scaling.json`` — the perf
trajectory artifact CI uploads — plus ``name,metric,value`` CSV lines on
stdout.

Shard counts > 1 need that many devices: on the CPU platform this script
forces ``<max shards>`` host devices (``jax_num_cpu_devices``, before the
backend starts); on a TPU it uses the chips there are and rejects a
shard count above ``len(jax.devices())``. Every row names the platform
it was measured on. Run it as its own process:

    PYTHONPATH=src python -m benchmarks.scaling [--fast]
        [--shards 1,2,4,8,16] [--scenarios traffic-2x2,powergrid-ring16]

``--processes P1,P2,...`` additionally sweeps real multi-process
execution: for each P > 1 the script re-launches itself as P coordinated
``jax.distributed`` CPU processes (repro.launch.variants.launch_group /
repro.distributed.bootstrap — each process forces max_shards/P host
devices, so the global device count matches the single-process run) and
merges the measured rows, labelled ``{scenario}-s{shards}-p{P}`` with a
``processes`` column, into the same artifact. The group children run
with ``JAX_PLATFORMS=cpu`` (their rows say platform ``cpu``): the parent
has already taken the chip for its own sweep. Shard counts that cannot
be balanced over P processes are skipped; the shards=1 unfused baseline
only exists at P=1.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

OUT_PATH = os.path.join("experiments", "bench", "BENCH_dials_scaling.json")


def _timed(fn, *args):
    import jax
    jax.block_until_ready(fn(*args))               # compile
    t0 = time.time()
    jax.block_until_ready(fn(*args))
    return time.time() - t0


def _make_collect_ab(env_mod, env_cfg, pc, *, n_envs, steps):
    """Per-scenario sharded-GS A/B: build + time the (shard-independent)
    replicated Algorithm-2 collect ONCE, return ``ab(shards)`` producing
    the per-cell columns — the region-decomposed collect re-times per
    mesh; the sharded columns are None where the env topology cannot
    tile that block count."""
    import jax
    from repro.core import gs as gs_mod, gs_sharded
    from repro.distributed import runtime
    from repro.marl import policy as policy_mod

    info = env_cfg.info()
    key = jax.random.PRNGKey(0)
    params = jax.vmap(lambda k: policy_mod.policy_init(k, pc))(
        jax.random.split(key, info.n_agents))
    rep = gs_mod.make_collector(env_mod, env_cfg, pc,
                                n_envs=n_envs, steps=steps)
    rep_s = _timed(rep, params, key)

    def ab(shards):
        out = {"collect_s": rep_s,
               "env_steps_per_s": n_envs * steps / rep_s,
               "collect_s_sharded_gs": None, "gs_speedup": None}
        ok, _why = gs_sharded.partition_supported(env_mod, env_cfg,
                                                  shards)
        if shards > 1 and ok:
            mesh = runtime.shard_mesh(shards)
            shc = gs_sharded.make_sharded_collector(
                env_mod, env_cfg, pc, n_envs=n_envs, steps=steps,
                mesh=mesh)
            sp = runtime.shard_agent_tree(params, mesh)
            out["collect_s_sharded_gs"] = _timed(shc, sp, key)
            out["gs_speedup"] = rep_s / out["collect_s_sharded_gs"]
        return out

    return ab


def _sweep(scenarios, shard_counts, *, rounds, inner, collect_steps,
           processes=1, telemetry_dir=None):
    # imported late: main() must set XLA_FLAGS first
    import jax
    from benchmarks.run import _setup
    from repro.core import dials
    from repro.launch import variants

    suffix = f"-p{processes}" if processes > 1 else ""
    platform = jax.devices()[0].platform
    rows = []
    for scenario in scenarios:
        env_name, side = variants.MARL_SCENARIOS[scenario]
        env_mod, env_cfg, info, pc, ac, ppo_cfg = _setup(env_name, side)
        n = info.n_agents
        collect_ab = _make_collect_ab(env_mod, env_cfg, pc, n_envs=4,
                                      steps=collect_steps)
        unfused_round_s = None
        for shards in shard_counts:
            if n % shards:
                print(f"# skip {scenario} shards={shards}: "
                      f"{n} agents not divisible")
                continue
            if shards % processes:
                print(f"# skip {scenario} shards={shards}: cannot "
                      f"balance over {processes} processes")
                continue
            # every cell runs twice: collect on the critical path
            # (async_collect=False) vs overlapped (True)
            steady_by_mode, total_by_mode = {}, {}
            for overlap in (False, True):
                # per-cell telemetry subdir: each (cell, mode) run gets
                # its own event log, so round indices stay monotone per
                # file and tools.telemetry_report --check passes per dir
                cell_tel = None
                if telemetry_dir:
                    cell_tel = os.path.join(
                        telemetry_dir,
                        f"{scenario}-s{shards}{suffix}-"
                        f"{'async' if overlap else 'sync'}")
                cfg = dials.DIALSConfig(
                    outer_rounds=rounds, aip_refresh=inner, collect_envs=4,
                    collect_steps=collect_steps, n_envs=8, rollout_steps=16,
                    eval_episodes=4, telemetry_dir=cell_tel,
                    **variants.dials_variant_for(shards, overlap))
                tr = dials.DIALSTrainer(env_mod, env_cfg, pc, ac,
                                        ppo_cfg, cfg)
                t0 = time.time()
                _, hist = tr.run(jax.random.PRNGKey(0))
                total_by_mode[overlap] = time.time() - t0
                # round 0 pays compilation (and async priming); measure
                # the steady-state rounds (with a single round, the
                # compile-inclusive time is all there is — still a valid
                # upper bound)
                steady_by_mode[overlap] = (
                    (hist[-1]["wall_s"] - hist[0]["wall_s"]) /
                    (len(hist) - 1)) if len(hist) > 1 \
                    else hist[0]["wall_s"]
            steady = steady_by_mode[False]
            inner_steps = cfg.aip_refresh * cfg.n_envs * \
                cfg.rollout_steps * n                  # F * E * T * N
            row = {"label": f"{scenario}-s{shards}{suffix}",
                   "platform": platform,
                   "scenario": scenario, "n_agents": n, "shards": shards,
                   "processes": processes, "streams": 4,
                   "fused": shards > 1,
                   "round_s": steady,
                   "round_s_async": steady_by_mode[True],
                   "overlap_speedup": steady / steady_by_mode[True],
                   "inner_steps_per_s": inner_steps / steady,
                   "inner_steps_per_s_async":
                       inner_steps / steady_by_mode[True],
                   "total_wall_s": total_by_mode[False],
                   "total_wall_s_async": total_by_mode[True],
                   **collect_ab(shards)}
            if shards == 1:
                unfused_round_s = steady
            if unfused_round_s is not None:
                row["speedup_vs_unfused"] = unfused_round_s / steady
            rows.append(row)
    return rows


def _stream_sweep(scenarios, streams_list, *, rounds, inner,
                  collect_steps, telemetry_dir=None):
    """Large-batch collect sweep: the loop (shards=1) path at stream
    widths S, first scenario only. Each cell runs the full DIALS round
    loop (ring-buffer collect feeding the fused AIP round) sync and
    async, plus a dedicated post-compile collect timing that gives the
    ``env_steps_per_s`` throughput curve the large-batch claim rests on
    (the in-loop collect span includes dispatch jitter; the dedicated
    timing is the apples-to-apples cell)."""
    import jax
    from benchmarks.run import _setup
    from repro.core import dials, gs as gs_mod
    from repro.launch import variants
    from repro.marl import policy as policy_mod

    scenario = scenarios[0]
    env_name, side = variants.MARL_SCENARIOS[scenario]
    env_mod, env_cfg, info, pc, ac, ppo_cfg = _setup(env_name, side)
    n = info.n_agents
    key = jax.random.PRNGKey(0)
    params = jax.vmap(lambda k: policy_mod.policy_init(k, pc))(
        jax.random.split(key, n))
    rows = []
    for streams in streams_list:
        coll = gs_mod.make_collector(env_mod, env_cfg, pc,
                                     n_envs=streams, steps=collect_steps)
        collect_s = _timed(coll, params, key)
        steady_by_mode, total_by_mode = {}, {}
        for overlap in (False, True):
            cell_tel = None
            if telemetry_dir:
                cell_tel = os.path.join(
                    telemetry_dir,
                    f"{scenario}-streams{streams}-"
                    f"{'async' if overlap else 'sync'}")
            cfg = dials.DIALSConfig(
                outer_rounds=rounds, aip_refresh=inner, collect_envs=4,
                collect_steps=collect_steps, n_envs=8, rollout_steps=16,
                eval_episodes=4, telemetry_dir=cell_tel,
                **variants.dials_variant_for(1, overlap,
                                             streams=streams))
            tr = dials.DIALSTrainer(env_mod, env_cfg, pc, ac,
                                    ppo_cfg, cfg)
            t0 = time.time()
            _, hist = tr.run(jax.random.PRNGKey(0))
            total_by_mode[overlap] = time.time() - t0
            steady_by_mode[overlap] = (
                (hist[-1]["wall_s"] - hist[0]["wall_s"]) /
                (len(hist) - 1)) if len(hist) > 1 \
                else hist[0]["wall_s"]
        steady = steady_by_mode[False]
        inner_steps = cfg.aip_refresh * cfg.n_envs * \
            cfg.rollout_steps * n
        rows.append({
            "label": f"{scenario}-streams{streams}",
            "platform": jax.devices()[0].platform,
            "scenario": scenario, "n_agents": n, "shards": 1,
            "processes": 1, "streams": streams, "fused": False,
            "round_s": steady,
            "round_s_async": steady_by_mode[True],
            "overlap_speedup": steady / steady_by_mode[True],
            "inner_steps_per_s": inner_steps / steady,
            "inner_steps_per_s_async":
                inner_steps / steady_by_mode[True],
            "total_wall_s": total_by_mode[False],
            "total_wall_s_async": total_by_mode[True],
            "collect_s": collect_s,
            "env_steps_per_s": streams * collect_steps / collect_s,
            "collect_s_sharded_gs": None, "gs_speedup": None,
        })
    return rows


def _spawn_group(args, processes, shard_counts, rows_path) -> None:
    """Re-launch this script as ``processes`` coordinated jax.distributed
    processes; rank 0 writes its rows to ``rows_path``."""
    from repro.launch import variants

    local = max(s for s in shard_counts if s % processes == 0) // processes
    argv = [sys.executable, "-m", "benchmarks.scaling",
            "--shards", args.shards, "--scenarios", args.scenarios,
            "--rows-out", rows_path]
    if args.rounds is not None:
        argv += ["--rounds", str(args.rounds)]
    if args.fast:
        argv.append("--fast")
    if args.telemetry_dir:
        # shared dir: every rank writes its own telemetry-p{rank}.jsonl
        argv += ["--telemetry-dir", args.telemetry_dir]
    # children must not inherit a forced device count from the parent's
    # own sweep: bootstrap sets their XLA_FLAGS from DIALS_LOCAL_DEVICES.
    # They run on host CPU devices: one process per chip, and the parent
    # may already hold it.
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    procs = variants.launch_group(argv, processes=processes,
                                  local_devices=local, env=env)
    rcs = [p.wait() for p in procs]
    if any(rcs):
        raise SystemExit(
            f"--processes {processes} group failed, exit codes {rcs}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: fewer rounds/steps")
    ap.add_argument("--shards", default="1,2,4,8,16",
                    help="comma-separated shard counts (1 = unfused "
                         "python-loop baseline); counts that do not "
                         "divide a scenario's agent count are skipped")
    ap.add_argument("--scenarios",
                    default="traffic-2x2,supplychain-line4,"
                            "powergrid-ring16,supplychain-line16",
                    help="comma-separated names from "
                         "launch.variants.MARL_SCENARIOS (the ring16/"
                         "line16 defaults are the side-4 16-agent cells "
                         "exercising shards 8/16)")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--streams", default=None,
                    help="comma-separated collect stream widths S — "
                         "sweeps the loop-path large-batch collect "
                         "(ring-buffer datasets, fused AIP round) on "
                         "the FIRST scenario, one row per S labelled "
                         "{scenario}-streams{S} with the "
                         "env_steps_per_s throughput column")
    ap.add_argument("--processes", default="1",
                    help="comma-separated process counts; each P > 1 "
                         "re-launches the sweep as P coordinated "
                         "jax.distributed CPU processes and merges the "
                         "rows (labelled -pP)")
    ap.add_argument("--telemetry-dir", default=None,
                    help="emit per-round typed telemetry (repro.obs) — "
                         "one subdirectory of JSONL event logs per "
                         "(cell, sync/async) run, merged to "
                         "telemetry.jsonl at the end; render/validate "
                         "with tools.telemetry_report")
    ap.add_argument("--profile-dir", default=None,
                    help="capture an XLA profiler trace of the "
                         "single-process sweep into this directory "
                         "(ignored for --processes > 1 groups)")
    ap.add_argument("--rows-out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    shard_counts = sorted({int(s) for s in args.shards.split(",")})
    scenarios = [s.strip() for s in args.scenarios.split(",") if s.strip()]
    rounds = args.rounds if args.rounds is not None else \
        (2 if args.fast else 4)
    if rounds < 1:
        ap.error("--rounds must be >= 1")
    inner = 4 if args.fast else 20
    collect_steps = 32 if args.fast else 64

    from repro.distributed import bootstrap
    group = bootstrap.config_from_env()
    if group is not None:
        # child mode: one rank of a --processes group. bootstrap (which
        # applies the forced device count and joins the coordination
        # service) must run before the sweep's jax import.
        ctx = bootstrap.bootstrap(group)
        from repro import compile_cache
        compile_cache.enable()
        rows = _sweep(scenarios, shard_counts, rounds=rounds, inner=inner,
                      collect_steps=collect_steps,
                      processes=ctx.num_processes,
                      telemetry_dir=args.telemetry_dir)
        if ctx.is_primary:
            if not args.rows_out:
                raise SystemExit("group child needs --rows-out")
            with open(args.rows_out, "w") as f:
                json.dump(rows, f, default=float)
        return

    process_counts = sorted({int(p) for p in args.processes.split(",")})
    from repro import compile_cache
    compile_cache.enable()
    rows = []
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    for processes in process_counts:
        if processes <= 1:
            # in-process sweep; multiple shards need multiple devices —
            # host CPU devices are forced before the backend starts (the
            # setting only sizes the CPU platform), chips are what exist
            import jax
            n_dev = max(shard_counts)
            if n_dev > 1:
                jax.config.update("jax_num_cpu_devices", n_dev)
            if n_dev > len(jax.devices()):
                raise SystemExit(
                    f"--shards up to {n_dev} needs {n_dev} devices; "
                    f"{jax.devices()[0].platform} has "
                    f"{len(jax.devices())}")
            from repro.obs import trace as obs_trace
            with obs_trace.profile(args.profile_dir):
                rows.extend(_sweep(scenarios, shard_counts, rounds=rounds,
                                   inner=inner,
                                   collect_steps=collect_steps,
                                   telemetry_dir=args.telemetry_dir))
                if args.streams:
                    streams_list = sorted(
                        {int(s) for s in args.streams.split(",")})
                    rows.extend(_stream_sweep(
                        scenarios, streams_list, rounds=rounds,
                        inner=inner, collect_steps=collect_steps,
                        telemetry_dir=args.telemetry_dir))
            continue
        if all(s % processes for s in shard_counts):
            print(f"# skip processes={processes}: no shard count "
                  f"balances over it")
            continue
        rows_path = os.path.join(os.path.dirname(OUT_PATH),
                                 f".rows-p{processes}.json")
        _spawn_group(args, processes, shard_counts, rows_path)
        with open(rows_path) as f:
            rows.extend(json.load(f))
        os.remove(rows_path)

    # schema gate before the artifact is written: every row must be a
    # valid typed scaling record (repro.obs.metrics.SCALING_ROW_SCHEMA) —
    # check_bench and live telemetry then share one vocabulary
    from repro.obs import metrics as obs_metrics
    problems = [p for r in rows
                for p in obs_metrics.validate_bench_row(
                    r, obs_metrics.SCALING_ROW_SCHEMA)]
    if problems:
        for p in problems:
            print(f"SCHEMA-INVALID {p}", file=sys.stderr)
        raise SystemExit(f"{len(problems)} scaling rows violate "
                         f"SCALING_ROW_SCHEMA")

    with open(OUT_PATH, "w") as f:
        json.dump(rows, f, indent=1, default=float)
    print("name,metric,value")
    for r in rows:
        for k, v in r.items():
            if k not in ("label", "scenario"):
                print(f"dials_scaling.{r['label']},{k},{v}")
    print(f"# wrote {OUT_PATH}")

    if args.telemetry_dir:
        # merge every cell's per-process logs into a telemetry.jsonl so
        # the uploaded artifact is readable without this package
        from repro.obs import sinks as obs_sinks
        merged = 0
        for root, _dirs, files in sorted(os.walk(args.telemetry_dir)):
            if any(f.startswith("telemetry-p") and f.endswith(".jsonl")
                   for f in files):
                obs_sinks.merge_dir(root)
                merged += 1
        print(f"# merged telemetry in {merged} cell dir(s) under "
              f"{args.telemetry_dir}")


if __name__ == "__main__":
    main()
