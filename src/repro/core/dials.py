"""Algorithm 1 — MARL with Distributed Influence-Augmented Local Simulators.

The orchestrator alternates:
  1. collect per-agent (ALSH, u) datasets from the GS under the current
     joint policy (Algorithm 2; ``repro.core.gs``),
  2. train all AIPs in parallel — one vmapped update (Section 3.2),
  3. run F inner steps of IALS rollouts + PPO for every agent in parallel
     (Algorithm 3; ``repro.core.ials``) with the AIPs FROZEN,
until the step budget is exhausted. ``F`` (``aip_refresh``) is the paper's
central hyperparameter: infrequent refresh keeps each agent's local
dynamics stationary (Section 4.3), and Lemma 2/Theorem 1 bound the cost of
the staleness.

Production hooks: periodic GS evaluation, checkpoint/restart via
``CheckpointManager``, the ``untrained`` ablation (the paper's
untrained-DIALS baseline), and **bounded staleness made real**:

* ``async_collect=True`` overlaps round k+1's GS collect with round k's
  F inner steps (``repro.distributed.async_collect`` — double-buffered
  dataset slots, spare-device or host-thread dispatch). The dataset
  consumed each round carries its collection-round tag in the round
  record (``data_round``); the steady-state lag is exactly one round,
  the staleness Lemma 2 licenses.
* ``max_aip_staleness`` is enforced, not decorative: a dataset older
  than the bound triggers a blocking force-sync collect
  (``forced_sync`` in the record), and an agent whose predictor would
  fall further behind than the bound — e.g. a straggler that keeps
  missing its refresh — is force-refreshed through
  ``repro.distributed.fault.freshness_gate`` (``stale_forced``).
  ``async_collect=True, max_aip_staleness=0`` degenerates to the serial
  schedule, which is how the equivalence tests pin the semantics.

Checkpoint-resume under ``async_collect``: the in-flight dataset is not
checkpointed, but its round tag is (``extra["async_round"]``, along
with the per-agent ``reports`` vector), so a resumed run *re-primes*
the double buffer — it re-collects that dataset from the prior round's
checkpointed params under the prior round's collect key and resumes on
the exact staleness schedule of the uninterrupted run (bitwise on the
loop path; see ``_reprime_collector``). Only when the needed prior step
has been rotated away does the resume fall back to a force-sync collect
(``forced_sync=True`` — fresher data, the safe direction under
Lemma 2).

Fault tolerance: ``run(..., chaos=FaultSchedule)`` threads the
deterministic fault injector through the round loop, the checkpoint
writer, and the heartbeat monitor; on a mesh spanning processes the
sharded path checkpoints through
``checkpoint.distributed.DistributedCheckpointManager`` (per-process
agent slices, two-phase rank-0 commit), and a ``heartbeats`` callback
that raises ``recovery.HostLossDetected`` hands the loss to the
re-bootstrap supervisor (``distributed.recovery``) instead of the
in-group elastic path.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.core import gs as gs_mod
from repro.core import ials as ials_mod
from repro.core import influence
from repro.distributed import async_collect as async_mod
from repro.distributed import fault
from repro.marl import policy as policy_mod
from repro.marl import ppo as ppo_mod
from repro.marl import runner as runner_mod
from repro.obs import metrics as obs_metrics
from repro.obs.trace import SYNC


@dataclasses.dataclass(frozen=True)
class DIALSConfig:
    aip_refresh: int = 50          # F, in inner train iterations
    outer_rounds: int = 4
    collect_envs: int = 8
    collect_steps: int = 128       # per env -> dataset size = envs*steps
    collect_holdout: int = 1       # env streams per agent held out of AIP
    #                                training; eval_ce runs on these (the
    #                                paper's held-out Fig.-4 CE). 0 = legacy
    #                                train-set CE (forced when collect_envs=1)
    untrained: bool = False        # paper's untrained-DIALS ablation
    eval_episodes: int = 8
    n_envs: int = 16
    rollout_steps: int = 16
    # The large-batch S knobs (repro.core.env_pool): stream counts for
    # the GS collect pool and the per-agent IALS pool. None defers to
    # the legacy collect_envs / n_envs values; setting them makes S a
    # pure width axis — per-stream fold-in keys mean a wider run
    # contains every narrower run's streams bitwise, and the donated
    # ring buffers + chunked AIP training keep peak memory ~one dataset
    # no matter how large S grows.
    collect_streams: Optional[int] = None
    ials_streams: Optional[int] = None
    max_aip_staleness: int = 2     # rounds; straggler/async-lag tolerance
    async_collect: bool = False    # overlap round k+1's GS collect with
    #                                round k's inner steps (one-round
    #                                dataset lag, bounded by
    #                                max_aip_staleness)
    ckpt_dir: Optional[str] = None
    ckpt_keep: int = 3
    # agent-sharded runtime (repro.core.dials_sharded): None = auto
    # (sharded whenever >1 device is visible), <=1 = force the
    # single-device path, N = force an N-shard ("shards",) mesh.
    shards: Optional[int] = None
    # Region-decomposed GS (repro.core.gs_sharded): run Algorithm 2 and
    # the periodic GS eval as shard_map'd block programs with halo
    # exchange instead of replicated joint rollouts. "auto" uses it
    # whenever the env's region_partition supports the mesh's block
    # count (and falls back to the replicated GS otherwise, e.g. a 2x2
    # grid on 4 shards); "on" requires it (raises when the topology
    # cannot tile); "off" keeps the replicated GS. Loop-path runs
    # (shards<=1 without a mesh) always use the replicated GS.
    sharded_gs: str = "auto"
    # Pallas fast paths for the inner-loop hot spots (AIP GRU, policy
    # GRU, GAE). "auto" defers to the sub-configs (which themselves
    # default to auto = kernel on TPU, oracle elsewhere); an explicit
    # "on"/"off" here overrides all three (repro.kernels.dispatch).
    use_kernels: str = "auto"
    # Runtime observability (repro.obs): a shared directory for
    # per-process JSONL event logs (typed round records, collect/fault
    # events). None = disabled — no files, no overhead, and (on the
    # sharded path) provably no change to the traced round program.
    telemetry_dir: Optional[str] = None
    # Fence host spans with block_until_ready for honest device timings
    # (loop path only — the sharded round is one fused program). Off by
    # default: fencing adds host syncs the drivers otherwise avoid.
    telemetry_fence: bool = False


def apply_kernel_mode(policy_cfg, aip_cfg, ppo_cfg, mode: str):
    """Propagate a driver-level ``use_kernels`` onto the three
    sub-configs that own a hot spot. Idempotent; "auto" is a no-op."""
    from repro.kernels import dispatch
    return (dispatch.override_mode(policy_cfg, mode),
            dispatch.override_mode(aip_cfg, mode),
            dispatch.override_mode(ppo_cfg, mode))


def collect_stream_count(cfg: DIALSConfig) -> int:
    """S for the GS collect pool: ``collect_streams``, defaulting to the
    legacy ``collect_envs``."""
    return (cfg.collect_streams if cfg.collect_streams is not None
            else cfg.collect_envs)


def ials_stream_count(cfg: DIALSConfig) -> int:
    """E for each agent's IALS pool: ``ials_streams``, defaulting to the
    legacy ``n_envs``."""
    return cfg.ials_streams if cfg.ials_streams is not None else cfg.n_envs


def holdout_sequences(cfg: DIALSConfig) -> int:
    """How many collected env streams per agent are held out for the
    held-out CE metric: ``collect_holdout`` clamped so at least one
    sequence always remains for AIP training."""
    return max(0, min(cfg.collect_holdout, collect_stream_count(cfg) - 1))


class DIALSTrainer:
    """Python-level orchestrator; every inner piece is a jitted program."""

    def __init__(self, env_mod, env_cfg, policy_cfg: policy_mod.PolicyConfig,
                 aip_cfg: influence.AIPConfig, ppo_cfg: ppo_mod.PPOConfig,
                 cfg: DIALSConfig):
        self.env_mod, self.env_cfg = env_mod, env_cfg
        if cfg.sharded_gs not in ("auto", "on", "off"):
            raise ValueError(
                f"sharded_gs must be auto|on|off, got {cfg.sharded_gs!r}")
        policy_cfg, aip_cfg, ppo_cfg = apply_kernel_mode(
            policy_cfg, aip_cfg, ppo_cfg, cfg.use_kernels)
        self.policy_cfg, self.aip_cfg = policy_cfg, aip_cfg
        self.ppo_cfg, self.cfg = ppo_cfg, cfg
        self.info = env_cfg.info()
        self.n_eval_seqs = holdout_sequences(cfg)

        self.collect = gs_mod.make_collector(
            env_mod, env_cfg, policy_cfg,
            n_envs=collect_stream_count(cfg), steps=cfg.collect_steps)
        # the donating twin + ring: steady-state collects write into the
        # retired slot's buffers — the wide dataset never reallocates or
        # visits the host on the loop path
        self.collect_into = gs_mod.make_collector_into(
            env_mod, env_cfg, policy_cfg,
            n_envs=collect_stream_count(cfg), steps=cfg.collect_steps)
        self._ring = async_mod.DeviceRing(self.collect, self.collect_into)
        self.ials_init, self.ials_train = ials_mod.make_ials_trainer(
            env_mod, env_cfg, policy_cfg, aip_cfg, ppo_cfg,
            n_envs=ials_stream_count(cfg), rollout_steps=cfg.rollout_steps)
        _, _, self.gs_eval = runner_mod.make_gs_trainer(
            env_mod, env_cfg, policy_cfg, ppo_cfg,
            runner_mod.RunConfig(n_envs=cfg.n_envs,
                                 rollout_steps=cfg.rollout_steps))
        self.train_aips = jax.jit(jax.vmap(
            lambda p, d, k: influence.train_aip(p, d, k, aip_cfg)))
        self.eval_aips = jax.jit(jax.vmap(
            lambda p, d: influence.eval_ce(p, d, aip_cfg)))
        self.aip_round = self._make_aip_round()
        self.manager = (CheckpointManager(cfg.ckpt_dir, keep=cfg.ckpt_keep)
                        if cfg.ckpt_dir else None)
        self._sharded = None       # lazily-built ShardedDIALSRunner
        self._dist_manager = None  # lazily-built DistributedCheckpointManager
        self._resume_extra = {}    # checkpoint extra of the restored step

    # -- the fused AIP round -------------------------------------------------
    def _make_aip_round(self):
        """Holdout split + held-out CE + vmapped AIP training + the
        bounded-staleness gate as ONE jitted program — the loop-path
        mirror of the sharded runner's shard body. Fusing it matters at
        large S: ``split_dataset``'s train/eval slices become in-program
        views of the ring slot instead of materialized device copies,
        and ``train_aip``'s minibatching / ``eval_ce``'s ``eval_chunk``
        already bound the per-step working set, so peak memory stays
        ~one dataset regardless of the stream count."""
        cfg, aip_cfg = self.cfg, self.aip_cfg
        n_eval = self.n_eval_seqs
        train_aips = jax.vmap(
            lambda p, d, k: influence.train_aip(p, d, k, aip_cfg))
        eval_aips = jax.vmap(lambda p, d: influence.eval_ce(p, d, aip_cfg))

        def aip_round(aips, data, aip_keys, fresh_mask, reports, rnd,
                      data_round):
            train_data, eval_data = gs_mod.split_dataset(data, n_eval)
            ce_before = eval_aips(aips, eval_data)
            forced = jnp.zeros_like(fresh_mask)
            if not cfg.untrained:
                new_aips, _ = train_aips(aips, train_data, aip_keys)
                eff, reports, forced = fault.freshness_gate(
                    fresh_mask, reports, data_round, rnd,
                    cfg.max_aip_staleness)
                aips = fault.masked_tree_update(aips, new_aips, eff)
            ce_after = eval_aips(aips, eval_data)
            return aips, reports, ce_before, ce_after, forced

        return jax.jit(aip_round)

    # -- state --------------------------------------------------------------
    def init(self, key):
        k1, k2 = jax.random.split(key)
        state = self.ials_init(k1)
        aip_params = jax.vmap(
            lambda k: influence.aip_init(k, self.aip_cfg))(
            jax.random.split(k2, self.info.n_agents))
        return {"ials": state, "aips": aip_params,
                "round": 0, "key": key}

    def _state_struct(self, state):
        return jax.tree.map(
            lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                       if hasattr(x, "shape") else x), state)

    def restore_or_init(self, key):
        state = self.init(key)
        self._resume_extra = {}
        if self.manager is not None:
            tree, step = self.manager.restore_latest(
                self._state_struct(state))
            if tree is not None:
                self._resume_extra = dict(self.manager.last_extra)
                tree["round"] = int(step)
                # the base key drives the per-round fold-in stream; a
                # resumed run must continue it exactly
                tree["key"] = jnp.asarray(tree["key"], state["key"].dtype)
                return tree
        return state

    # -- path selection ------------------------------------------------------
    def _select_shards(self) -> int:
        """Shard count for the sharded runtime; 0 = single-device path."""
        from repro.distributed import runtime as runtime_lib
        cfg, n_agents = self.cfg, self.info.n_agents
        n_dev = len(jax.devices())
        if cfg.shards is not None:
            if cfg.shards <= 1:
                return 0
            if cfg.shards > n_dev:
                raise ValueError(
                    f"shards={cfg.shards} but only {n_dev} devices")
            if n_agents % cfg.shards:
                raise ValueError(
                    f"{n_agents} agents cannot tile {cfg.shards} shards")
            return cfg.shards
        if n_dev <= 1:
            return 0
        s = runtime_lib.choose_shards(n_agents, n_dev)
        return s if s > 1 else 0

    # -- key plumbing --------------------------------------------------------
    def _collect_key(self, base_key, rnd: int):
        """The round-``rnd`` collect key of the per-round fold-in stream —
        the same derivation the serial path (and the fused sharded round
        program) performs, so async and serial runs draw identical
        collect randomness for any given round."""
        return jax.random.split(jax.random.fold_in(base_key, rnd), 3)[0]

    # -- checkpoint-resume plumbing ------------------------------------------
    def _ckpt_extra(self, collector, reports) -> dict:
        """What a checkpoint must carry beyond the state tree for an
        exact resume: the in-flight async collect's round tag and the
        per-agent data-report rounds (staleness bookkeeping), already
        read to the host."""
        return {"async_round": (collector.pending_round
                                if collector is not None else None),
                "reports": reports.tolist()}

    def _restored_reports(self, state):
        """The resumed ``reports`` vector: the checkpointed one when
        present, else the legacy treat-AIPs-as-fresh default."""
        saved = self._resume_extra.get("reports")
        if saved is not None and len(saved) == self.info.n_agents:
            return jnp.asarray(saved, jnp.int32)
        return jnp.full((self.info.n_agents,), state["round"] - 1,
                        jnp.int32)

    def _params_at_round(self, p: int, state):
        """The joint policy params as of the TOP of round ``p`` — what
        the original run submitted its tag-``p`` collect with: the
        step-``p`` checkpoint (end of round p-1), or the deterministic
        init for p == 0. None when step ``p`` was rotated away."""
        if p <= 0:
            return self.init(state["key"])["ials"]["params"]
        tree, step = self.manager.restore_step(p, self._state_struct(state))
        return None if tree is None else tree["ials"]["params"]

    def _reprime_collector(self, collector, state, *, runner=None) -> bool:
        """Exact async resume: re-submit the interrupted run's in-flight
        collect — same params (from the prior checkpoint), same key,
        same round tag — so the resumed staleness schedule is identical
        to the uninterrupted one. False → caller falls back to the
        force-sync prime (fresher data, Lemma-2-safe)."""
        p = self._resume_extra.get("async_round")
        if p is None:
            return False
        params = self._params_at_round(int(p), state)
        if params is None:
            return False
        if runner is not None:
            from repro.distributed import runtime as runtime_lib
            params = runtime_lib.shard_agent_tree(params, runner.mesh)
        collector.submit(params, self._collect_key(state["key"], int(p) + 1),
                         int(p))
        return True

    def _sharded_manager(self, telemetry=obs.DISABLED):
        """The sharded path's checkpoint manager: the distributed
        per-process-slice layout with a two-phase rank-0 commit — the
        same format on one process or many, so checkpoints move freely
        across process/shard counts (elastic restarts, post-loss
        re-bootstrap)."""
        from repro.checkpoint.distributed import DistributedCheckpointManager
        if self._dist_manager is None:
            self._dist_manager = DistributedCheckpointManager(
                self.cfg.ckpt_dir, keep=self.cfg.ckpt_keep,
                process_id=jax.process_index())
        self._dist_manager.telemetry = telemetry
        return self._dist_manager

    def _make_collector_executor(self, telemetry=obs.DISABLED):
        """Loop-path executor: a host worker thread driving the ring's
        collect — every dataset still lands in a donated device slot
        (the ring's obtain-before-submit ordering makes the worker-thread
        calls safe: obtain() harvests the in-flight future before any
        force-sync submits another). Placement is deliberately left
        untouched: committing the dataset to a spare device would drag
        every downstream jit (AIP train, inner steps) into recompiles
        and cross-device transfers. The sharded driver is the one that
        collects on a spare device — it re-places the dataset onto the
        mesh explicitly."""
        return async_mod.AsyncCollector(self._ring.collect, mode="thread",
                                        telemetry=telemetry)

    # -- Algorithm 1 --------------------------------------------------------
    def run(self, key, *, log: Optional[Callable] = None,
            straggler_mask: Optional[Callable] = None,
            heartbeats: Optional[Callable] = None,
            chaos=None):
        """Runs ``outer_rounds`` rounds of (collect → AIP train → F inner
        steps). Returns (state, history). ``straggler_mask(round) ->
        (N,) {0,1}`` simulates late shards (bounded-staleness refresh,
        force-refreshed past ``max_aip_staleness``).

        ``heartbeats(round) -> iterable of dead host (process) ids``
        turns host loss survivable: called at the top of every round
        (typically ``fault.HostMonitor.gate``), and when it reports a
        host dead, that host's agent blocks are reassigned to the
        surviving shards on a shrunken mesh and training continues —
        the round record carries ``n_shards``/``reassigned``/
        ``dead_hosts``. Requires the sharded path. Detection is at
        round granularity: a host that dies *inside* a round program
        stalls that program's collectives — the monitor converts silence
        *between* rounds into a plan.

        ``chaos`` (a ``distributed.chaos.FaultSchedule``) injects the
        deterministic fault schedule: round-boundary host kills /
        interrupts via its ``round_start`` hook, checkpoint-writer
        faults via ``CheckpointManager.hooks``.

        Dispatches to the agent-sharded fused runtime whenever more than
        one device is visible (or ``cfg.shards`` forces a mesh); both
        paths compute the same numbers — the sharded one in a single
        program per round instead of ``F + 3``.
        """
        cfg = self.cfg
        state = self.restore_or_init(key)
        n_shards = self._select_shards()
        if n_shards:
            return self._run_sharded(state, n_shards, log=log,
                                     straggler_mask=straggler_mask,
                                     heartbeats=heartbeats, chaos=chaos)
        if heartbeats is not None:
            raise ValueError(
                "heartbeats= (elastic host-loss handling) requires the "
                "sharded runtime — the single-device loop path has no "
                "mesh to shrink")
        if cfg.sharded_gs == "on":
            # honor the forced mode instead of silently benchmarking the
            # replicated GS: the region-decomposed GS is a mesh program
            raise ValueError(
                "sharded_gs='on' requires the sharded runtime (more than "
                "one device, or DIALSConfig.shards > 1); the "
                "single-device loop path always uses the replicated GS")
        n = self.info.n_agents
        tel = obs.maybe(cfg.telemetry_dir, fence=cfg.telemetry_fence)
        tr = tel.tracer
        kernels = obs_metrics.kernel_summary(self.policy_cfg, self.aip_cfg,
                                             self.ppo_cfg)
        collector = (self._make_collector_executor(tel)
                     if cfg.async_collect else None)
        if chaos is not None and self.manager is not None:
            self.manager.hooks = chaos.checkpoint_phase
        # collection round of each agent's newest trained-on dataset —
        # checkpointed (extra["reports"]) so resume keeps the schedule
        reports = self._restored_reports(state)
        if collector is not None and state["round"] > 0 \
                and cfg.max_aip_staleness > 0:
            # re-prime the interrupted in-flight collect; on failure the
            # first obtain() below force-syncs (the legacy resume)
            self._reprime_collector(collector, state)
        history = []
        t_start = time.time()
        tel.emit("run_start", path="loop", env=self.info.name,
                 n_shards=1, start_round=state["round"],
                 outer_rounds=cfg.outer_rounds,
                 async_collect=cfg.async_collect, kernels=kernels)
        try:
            for rnd in range(state["round"], cfg.outer_rounds):
                if chaos is not None:
                    chaos.round_start(rnd)
                tr.reset()
                t_round = time.perf_counter()
                with tr.span("dials.round"):
                    key = jax.random.fold_in(state["key"], rnd)
                    kc, kt, ke = jax.random.split(key, 3)

                    # (1) Algorithm 2: datasets from the GS. Async: consume
                    # the double buffer (freshness-gated; round 0 primes with
                    # a blocking collect) and launch the NEXT round's collect
                    # under THIS round's entry policy — it overlaps the F
                    # inner steps below and is consumed one round later.
                    with tr.span("dials.collect"):
                        if collector is not None:
                            with tr.span(SYNC + "obtain"):
                                tagged, forced_sync = collector.obtain(
                                    rnd, state["ials"]["params"], kc,
                                    max_staleness=cfg.max_aip_staleness)
                            # pipeline the next round's collect — unless the
                            # bound forbids any lag (a tag-rnd dataset could
                            # never be consumed at rnd+1, so don't collect it)
                            if (rnd + 1 < cfg.outer_rounds and collector.idle()
                                    and cfg.max_aip_staleness > 0):
                                collector.submit(
                                    state["ials"]["params"],
                                    self._collect_key(state["key"], rnd + 1),
                                    rnd)
                            data, data_round = tagged.data, tagged.round
                        else:
                            data = self._ring.collect(state["ials"]["params"],
                                                      kc)
                            data_round, forced_sync = rnd, False
                        tr.fence(data)

                    # (2) fused AIP round: holdout split + held-out CE + AIP
                    # training + bounded-staleness gate, one jitted program
                    # reading the ring slot in place (training is skipped for
                    # untrained-DIALS — a static branch of the program)
                    with tr.span("dials.aip_train"):
                        mask = (jnp.asarray(straggler_mask(rnd), jnp.float32)
                                if straggler_mask is not None
                                else jnp.ones((n,), jnp.float32))
                        (state["aips"], reports, ce_before, ce_after,
                         forced) = self.aip_round(
                            state["aips"], data, jax.random.split(kt, n),
                            mask, reports, rnd, data_round)
                        stale_forced = tr.pull("stale_forced", forced.sum(),
                                               int)
                        tr.fence((ce_before, ce_after))

                    # (3) F inner IALS+PPO steps, AIPs frozen
                    with tr.span("dials.inner_steps"):
                        metrics = None
                        for _ in range(cfg.aip_refresh):
                            state["ials"], metrics = self.ials_train(
                                state["ials"], state["aips"])
                        tr.fence(state["ials"])

                    with tr.span("dials.gs_eval"):
                        ret = tr.fence(self.gs_eval(
                            state["ials"]["params"], ke,
                            episodes=cfg.eval_episodes))

                    # the round's reads, one dials.sync span each, in the
                    # order the record lists them
                    with tr.span("dials.record"):
                        stats = obs_metrics.staleness_stats(reports, rnd)
                        ce_before = ce_before.mean()
                        ce_after = ce_after.mean()
                        gs_return = tr.pull("gs_return", ret)
                        ials_reward = (
                            None if metrics is None
                            else tr.pull("ials_reward", metrics["reward"]))
                        ce_before = tr.pull("aip_ce_before", ce_before)
                        ce_after = tr.pull("aip_ce_after", ce_after)
                        lag_min = tr.pull("staleness_min",
                                          stats["staleness_min"], int)
                        lag_mean = tr.pull("staleness_mean",
                                           stats["staleness_mean"])
                        lag_max = tr.pull("staleness_max",
                                          stats["staleness_max"], int)
                        round_s = time.perf_counter() - t_round
                        phases = tr.phase_seconds()
                        # collect throughput (sync path only — the async span
                        # measures obtain wait, not simulator time)
                        collect_span = phases.get("dials.collect")
                        env_steps = (collect_stream_count(cfg)
                                     * cfg.collect_steps)
                        env_rate = (env_steps / collect_span
                                    if collector is None and collect_span
                                    else None)
                        rec = obs_metrics.round_record(
                            round=rnd,
                            gs_return=gs_return,
                            ials_reward=ials_reward,
                            aip_ce_before=ce_before,
                            aip_ce_after=ce_after,
                            data_round=data_round,
                            forced_sync=forced_sync,
                            stale_forced=stale_forced,
                            staleness_min=lag_min,
                            staleness_mean=lag_mean,
                            staleness_max=lag_max,
                            n_shards=1,
                            reassigned=0,
                            dead_hosts=[],
                            kernels=kernels,
                            collect_s=collect_span,
                            env_steps_per_s=env_rate,
                            aip_s=phases.get("dials.aip_train"),
                            inner_s=phases.get("dials.inner_steps"),
                            eval_s=phases.get("dials.gs_eval"),
                            mirror_s=None,
                            sync_s=tr.sync_seconds(),
                            round_s=round_s,
                            wall_s=time.time() - t_start)
                tel.emit_round(rec)
                history.append(rec)
                if log:
                    log(rec)
                state["round"] = rnd + 1
                if self.manager is not None:
                    self.manager.save(rnd + 1, state, extra=self._ckpt_extra(
                        collector, tr.pull("reports", reports,
                                           jax.device_get)))
        finally:
            if collector is not None:
                collector.close()
            tel.emit("run_end", rounds=len(history))
            tel.close()
        if self.manager is not None:
            self.manager.wait()
        return state, history

    # -- sharded path --------------------------------------------------------
    def _sharded_runner(self, n_shards: int):
        from repro.core import dials_sharded
        if self._sharded is None or self._sharded.n_shards != n_shards:
            self._sharded = dials_sharded.ShardedDIALSRunner(
                self.env_mod, self.env_cfg, self.policy_cfg, self.aip_cfg,
                self.ppo_cfg, self.cfg, n_shards=n_shards)
        return self._sharded

    def _make_sharded_collector(self, runner, telemetry=obs.DISABLED):
        """Async double-buffer for the sharded path — dispatch mode only:
        a host thread could race the donation. The region-decomposed
        collect is a mesh program — it runs on the shard devices
        themselves, so it is dispatched directly, without the
        spare-device input copy (JAX async dispatch still enqueues it
        ahead of the train program). ``spare_device`` is None on a
        multi-process mesh (runtime.spare_device owns that guard)."""
        from repro.distributed import runtime as runtime_lib
        return async_mod.AsyncCollector(
            runner.collect, mode="dispatch",
            spare_device=(None if runner.use_sharded_gs else
                          runtime_lib.spare_device(runner.n_shards)),
            telemetry=telemetry)

    def _reassign(self, runner, carry, mirror, collector, dead_hosts,
                  telemetry=obs.DISABLED):
        """Elastic shard reassignment after host loss.

        The dead hosts' shard slots are dropped, ``fault.elastic_plan``
        re-tiles the agent axis over the survivors, a new runner is
        built on the shrunken mesh, and the carry is re-placed from the
        host ``mirror`` (the end-of-previous-round snapshot every host
        holds — the on-mesh carry references the dead process's buffers
        and is unusable). Any in-flight async collect belongs to the
        dead mesh and is discarded; the next ``obtain`` force-syncs.
        Returns ``(runner, carry, collector, n_reassigned_blocks)``."""
        from repro.core import dials_sharded
        from repro.distributed import runtime as runtime_lib
        dead_shards = runtime_lib.shards_on_hosts(runner.mesh, dead_hosts)
        if not dead_shards:
            return runner, carry, collector, 0
        plan = fault.elastic_plan(
            self.info.n_agents, runner.n_shards, dead_shards,
            telemetry=telemetry if telemetry.enabled else None)
        survivors = runtime_lib.surviving_devices(runner.mesh, dead_hosts)
        new_mesh = runtime_lib.shard_mesh(plan.new_shards,
                                          devices=survivors)
        runner = dials_sharded.ShardedDIALSRunner(
            self.env_mod, self.env_cfg, self.policy_cfg, self.aip_cfg,
            self.ppo_cfg, self.cfg, mesh=new_mesh)
        self._sharded = runner
        carry = fault.reshard_agents(mirror, new_mesh)
        if collector is not None:
            collector.close()
            collector = self._make_sharded_collector(runner, telemetry)
        return runner, carry, collector, len(dead_shards)

    def _run_sharded(self, state, n_shards: int, *, log, straggler_mask,
                     heartbeats=None, chaos=None):
        """The same round loop over the mesh. Sync: one fused donated
        program per round. Async: the round is split into a collect
        program and a shard-train program — round k+1's collect is
        dispatched (onto a spare device when one exists) BEFORE round k's
        shard-train program, so it runs while the shard_map section does.
        Dispatch order also makes this donation-safe: the collect is
        enqueued with the pre-donation parameter buffers.

        With ``heartbeats`` set the run is *elastic*: every round ends
        by refreshing a host-side mirror of the carry (an all-gather on
        a multi-process mesh — the availability tax), and a lapsed
        heartbeat at the top of a round triggers ``_reassign`` before
        training continues on the shrunken mesh."""
        from repro.distributed import runtime as runtime_lib
        cfg = self.cfg
        runner = self._sharded_runner(n_shards)
        n = self.info.n_agents
        base_key = state["key"]
        carry = runner.shard_carry(
            {"aips": state["aips"], "ials": state["ials"],
             "reports": self._restored_reports(state)})
        tel = obs.maybe(cfg.telemetry_dir, fence=cfg.telemetry_fence)
        tr = tel.tracer
        kernels = obs_metrics.kernel_summary(self.policy_cfg, self.aip_cfg,
                                             self.ppo_cfg)
        # the distributed per-slice manager works on any process count —
        # each process writes only its local agent rows, rank 0 commits
        mgr = (self._sharded_manager(tel)
               if self.manager is not None else None)
        if chaos is not None and mgr is not None:
            mgr.hooks = chaos.checkpoint_phase
        collector = (self._make_sharded_collector(runner, tel)
                     if cfg.async_collect else None)
        if collector is not None and state["round"] > 0 \
                and cfg.max_aip_staleness > 0:
            self._reprime_collector(collector, state, runner=runner)
        elastic = heartbeats is not None
        mirror = runner.unshard_carry(carry) if elastic else None
        history = []
        t_start = time.time()
        tel.emit("run_start", path="sharded", env=self.info.name,
                 n_shards=runner.n_shards, start_round=state["round"],
                 outer_rounds=cfg.outer_rounds,
                 async_collect=cfg.async_collect, elastic=elastic,
                 sharded_gs=runner.use_sharded_gs, kernels=kernels)
        try:
            for rnd in range(state["round"], cfg.outer_rounds):
                if chaos is not None:
                    # the round boundary: the one point where killing a
                    # host cannot strand survivors inside a collective
                    chaos.round_start(rnd)
                tr.reset()
                t_round = time.perf_counter()
                with tr.span("dials.round"):
                    dead_hosts, reassigned = (), 0
                    if elastic:
                        dead_hosts = tuple(heartbeats(rnd))
                        if dead_hosts:
                            runner, carry, collector, reassigned = \
                                self._reassign(runner, carry, mirror,
                                               collector, dead_hosts, tel)
                    mask = (jnp.asarray(straggler_mask(rnd), jnp.float32)
                            if straggler_mask is not None and not cfg.untrained
                            else jnp.ones((n,), jnp.float32))
                    if collector is None:
                        carry, rec = runner.round(carry, base_key, rnd, mask)
                        forced_sync, collect_s = False, None
                    else:
                        with tr.span(SYNC + "obtain"):
                            tagged, forced_sync = collector.obtain(
                                rnd, carry["ials"]["params"],
                                self._collect_key(base_key, rnd),
                                max_staleness=cfg.max_aip_staleness)
                        # a tag-rnd dataset can only be consumed if the bound
                        # tolerates one round of lag
                        if (rnd + 1 < cfg.outer_rounds and collector.idle()
                                and cfg.max_aip_staleness > 0):
                            collector.submit(
                                carry["ials"]["params"],
                                self._collect_key(base_key, rnd + 1), rnd)
                        # agent-shard the dataset onto the mesh (it arrives on
                        # the spare device when one exists); an async transfer.
                        # Identity for the region-decomposed collect — its
                        # output is born mesh-sharded.
                        data = runner.place_dataset(tagged.data)
                        carry, rec = runner.train_round(
                            carry, data, base_key, rnd, tagged.round, mask)
                        collect_s = collector.last_obtain_wait_s
                    # the round's host syncs: one read per key of the on-mesh
                    # record (telemetry scalars included — they were computed
                    # inside the round program, not by extra fetches)
                    raw = {k: tr.pull(k, v) for k, v in rec.items()}
                    mirror_s = None
                    if elastic:
                        # the availability tax: refresh the host mirror the
                        # NEXT round's reassignment would restore from (an
                        # all-gather on a multi-process mesh)
                        with tr.span("dials.mirror"):
                            t_mirror = time.perf_counter()
                            mirror = tr.fence(runner.unshard_carry(carry))
                            mirror_s = time.perf_counter() - t_mirror
                    rec = obs_metrics.round_record(
                        round=rnd,
                        gs_return=raw["gs_return"],
                        ials_reward=(None if cfg.aip_refresh == 0
                                     else raw["ials_reward"]),
                        aip_ce_before=raw["aip_ce_before"],
                        aip_ce_after=raw["aip_ce_after"],
                        data_round=raw["data_round"],
                        forced_sync=forced_sync,
                        stale_forced=raw["stale_forced"],
                        staleness_min=raw["staleness_min"],
                        staleness_mean=raw["staleness_mean"],
                        staleness_max=raw["staleness_max"],
                        n_shards=runner.n_shards,
                        reassigned=reassigned,
                        dead_hosts=list(dead_hosts),
                        kernels=kernels,
                        collect_s=collect_s,
                        env_steps_per_s=None,
                        aip_s=None, inner_s=None, eval_s=None,
                        mirror_s=mirror_s,
                        sync_s=tr.sync_seconds(),
                        round_s=time.perf_counter() - t_round,
                        wall_s=time.time() - t_start)
                tel.emit_round(rec)
                history.append(rec)
                if log:
                    log(rec)
                if mgr is not None:
                    # the local-slice copy inside save() runs before the
                    # next round donates these buffers; reports is tiny
                    # ((N,) int32) but global — fetch for the extra
                    mgr.save(rnd + 1, {
                        "ials": carry["ials"], "aips": carry["aips"],
                        "round": rnd + 1, "key": base_key},
                        extra=self._ckpt_extra(
                            collector,
                            tr.pull("reports", carry["reports"],
                                    runtime_lib.fetch_tree)))
        finally:
            tel.emit("run_end", rounds=len(history))
            tel.close()
        unshard = runner.unshard_carry(carry)
        unshard.pop("reports", None)     # keep both paths' state schema
        state = {**unshard, "round": cfg.outer_rounds, "key": base_key}
        if mgr is not None:
            mgr.wait()
        return state, history
