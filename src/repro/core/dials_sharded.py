"""Sharded DIALS runtime — Algorithm 1 as ONE program over a device mesh.

The single-device :class:`~repro.core.dials.DIALSTrainer` pays a host
round-trip per inner step (``F + 3`` syncs per outer round). This runner
executes one full outer round — GS collect → per-shard AIP training →
F inner IALS+PPO steps → GS eval — as a **single jitted, donated-buffer
program** with the agent axis of params/opt/AIPs/locals sharded over a
1-D ``("shards",)`` mesh (``repro.distributed.runtime``):

* the per-shard section (AIP train + bounded-staleness refresh + a
  ``lax.scan`` over the F inner steps) runs under ``shard_map`` and is
  **collective-free by construction** — :meth:`inner_jaxpr` /
  :meth:`split_inner_jaxpr` expose its jaxpr so tests assert no
  cross-shard communication exists between AIP refreshes (the paper's
  runtime-stays-constant claim, made checkable);
* GS collect and the periodic GS eval run **region-decomposed on the
  same mesh** (``repro.core.gs_sharded``) whenever the env's
  ``region_partition`` supports the block count
  (``DIALSConfig.sharded_gs``: auto/on/off): block-local dynamics plus
  one halo exchange per step, the dataset emitted already agent-sharded.
  The audit extends accordingly — :meth:`audit_collectives` asserts the
  train body stays collective-free while every GS body contains ONLY
  halo-exchange collectives (``runtime.HALO_PRIMS``). With the
  replicated fallback the GS programs are the joint-policy gather points
  the partitioner inserts at the refresh boundary, as before;
* per-agent randomness comes from ``repro.core.ials``'s shard-equivariant
  keying, so the sharded round is numerically the single-device round —
  the driver can switch paths freely.

For the overlapped-collect driver (``DIALSConfig.async_collect``) the
fused round is **split in two**: :attr:`collect` (Algorithm 2 alone) and
:meth:`train_round` (everything after it, taking the dataset plus its
collection-round tag as arguments). The driver dispatches round k+1's
collect — on a spare device when the machine has one beyond the mesh —
before round k's shard-train program, so the two overlap; the per-shard
body enforces ``max_aip_staleness`` through
``repro.distributed.fault.freshness_gate`` (stragglers are tolerated up
to the bound, then force-refreshed), with the per-agent report rounds
carried on-mesh.

Host syncs per round: the metrics record, one read per scalar (the
driver's ``dials.sync.<key>`` spans). Telemetry holds that line: the
observability scalars (staleness distribution, CE, forced counts —
``repro.obs.metrics``) accumulate on-mesh inside this program and ride
the same record fetch; host-side spans and sinks live entirely in the
driver, so enabling telemetry does not change the traced round program
at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dials as dials_mod
from repro.core import gs as gs_mod
from repro.core import gs_sharded
from repro.core import ials as ials_mod
from repro.core import influence
from repro.distributed import fault
from repro.distributed import runtime as runtime_lib
from repro.marl import runner as runner_mod
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


class ShardedDIALSRunner:
    """Mesh-resident executor of one Algorithm-1 outer round.

    Built by ``DIALSTrainer`` when more than one device is available (or a
    shard count is forced); owns no training-loop policy — checkpointing,
    logging, the round loop, and the async-collect double buffer stay in
    the driver.
    """

    def __init__(self, env_mod, env_cfg, policy_cfg, aip_cfg, ppo_cfg, cfg,
                 *, mesh=None, n_shards=None):
        self.env_mod, self.env_cfg, self.cfg = env_mod, env_cfg, cfg
        # idempotent: a DIALSTrainer-built runner arrives pre-overridden
        policy_cfg, aip_cfg, ppo_cfg = dials_mod.apply_kernel_mode(
            policy_cfg, aip_cfg, ppo_cfg, cfg.use_kernels)
        self.aip_cfg = aip_cfg
        self.info = env_cfg.info()
        self.n_eval_seqs = dials_mod.holdout_sequences(cfg)
        n_agents = self.info.n_agents
        if mesh is None:
            if n_shards is None:
                n_shards = runtime_lib.choose_shards(n_agents)
            mesh = runtime_lib.shard_mesh(n_shards)
        self.mesh = mesh
        self.n_shards = mesh.shape[runtime_lib.SHARD_AXIS]
        if n_agents % self.n_shards:
            raise ValueError(
                f"{n_agents} agents cannot tile {self.n_shards} shards")

        self.use_sharded_gs = self._resolve_sharded_gs()
        if self.use_sharded_gs:
            # region-decomposed GS on the mesh: block-local dynamics +
            # halo exchange; dataset lands agent-sharded, no re-placement
            self.collect = gs_sharded.make_sharded_collector(
                env_mod, env_cfg, policy_cfg,
                n_envs=dials_mod.collect_stream_count(cfg),
                steps=cfg.collect_steps, mesh=self.mesh)
            self.gs_eval = gs_sharded.make_sharded_evaluator(
                env_mod, env_cfg, policy_cfg, mesh=self.mesh)
        else:
            self.collect = gs_mod.make_collector(
                env_mod, env_cfg, policy_cfg,
                n_envs=dials_mod.collect_stream_count(cfg),
                steps=cfg.collect_steps)
            _, _, self.gs_eval = runner_mod.make_gs_trainer(
                env_mod, env_cfg, policy_cfg, ppo_cfg,
                runner_mod.RunConfig(n_envs=cfg.n_envs,
                                     rollout_steps=cfg.rollout_steps))
        self.ials_init = ials_mod.make_ials_init(
            env_mod, env_cfg, policy_cfg, aip_cfg,
            n_envs=dials_mod.ials_stream_count(cfg))
        self._agent_train = ials_mod.make_agent_trainer(
            env_mod, env_cfg, policy_cfg, aip_cfg, ppo_cfg,
            n_envs=dials_mod.ials_stream_count(cfg),
            rollout_steps=cfg.rollout_steps)
        self._shard_body = self._make_shard_body()
        self._train_fn = self._make_train()
        self._round_fn = self._make_round()
        # sync path: the whole round fused. async path: the driver calls
        # self.collect and train_round separately so they can overlap.
        self.round = jax.jit(self._round_fn, donate_argnums=0)
        self.train_round = jax.jit(self._train_fn, donate_argnums=0)

    # -- GS decomposition selection ------------------------------------------
    def _resolve_sharded_gs(self) -> bool:
        mode = self.cfg.sharded_gs
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"sharded_gs must be auto|on|off, got {mode!r}")
        if mode == "off":
            return False
        ok, why = gs_sharded.partition_supported(
            self.env_mod, self.env_cfg, self.n_shards)
        if mode == "on" and not ok:
            raise ValueError(
                f"sharded_gs='on' but the GS cannot decompose into "
                f"{self.n_shards} blocks: {why}")
        return ok

    # -- per-shard program ---------------------------------------------------
    def _make_shard_body(self):
        """The collective-free section: everything between AIP refreshes.

        All arguments arrive pre-sliced to this shard's agents (leading
        axis N/num_shards) except the two replicated scalars (current
        round, dataset collection round); nothing here may touch another
        shard — the freshness gate and masked update are elementwise.
        """
        cfg, aip_cfg = self.cfg, self.aip_cfg
        n_eval = self.n_eval_seqs
        train_aips = jax.vmap(
            lambda p, d, k: influence.train_aip(p, d, k, aip_cfg))
        eval_aips = jax.vmap(lambda p, d: influence.eval_ce(p, d, aip_cfg))
        train_agents = jax.vmap(self._agent_train)

        def shard_body(aips, ials, reports, data, aip_keys, fresh_mask,
                       rnd, data_round):
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

            def inner(ials, _):
                return train_agents(ials, aips)

            if cfg.aip_refresh:
                ials, metrics = jax.lax.scan(
                    inner, ials, None, length=cfg.aip_refresh)
                metrics = jax.tree.map(lambda x: x[-1], metrics)  # last F
            else:
                # no inner steps ran; a well-shaped placeholder keeps the
                # shard_map out_specs intact — the driver reports
                # ials_reward as null for this (static) config
                metrics = {"reward": jnp.zeros(reports.shape, jnp.float32)}
            return aips, ials, reports, ce_before, ce_after, metrics, forced

        return shard_body

    # -- abstract tracing (tests / audits) -----------------------------------
    def _abstract_carry(self):
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        return {"aips": jax.eval_shape(
                    lambda k: jax.vmap(
                        lambda kk: influence.aip_init(kk, self.aip_cfg))(
                        jax.random.split(k, self.info.n_agents)), key),
                "ials": jax.eval_shape(self.ials_init, key),
                "reports": jax.ShapeDtypeStruct(
                    (self.info.n_agents,), jnp.int32)}

    def round_jaxpr(self):
        """Jaxpr of the whole fused round, traced abstractly at this
        runner's shapes (no FLOPs)."""
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        carry = self._abstract_carry()
        rnd = jax.ShapeDtypeStruct((), jnp.int32)
        mask = jax.ShapeDtypeStruct((self.info.n_agents,), jnp.float32)
        return jax.make_jaxpr(self._round_fn)(carry, key, rnd, mask)

    def train_round_jaxpr(self):
        """Jaxpr of the shard-train program of the SPLIT round (the async
        path's second half: AIP train + F inner steps + GS eval, dataset
        passed in)."""
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        carry = self._abstract_carry()
        data = jax.eval_shape(self.collect, carry["ials"]["params"], key)
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        mask = jax.ShapeDtypeStruct((self.info.n_agents,), jnp.float32)
        return jax.make_jaxpr(self._train_fn)(
            carry, data, key, scalar, scalar, mask)

    def _classify_bodies(self, jaxpr, what):
        """Split a traced program's shard_map bodies into (train body,
        GS bodies). The train body is the unique collective-free one;
        every other shard_map is a region-decomposed GS program, which
        always carries its halo ppermutes. With the replicated-GS
        fallback the program contains exactly the one train shard_map."""
        bodies = runtime_lib.find_shard_map_jaxprs(jaxpr)
        train = [b for b in bodies
                 if not runtime_lib.collectives_in_jaxpr(b)]
        gs_bodies = [b for b in bodies
                     if runtime_lib.collectives_in_jaxpr(b)]
        assert len(train) == 1, \
            f"expected exactly one collective-free shard_map (the " \
            f"train body) in the {what}, found {len(train)} among " \
            f"{len(bodies)} shard_maps"
        n_gs = (2 if self.use_sharded_gs and what == "round" else
                1 if self.use_sharded_gs else 0)
        assert len(gs_bodies) == n_gs, \
            f"expected {n_gs} GS shard_maps in the {what}, " \
            f"found {len(gs_bodies)}"
        return train[0], gs_bodies

    def inner_jaxpr(self):
        """The per-shard train body of the round, EXTRACTED from the
        traced fused round program (not re-traced separately) — the
        artifact the no-collectives assertion runs against. Everything
        between AIP refreshes lives inside this one shard_map."""
        return self._classify_bodies(self.round_jaxpr(), "round")[0]

    def split_inner_jaxpr(self):
        """Same audit artifact, extracted from the split shard-train
        program the async-collect driver actually runs."""
        return self._classify_bodies(
            self.train_round_jaxpr(), "shard-train program")[0]

    def gs_jaxprs(self):
        """The region-decomposed GS bodies of the fused round (collect +
        eval; empty with the replicated fallback) — the artifacts the
        halo-only assertion runs against."""
        return self._classify_bodies(self.round_jaxpr(), "round")[1]

    def contract_programs(self):
        """Both round programs and their extracted bodies as tagged
        ``repro.analysis.contracts.Program`` records — what the static
        checker (``tools/check_programs.py``) and
        :meth:`audit_collectives` feed the rule engine."""
        from repro.analysis.contracts import Program
        programs = []
        for what, role, jaxpr in (
                ("round", "round", self.round_jaxpr()),
                ("shard-train program", "train_round",
                 self.train_round_jaxpr())):
            train, gs_bodies = self._classify_bodies(jaxpr, what)
            programs.append(Program(
                name=f"{what} per-shard train body",
                roles=("train_body",), jaxpr=train))
            programs.extend(Program(
                name=f"{what} GS body", roles=("gs_body",), jaxpr=body)
                for body in gs_bodies)
        return programs

    def audit_collectives(self):
        """The full communication contract of both round programs, as
        one executable check through the rule engine: the train body is
        collective-free, and every GS body contains exactly the
        halo-exchange collectives and nothing else — violations raise
        with the offending primitive's source line."""
        from repro.analysis import contracts
        contracts.raise_findings(contracts.run_rules(
            self.contract_programs(),
            rules=(contracts.CollectiveFree(), contracts.HaloOnly())))

    # -- the shard-train program ---------------------------------------------
    def _make_train(self):
        cfg, mesh = self.cfg, self.mesh
        n_agents = self.info.n_agents
        sharded = P(runtime_lib.SHARD_AXIS)
        body = runtime_lib.shard_map_nocheck(
            self._shard_body, mesh,
            in_specs=(sharded,) * 6 + (P(), P()),
            out_specs=(sharded,) * 7)

        def train_fn(carry, data, base_key, rnd, data_round, fresh_mask):
            """carry = {"aips", "ials", "reports"} (donated). ``data`` is
            the round's dataset, ``data_round`` its collection tag (= rnd
            on the serial schedule, rnd-1 in the async steady state).
            Returns (carry', rec)."""
            key = jax.random.fold_in(base_key, rnd)
            _kc, kt, ke = jax.random.split(key, 3)

            # (2)+(3) per-shard: AIP train + staleness gate + F frozen-AIP
            # inner steps
            with obs_trace.annotate("shard_train"):
                aips, ials, reports, ce_before, ce_after, metrics, \
                    forced = body(
                        carry["aips"], carry["ials"], carry["reports"],
                        data, jax.random.split(kt, n_agents), fresh_mask,
                        jnp.asarray(rnd, jnp.int32),
                        jnp.asarray(data_round, jnp.int32))

            # (4) periodic GS eval — the once-per-round joint-policy sync
            with obs_trace.annotate("gs_eval"):
                ret = self.gs_eval(ials["params"], ke,
                                   episodes=cfg.eval_episodes)
            # telemetry scalars accumulate here, ON-MESH, outside the
            # shard_map body (cross-shard reductions are legal at this
            # level, like the CE means): they ride the one existing
            # per-round record fetch — zero extra host syncs
            rec = {"gs_return": ret,
                   "ials_reward": metrics["reward"].mean(),
                   "aip_ce_before": ce_before.mean(),
                   "aip_ce_after": ce_after.mean(),
                   "data_round": jnp.asarray(data_round, jnp.int32),
                   "stale_forced": forced.sum(),
                   **obs_metrics.staleness_stats(reports, rnd)}
            return {"aips": aips, "ials": ials, "reports": reports}, rec

        return train_fn

    # -- the fused round -----------------------------------------------------
    def _make_round(self):
        def round_fn(carry, base_key, rnd, fresh_mask):
            """The serial schedule: collect under THIS round's policy
            (data_round = rnd), then the shard-train section, one fused
            donated program."""
            key = jax.random.fold_in(base_key, rnd)
            kc, _kt, _ke = jax.random.split(key, 3)

            # (1) Algorithm 2: datasets from the GS under the joint policy
            with obs_trace.annotate("gs_collect"):
                data = self.collect(carry["ials"]["params"], kc)
            return self._train_fn(carry, data, base_key, rnd, rnd,
                                  fresh_mask)

        return round_fn

    # -- placement -----------------------------------------------------------
    def place_dataset(self, data):
        """Agent-shard a collected dataset onto the mesh (leaves are
        agent-major, (N, S, T, ...)). The async driver uses this to move
        a spare-device collect result next to the shard-train program;
        the region-decomposed collector already emits mesh-sharded
        leaves, so this is the identity there (no post-collect
        re-placement — the contract of the sharded GS)."""
        if self.use_sharded_gs:
            return data
        return runtime_lib.shard_agent_tree(data, self.mesh)

    def shard_carry(self, carry):
        """Move an {"aips", "ials", "reports"} carry onto the mesh,
        agent-sharded."""
        return runtime_lib.shard_agent_tree(carry, self.mesh)

    def unshard_carry(self, carry):
        """Fetch a mesh-resident carry back to host-addressable arrays
        (checkpointing, path switching, the elastic driver's host
        mirror). On a mesh spanning processes this is an all-gather —
        every process ends up holding every agent's block, which is
        exactly what lets a surviving host adopt a dead host's agents."""
        return runtime_lib.fetch_tree(carry)
