"""Program registry for the contract checker.

This module knows how to build every traced program in the repo as a
:class:`repro.analysis.contracts.Program` — abstractly, at tiny sizes
(jaxprs via ``make_jaxpr``/``eval_shape``, no FLOPs) — so
``tools/check_programs.py`` can run the full rule set over **both
drivers × every registered scenario**:

* the **sharded driver**'s fused round + split shard-train program
  (donation, sync budget, callback rules), their extracted per-shard
  train bodies (collective-free) and GS bodies (halo-only), and the
  collect program;
* the **loop driver**'s jitted pieces (collect, AIP train, IALS inner
  step, GS eval) — no mesh, so no collective rules fire, but callback
  and structural rules run identically (the driver-parity contract);
* the **kernel dispatch paths** (GRU/GAE ops, oracle and Pallas) as
  dtype round-trip programs;
* the **wide-stream collect path** — the donating ring-slot collect and
  the fused round re-audited at S=64 streams, where donation aliasing
  and the sync budget can silently regress as shapes grow.

New traced programs MUST register here (see ROADMAP): either extend
:func:`scenario_programs` or append a builder via
:func:`register_programs` — the CI ``analysis`` job checks whatever
this module yields.
"""
from __future__ import annotations

import functools
from typing import Callable, Iterable, List, Optional

import jax
import jax.numpy as jnp

from repro.analysis.contracts import Program

__all__ = ["tiny_trainer", "loop_programs", "sharded_programs",
           "kernel_dtype_programs", "recovery_programs",
           "stream_programs", "scenario_programs", "all_programs",
           "register_programs", "DRIVERS"]

DRIVERS = ("loop", "sharded")

# extension point: fns () -> List[Program], run by all_programs()
_EXTRA_BUILDERS: List[Callable[[], List[Program]]] = []


def register_programs(builder: Callable[[], List[Program]]) -> None:
    """Register additional programs with the checker (future traced
    programs must call this — the CI analysis job audits the union)."""
    _EXTRA_BUILDERS.append(builder)


def tiny_trainer(env: str, *, kind: str = "fnn", **kw):
    """A ``DIALSTrainer`` at trace-only sizes (mirrors the test suite's
    tiny config) — never ``run()`` here; the checker only traces."""
    from repro.core import dials, influence
    from repro.envs import registry
    from repro.marl import policy as policy_mod, ppo as ppo_mod

    env_mod, cfg = registry.make(env, horizon=16)
    info = cfg.info()
    pc = policy_mod.PolicyConfig(obs_dim=info.obs_dim,
                                 n_actions=info.n_actions, kind=kind,
                                 hidden=(16,), gru_hidden=8)
    ac = influence.AIPConfig(in_dim=info.alsh_dim,
                             n_sources=info.n_influence, kind=kind,
                             hidden=(16,), gru_hidden=8, epochs=2,
                             batch=16)
    ppo_cfg = ppo_mod.PPOConfig(epochs=1, minibatches=2)
    dcfg = dials.DIALSConfig(**{
        **dict(outer_rounds=2, aip_refresh=2, collect_envs=2,
               collect_steps=16, n_envs=2, rollout_steps=8,
               eval_episodes=2), **kw})
    return dials.DIALSTrainer(env_mod, cfg, pc, ac, ppo_cfg, dcfg)


def _key_aval():
    return jax.ShapeDtypeStruct((2,), jnp.uint32)


# ---------------------------------------------------------------------------
# loop driver
# ---------------------------------------------------------------------------
def loop_programs(env: str, *, kind: str = "fnn") -> List[Program]:
    """The loop driver's jitted pieces, traced abstractly."""
    from repro.core import gs as gs_mod
    from repro.core import influence

    trainer = tiny_trainer(env, kind=kind, shards=1)
    info, cfg = trainer.info, trainer.cfg
    key = _key_aval()
    state = jax.eval_shape(trainer.ials_init, key)
    params = state["params"]
    aips = jax.eval_shape(
        lambda k: jax.vmap(
            lambda kk: influence.aip_init(kk, trainer.aip_cfg))(
            jax.random.split(k, info.n_agents)), key)
    data = jax.eval_shape(trainer.collect, params, key)
    train_data = jax.eval_shape(
        lambda d: gs_mod.split_dataset(d, trainer.n_eval_seqs)[0], data)
    agent_keys = jax.ShapeDtypeStruct((info.n_agents, 2), jnp.uint32)
    gs_eval = functools.partial(trainer.gs_eval,
                                episodes=cfg.eval_episodes)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    mask = jax.ShapeDtypeStruct((info.n_agents,), jnp.float32)
    reports = jax.ShapeDtypeStruct((info.n_agents,), jnp.int32)
    aip_round_args = (aips, data, agent_keys, mask, reports,
                      scalar, scalar)
    n_data_leaves = len(jax.tree.leaves(data))
    pre = f"loop/{env}"
    return [
        Program(name=f"{pre}/collect", roles=("collect", "program"),
                jaxpr=jax.make_jaxpr(trainer.collect)(params, key),
                fn=trainer.collect, args=(params, key)),
        # the donating ring-slot variant of the same pool rollout: the
        # RingBufferResident + DonationUsed pair pins the no-host-round-
        # trip / no-realloc claim the DeviceRing makes
        Program(name=f"{pre}/ring_collect",
                roles=("ring_collect", "donated", "program"),
                jaxpr=jax.make_jaxpr(trainer.collect_into)(
                    data, params, key),
                fn=trainer.collect_into, args=(data, params, key),
                donate_argnums=(0,),
                meta={"expect_aliased": n_data_leaves}),
        Program(name=f"{pre}/train_aips", roles=("program",),
                jaxpr=jax.make_jaxpr(trainer.train_aips)(
                    aips, train_data, agent_keys),
                fn=trainer.train_aips, args=(aips, train_data,
                                             agent_keys)),
        # the fused AIP round (holdout split + eval + train + freshness
        # gate as ONE program over the ring-resident dataset)
        Program(name=f"{pre}/aip_round", roles=("program",),
                jaxpr=jax.make_jaxpr(trainer.aip_round)(*aip_round_args),
                fn=trainer.aip_round, args=aip_round_args),
        Program(name=f"{pre}/ials_train", roles=("program",),
                jaxpr=jax.make_jaxpr(trainer.ials_train)(state, aips),
                fn=trainer.ials_train, args=(state, aips)),
        Program(name=f"{pre}/gs_eval", roles=("program",),
                jaxpr=jax.make_jaxpr(gs_eval)(params, key),
                fn=gs_eval, args=(params, key)),
    ]


# ---------------------------------------------------------------------------
# sharded driver
# ---------------------------------------------------------------------------
def sharded_programs(env: str, *, kind: str = "fnn",
                     n_shards: Optional[int] = None) -> List[Program]:
    """The sharded driver's fused/split round programs plus their
    extracted train and GS bodies. Needs >1 visible device to build a
    multi-shard mesh; a 1-device process still audits a 1-shard mesh."""
    from repro.core import dials_sharded
    from repro.distributed import runtime

    trainer = tiny_trainer(env, kind=kind)
    info = trainer.info
    if n_shards is None:
        n_shards = runtime.choose_shards(info.n_agents,
                                         len(jax.devices()))
    runner = dials_sharded.ShardedDIALSRunner(
        trainer.env_mod, trainer.env_cfg, trainer.policy_cfg,
        trainer.aip_cfg, trainer.ppo_cfg, trainer.cfg,
        n_shards=n_shards)

    key = _key_aval()
    carry = runner._abstract_carry()
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    mask = jax.ShapeDtypeStruct((info.n_agents,), jnp.float32)
    round_args = (carry, key, scalar, mask)
    data = jax.eval_shape(runner.collect, carry["ials"]["params"], key)
    train_args = (carry, data, key, scalar, scalar, mask)
    n_carry_leaves = len(jax.tree.leaves(carry))

    round_jx = runner.round_jaxpr()
    train_jx = runner.train_round_jaxpr()
    pre = f"sharded/{env}@{runner.n_shards}"
    programs = [
        Program(name=f"{pre}/round", roles=("round", "donated"),
                jaxpr=round_jx, fn=runner.round, args=round_args,
                donate_argnums=(0,),
                meta={"expect_aliased": n_carry_leaves}),
        Program(name=f"{pre}/train_round",
                roles=("train_round", "donated"),
                jaxpr=train_jx, fn=runner.train_round, args=train_args,
                donate_argnums=(0,),
                meta={"expect_aliased": n_carry_leaves}),
        Program(name=f"{pre}/collect", roles=("collect", "program"),
                jaxpr=jax.make_jaxpr(runner.collect)(
                    carry["ials"]["params"], key),
                fn=runner.collect,
                args=(carry["ials"]["params"], key)),
    ]
    for what, jx in (("round", round_jx), ("train_round", train_jx)):
        train_body, gs_bodies = runner._classify_bodies(
            jx, "round" if what == "round" else "shard-train program")
        programs.append(Program(
            name=f"{pre}/{what}/train_body", roles=("train_body",),
            jaxpr=train_body))
        programs.extend(Program(
            name=f"{pre}/{what}/gs_body[{i}]", roles=("gs_body",),
            jaxpr=body) for i, body in enumerate(gs_bodies))
    return programs


# ---------------------------------------------------------------------------
# recovery / resume path (post-loss re-bootstrap)
# ---------------------------------------------------------------------------
def recovery_programs(env: str = "traffic", *,
                      kind: str = "fnn") -> List[Program]:
    """The post-loss resume path's traced programs.

    After a host death the survivors re-exec, re-bootstrap as a shrunken
    group, and resume from the committed distributed checkpoint — so the
    programs that actually run are (a) the fused round retraced on the
    *shrunken* mesh and (b) the two jit-identity re-shard transfers the
    restore/mirror path performs: checkpoint rows (host/replicated) →
    agent-sharded placement, and agent-sharded state → replicated fetch
    (the checkpoint snapshot + metrics path). The round re-audits under
    the full rule set; the ``("reshard",)`` programs feed the
    ``ReshardCollectives`` rule, which pins the restore path to
    data-movement collectives only (all-gather / collective-permute) —
    a surprise all-reduce here would mean the resume path silently
    recomputes instead of moving rows."""
    from repro.core import dials_sharded
    from repro.distributed import runtime

    trainer = tiny_trainer(env, kind=kind)
    info = trainer.info
    n_dev = len(jax.devices())
    # the shrunken group: half the devices vanished with the dead host
    n_shards = runtime.choose_shards(info.n_agents, max(1, n_dev // 2))
    runner = dials_sharded.ShardedDIALSRunner(
        trainer.env_mod, trainer.env_cfg, trainer.policy_cfg,
        trainer.aip_cfg, trainer.ppo_cfg, trainer.cfg,
        n_shards=n_shards)

    key = _key_aval()
    carry = runner._abstract_carry()
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    mask = jax.ShapeDtypeStruct((info.n_agents,), jnp.float32)
    n_carry_leaves = len(jax.tree.leaves(carry))
    round_jx = runner.round_jaxpr()
    pre = f"recovery/{env}@{runner.n_shards}"
    programs = [
        Program(name=f"{pre}/resume_round", roles=("round", "donated"),
                jaxpr=round_jx, fn=runner.round,
                args=(carry, key, scalar, mask), donate_argnums=(0,),
                meta={"expect_aliased": n_carry_leaves}),
    ]
    # the resume round IS a fused round program — classify it as one
    # (the "round" key sets the expected GS-body count: collect + eval)
    train_body, gs_bodies = runner._classify_bodies(round_jx, "round")
    programs.append(Program(
        name=f"{pre}/resume_round/train_body", roles=("train_body",),
        jaxpr=train_body))
    programs.extend(Program(
        name=f"{pre}/resume_round/gs_body[{i}]", roles=("gs_body",),
        jaxpr=body) for i, body in enumerate(gs_bodies))

    # the re-shard transfers: jit identities whose in/out shardings force
    # XLA to emit exactly the data movement the restore path performs
    sharded = jax.tree.map(
        lambda _: runtime.agent_sharding(runner.mesh), carry)
    replicated = jax.tree.map(
        lambda _: runtime.replicated_sharding(runner.mesh), carry)
    place = jax.jit(lambda t: t, in_shardings=(replicated,),
                    out_shardings=sharded)
    fetch = jax.jit(lambda t: t, in_shardings=(sharded,),
                    out_shardings=replicated)
    programs.extend([
        Program(name=f"{pre}/reshard_place", roles=("reshard",),
                fn=place, args=(carry,),
                meta={"mesh_devices": runner.mesh.devices.size}),
        Program(name=f"{pre}/reshard_fetch", roles=("reshard",),
                fn=fetch, args=(carry,),
                meta={"mesh_devices": runner.mesh.devices.size}),
    ])
    return programs


# ---------------------------------------------------------------------------
# wide-stream (S-swept) collect path
# ---------------------------------------------------------------------------
def stream_programs(env: str = "traffic", *, streams: int = 64,
                    kind: str = "fnn") -> List[Program]:
    """The large-batch collect path at a wide stream count S.

    The S knobs (``DIALSConfig.collect_streams``) only change a vmapped
    batch axis, so the contracts that hold at S=2 must hold at S=64 —
    but donation aliasing, the ring's struct round-trip, and the fused
    round's sync budget are exactly the properties that CAN silently
    regress when a shape grows (XLA drops an alias, a reduction widens
    an output). This re-audits the loop ring collect and the sharded
    fused round with the stream axis actually wide."""
    from repro.core import dials_sharded

    trainer = tiny_trainer(env, kind=kind, collect_streams=streams)
    info = trainer.info
    key = _key_aval()
    params = jax.eval_shape(trainer.ials_init, key)["params"]
    data = jax.eval_shape(trainer.collect, params, key)
    pre = f"streams/{env}@S{streams}"
    programs = [
        Program(name=f"{pre}/ring_collect",
                roles=("ring_collect", "donated", "program"),
                jaxpr=jax.make_jaxpr(trainer.collect_into)(
                    data, params, key),
                fn=trainer.collect_into, args=(data, params, key),
                donate_argnums=(0,),
                meta={"expect_aliased": len(jax.tree.leaves(data))}),
    ]
    runner = dials_sharded.ShardedDIALSRunner(
        trainer.env_mod, trainer.env_cfg, trainer.policy_cfg,
        trainer.aip_cfg, trainer.ppo_cfg, trainer.cfg, n_shards=1)
    carry = runner._abstract_carry()
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    mask = jax.ShapeDtypeStruct((info.n_agents,), jnp.float32)
    round_jx = runner.round_jaxpr()
    programs.append(Program(
        name=f"{pre}/round", roles=("round", "donated"),
        jaxpr=round_jx, fn=runner.round,
        args=(carry, key, scalar, mask), donate_argnums=(0,),
        meta={"expect_aliased": len(jax.tree.leaves(carry))}))
    train_body, gs_bodies = runner._classify_bodies(round_jx, "round")
    programs.append(Program(
        name=f"{pre}/round/train_body", roles=("train_body",),
        jaxpr=train_body))
    programs.extend(Program(
        name=f"{pre}/round/gs_body[{i}]", roles=("gs_body",),
        jaxpr=body) for i, body in enumerate(gs_bodies))
    return programs


# ---------------------------------------------------------------------------
# kernel dispatch dtype contracts
# ---------------------------------------------------------------------------
def kernel_dtype_programs(dtype=jnp.bfloat16) -> List[Program]:
    """The GRU/GAE hot-spot ops, oracle and kernel path, as dtype
    round-trip programs: reduced-precision in ⇒ reduced-precision out
    (internals may accumulate f32; outputs must cast back)."""
    from repro.kernels.gae import ops as gae_ops
    from repro.kernels.gru import ops as gru_ops
    from repro.marl import gae as gae_oracle
    from repro.nn import gru as gru_oracle

    b, t, d_in, h = 2, 8, 4, 8
    seq = jax.ShapeDtypeStruct((b, t), dtype)
    last = jax.ShapeDtypeStruct((b,), dtype)
    gae_args = (seq, seq, seq, last)
    xs = jax.ShapeDtypeStruct((b, t, d_in), dtype)
    gru_params = {
        "wi": jax.ShapeDtypeStruct((d_in, 3 * h), dtype),
        "wh": jax.ShapeDtypeStruct((h, 3 * h), dtype),
        "bi": jax.ShapeDtypeStruct((3 * h,), dtype),
        "bh": jax.ShapeDtypeStruct((3 * h,), dtype),
    }
    kernel_gae = functools.partial(gae_ops.gae, interpret=True)
    kernel_gru = functools.partial(gru_ops.gru_sequence, interpret=True)
    # the agent-blocked launch: per-agent params, as DIALS maps them
    n_agents = 3
    agent_params = {k: jax.ShapeDtypeStruct((n_agents,) + v.shape, dtype)
                    for k, v in gru_params.items()}
    agent_xs = jax.ShapeDtypeStruct((n_agents,) + xs.shape, dtype)
    return [
        Program(name="kernels/gae/oracle", roles=("dtype",),
                fn=gae_oracle.gae, args=gae_args),
        Program(name="kernels/gae/pallas", roles=("dtype",),
                fn=kernel_gae, args=gae_args),
        Program(name="kernels/gru/oracle", roles=("dtype",),
                fn=gru_oracle.gru_sequence, args=(gru_params, xs)),
        Program(name="kernels/gru/pallas", roles=("dtype",),
                fn=kernel_gru, args=(gru_params, xs)),
        Program(name="kernels/gru/pallas-vmapped", roles=("dtype",),
                fn=jax.vmap(kernel_gru), args=(agent_params, agent_xs)),
    ]


# ---------------------------------------------------------------------------
# the full catalogue
# ---------------------------------------------------------------------------
def scenario_programs(env: str, drivers: Iterable[str] = DRIVERS,
                      *, kind: str = "fnn") -> List[Program]:
    out: List[Program] = []
    if "loop" in drivers:
        out.extend(loop_programs(env, kind=kind))
    if "sharded" in drivers:
        out.extend(sharded_programs(env, kind=kind))
    return out


def all_programs(scenarios: Optional[Iterable[str]] = None,
                 drivers: Iterable[str] = DRIVERS,
                 *, kernels: bool = True,
                 recovery: bool = True,
                 streams: bool = True) -> List[Program]:
    """Every registered program: both drivers × every scenario, the
    kernel dtype contracts, the post-loss resume-path programs, the
    wide-stream collect re-audit, and anything added via
    :func:`register_programs`."""
    from repro.envs import registry

    if scenarios is None:
        scenarios = registry.names()
    scenarios = list(scenarios)
    out: List[Program] = []
    for env in scenarios:
        out.extend(scenario_programs(env, drivers))
    if kernels:
        out.extend(kernel_dtype_programs())
    if recovery and scenarios and "sharded" in drivers:
        out.extend(recovery_programs(scenarios[0]))
    if streams and scenarios:
        out.extend(stream_programs(scenarios[0]))
    for builder in _EXTRA_BUILDERS:
        out.extend(builder())
    return out
