"""Plain reference copy of the traffic environment (the program's
``repro.envs.traffic`` as of the benchmark's first version). The
benchmark's correctness reference imports nothing of the program;
this copy is its yardstick, so later changes to the program's env
show up as a disagreement."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .base import EnvInfo, contiguous_partition


@dataclasses.dataclass(frozen=True)
class TrafficConfig:
    n: int = 2                  # grid side; N = n*n agents
    lane_len: int = 8           # L
    p_in: float = 0.3           # boundary car-injection probability
    horizon: int = 100
    init_density: float = 0.2

    @property
    def n_agents(self) -> int:
        return self.n * self.n

    def info(self) -> EnvInfo:
        obs_dim = 4 * self.lane_len + 2
        return EnvInfo(name="traffic", n_agents=self.n_agents,
                       obs_dim=obs_dim, n_actions=2, n_influence=4,
                       horizon=self.horizon,
                       alsh_dim=obs_dim + 2)


# ---------------------------------------------------------------------------
# Shared per-intersection transition (the \dot{T}_i of the IALM)
# ---------------------------------------------------------------------------
def lane_step(lanes, green, inflow):
    """One intersection's lanes for one step.

    lanes: (4, L) bool — cell 0 is the region entry, cell L-1 the stop line.
    green: (4,) bool — may the head car cross.
    inflow: (4,) bool — does a car enter cell 0 (the influence sources).

    Returns (new_lanes, out (4,) bool crossed cars, moved (), count ()).
    """
    lanes = lanes.astype(bool)
    ahead_free = jnp.concatenate(
        [~lanes[:, 1:], green[:, None].astype(bool)], axis=1)   # (4, L)
    move = lanes & ahead_free
    shifted = jnp.concatenate(
        [jnp.zeros((4, 1), bool), move[:, :-1]], axis=1)
    new = (lanes & ~move) | shifted
    out = move[:, -1]
    # inflow enters cell 0 if it is free after the shift
    enter = inflow.astype(bool) & ~new[:, 0]
    new = new.at[:, 0].set(new[:, 0] | enter)
    moved = move.sum()                 # mean-speed proxy over pre-step cars
    count = lanes.sum()
    return new, out, moved.astype(jnp.float32), count.astype(jnp.float32)


def _green(phase):
    """phase () int -> (4,) bool for lanes [N, E, S, W]."""
    ns = phase == 0
    return jnp.stack([ns, ~ns, ns, ~ns], axis=-1)


def _obs(lanes, phase):
    return jnp.concatenate([
        lanes.reshape(-1).astype(jnp.float32),
        jax.nn.one_hot(phase, 2, dtype=jnp.float32),
    ])


# ---------------------------------------------------------------------------
# Global simulator
# ---------------------------------------------------------------------------
def gs_init(key, cfg: TrafficConfig):
    k1, k2 = jax.random.split(key)
    lanes = jax.random.bernoulli(
        k1, cfg.init_density, (cfg.n, cfg.n, 4, cfg.lane_len))
    phase = jax.random.randint(k2, (cfg.n, cfg.n), 0, 2)
    return {"lanes": lanes, "phase": phase, "t": jnp.zeros((), jnp.int32)}


def gs_inflow(out, inject, cfg: TrafficConfig):
    """Wire crossed cars into neighbours. out, inject: (n, n, 4)."""
    n = cfg.n
    z = jnp.zeros((1, n), bool)
    zc = jnp.zeros((n, 1), bool)
    # lane 0 (from N, heading S): inflow[i] = out[i-1]; row 0 injected
    in_n = jnp.concatenate([inject[:1, :, 0], out[:-1, :, 0]], axis=0)
    # lane 2 (from S, heading N): inflow[i] = out[i+1]; row n-1 injected
    in_s = jnp.concatenate([out[1:, :, 2], inject[-1:, :, 2]], axis=0)
    # lane 1 (from E, heading W): inflow[:, j] = out[:, j+1]; col n-1 injected
    in_e = jnp.concatenate([out[:, 1:, 1], inject[:, -1:, 1]], axis=1)
    # lane 3 (from W, heading E): inflow[:, j] = out[:, j-1]; col 0 injected
    in_w = jnp.concatenate([inject[:, :1, 3], out[:, :-1, 3]], axis=1)
    del z, zc
    return jnp.stack([in_n, in_e, in_s, in_w], axis=-1)        # (n, n, 4)


def gs_step_given(state, actions, inject, cfg: TrafficConfig):
    """Deterministic GS step given boundary-injection bits (n, n, 4)."""
    n = cfg.n
    phase = (state["phase"] + actions.reshape(n, n)) % 2
    green = _green(phase)                                      # (n, n, 4)

    lanes = state["lanes"]
    # First pass: who crosses (out bits depend only on pre-step state).
    ahead_free_head = green
    out = lanes[..., -1] & ahead_free_head                     # (n, n, 4)
    inflow = gs_inflow(out, inject, cfg)                       # (n, n, 4)

    step_fn = jax.vmap(jax.vmap(lane_step))
    new_lanes, out2, moved, count = step_fn(lanes, green, inflow)
    # out2 == out by construction (same formula); keep out for wiring.
    del out2

    rewards = (moved / jnp.maximum(count, 1.0)).reshape(-1)
    obs = jax.vmap(jax.vmap(_obs))(new_lanes, phase).reshape(cfg.n_agents, -1)
    u = inflow.reshape(cfg.n_agents, 4).astype(jnp.float32)
    new_state = {"lanes": new_lanes, "phase": phase, "t": state["t"] + 1}
    done = new_state["t"] >= cfg.horizon
    return new_state, obs, rewards, u, done


def gs_exo(key, cfg: TrafficConfig):
    """Exogenous draws: boundary car-injection bits (n, n, 4)."""
    return jax.random.bernoulli(key, cfg.p_in, (cfg.n, cfg.n, 4))


def exo_locals(inject, cfg: TrafficConfig):
    """Per-region restriction of the exogenous draws. Boundary injection
    reaches a region only through its inflow u, so the LS transition
    takes no direct exogenous input."""
    del inject
    return jnp.zeros((cfg.n_agents, 0))


def region_partition(cfg: TrafficConfig, n_blocks: int):
    """Contiguous row bands of the n×n intersection grid. A band's only
    inter-region couplings are the hand-offs to the rows directly above/
    below (adjacent band) and east/west within the band, so one-hop
    block adjacency holds iff bands are whole rows: ``n_blocks`` must
    divide the grid side."""
    if cfg.n % n_blocks:
        raise ValueError(
            f"traffic grid side {cfg.n} cannot split into {n_blocks} "
            f"row bands")
    return contiguous_partition(cfg.n_agents, n_blocks)


def boundary_influence(states, actions, inject, cfg: TrafficConfig):
    """Agent-major restatement of the realized inflow: u (N, 4) from the
    pre-step lanes/phases, the joint actions, and the boundary-injection
    draws. Row (i, j) reads only its grid neighbours' ``out`` bits (plus
    its own injection), so zero rows are inert — an empty lane never
    emits a crossing car."""
    n = cfg.n
    lanes = states["lanes"].reshape(n, n, 4, cfg.lane_len)
    phase = (states["phase"].reshape(n, n) + actions.reshape(n, n)) % 2
    green = _green(phase)                                      # (n, n, 4)
    out = lanes[..., -1].astype(bool) & green
    inflow = gs_inflow(out, inject, cfg)
    return inflow.reshape(cfg.n_agents, 4).astype(jnp.float32)


def gs_step(state, actions, key, cfg: TrafficConfig):
    return gs_step_given(state, actions, gs_exo(key, cfg), cfg)


def gs_obs(state, cfg: TrafficConfig):
    return jax.vmap(jax.vmap(_obs))(state["lanes"], state["phase"]) \
        .reshape(cfg.n_agents, -1)


def gs_locals(state, cfg: TrafficConfig):
    """Per-agent local states (N, ...) for dataset collection."""
    return {"lanes": state["lanes"].reshape(cfg.n_agents, 4, cfg.lane_len),
            "phase": state["phase"].reshape(cfg.n_agents)}


# ---------------------------------------------------------------------------
# Local simulator (one intersection; inflow driven by the AIP)
# ---------------------------------------------------------------------------
def ls_init(key, cfg: TrafficConfig):
    k1, k2 = jax.random.split(key)
    return {"lanes": jax.random.bernoulli(k1, cfg.init_density,
                                          (4, cfg.lane_len)),
            "phase": jax.random.randint(k2, (), 0, 2),
            "t": jnp.zeros((), jnp.int32)}


def ls_step_given(local, action, u, exo, cfg: TrafficConfig):
    """Uniform-protocol alias: the traffic LS takes no direct exogenous
    input (``exo`` is the empty per-region restriction)."""
    del exo
    return ls_step(local, action, u, None, cfg)


def ls_step(local, action, u, key, cfg: TrafficConfig):
    """u: (4,) influence-source bits (sampled from the AIP)."""
    del key
    phase = (local["phase"] + action) % 2
    green = _green(phase)
    new_lanes, _out, moved, count = lane_step(local["lanes"], green,
                                              u.astype(bool))
    reward = moved / jnp.maximum(count, 1.0)
    obs = _obs(new_lanes, phase)
    new = {"lanes": new_lanes, "phase": phase, "t": local["t"] + 1}
    done = new["t"] >= cfg.horizon
    return new, obs, reward, done


def ls_obs(local, cfg: TrafficConfig):
    return _obs(local["lanes"], local["phase"])



def config_from_side(side: int) -> TrafficConfig:
    """The configuration of a ``side`` x ``side`` grid of intersections."""
    return TrafficConfig(n=side)


def cross_chip_mask(cfg, n_shards: int):
    """(N, 1, 1, 4) zeros where an agent's incoming lane [N, E, S, W] is
    fed by a neighbour whose agent block lies on another of ``n_shards``
    chips: what the influence bits of a GS collect hold if the exchange
    between chips is left out (``harness.faults.exchange_dropped``).
    ``cfg`` is any configuration with the grid side ``n``."""
    side = cfg.n
    n = side * side
    block = n // n_shards
    mask = np.ones((n, 4), np.float32)
    for i in range(n):
        r, c = divmod(i, side)
        for lane, (dr, dc) in enumerate(((-1, 0), (0, 1), (1, 0), (0, -1))):
            rr, cc = r + dr, c + dc
            if 0 <= rr < side and 0 <= cc < side and \
                    (rr * side + cc) // block != i // block:
                mask[i, lane] = 0.0
    return jnp.asarray(mask[:, None, None, :])
