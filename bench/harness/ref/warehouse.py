"""Plain reference copy of the warehouse environment (the program's
``repro.envs.warehouse`` as of the benchmark's first version). The
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
class WarehouseConfig:
    k: int = 2                   # k*k robots
    p_item: float = 0.02
    horizon: int = 100

    @property
    def n_agents(self) -> int:
        return self.k * self.k

    @property
    def grid(self) -> int:       # global grid side
        return 4 * self.k + 1

    def info(self) -> EnvInfo:
        obs_dim = 25 + 12
        return EnvInfo(name="warehouse", n_agents=self.n_agents,
                       obs_dim=obs_dim, n_actions=5, n_influence=12,
                       horizon=self.horizon, alsh_dim=obs_dim + 5)


def item_cells(cfg: WarehouseConfig) -> np.ndarray:
    """(N, 12, 2) absolute coords of each region's item cells.
    Order: north shelf (3), east (3), south (3), west (3)."""
    cells = np.zeros((cfg.n_agents, 12, 2), np.int32)
    for i in range(cfg.k):
        for j in range(cfg.k):
            r0, c0 = 4 * i, 4 * j
            cs = ([(r0, c0 + d) for d in (1, 2, 3)] +          # north
                  [(r0 + d, c0 + 4) for d in (1, 2, 3)] +      # east
                  [(r0 + 4, c0 + d) for d in (1, 2, 3)] +      # south
                  [(r0 + d, c0) for d in (1, 2, 3)])           # west
            cells[i * cfg.k + j] = np.array(cs, np.int32)
    return cells


def region_origin(cfg: WarehouseConfig) -> np.ndarray:
    """(N, 2) top-left corner of each region."""
    out = np.zeros((cfg.n_agents, 2), np.int32)
    for i in range(cfg.k):
        for j in range(cfg.k):
            out[i * cfg.k + j] = (4 * i, 4 * j)
    return out


_MOVES = np.array([[0, 0], [-1, 0], [0, 1], [1, 0], [0, -1]], np.int32)


# ---------------------------------------------------------------------------
# Shared per-region transition (the \dot{T}_i of the IALM)
# ---------------------------------------------------------------------------
def region_step(pos, ages, action, u, spawn):
    """One region for one step, in LOCAL coordinates.

    pos: (2,) robot position in [0,4]²; ages: (12,) item ages (0 = empty);
    action: () in [0,5); u: (12,) bool — another robot on item cell c;
    spawn: (12,) bool — item-appearance draws for this step.

    Returns (new_pos, new_ages, reward, on_item (12,) bool self-occupancy).
    """
    move = jnp.asarray(_MOVES)[action]
    new_pos = jnp.clip(pos + move, 0, 4)

    # local coords of the 12 item cells (same for every region)
    local_cells = jnp.asarray(
        [[0, 1], [0, 2], [0, 3], [1, 4], [2, 4], [3, 4],
         [4, 1], [4, 2], [4, 3], [1, 0], [2, 0], [3, 0]], jnp.int32)
    on_item = jnp.all(local_cells == new_pos[None, :], axis=1)   # (12,)

    active = ages > 0
    max_age = jnp.maximum(jnp.max(ages), 1).astype(jnp.float32)
    collected_self = on_item & active
    reward = jnp.sum(jnp.where(collected_self,
                               ages.astype(jnp.float32) / max_age, 0.0))

    removed = active & (on_item | u.astype(bool))
    ages = jnp.where(removed, 0, ages)
    ages = jnp.where(ages > 0, ages + 1, ages)                  # age
    ages = jnp.where((ages == 0) & spawn.astype(bool), 1, ages)  # spawn
    return new_pos, ages, reward, on_item


def _obs(pos, ages):
    pos_oh = jnp.zeros((5, 5), jnp.float32).at[pos[0], pos[1]].set(1.0)
    return jnp.concatenate([pos_oh.reshape(-1),
                            (ages > 0).astype(jnp.float32)])


# ---------------------------------------------------------------------------
# Global simulator
# ---------------------------------------------------------------------------
def gs_init(key, cfg: WarehouseConfig):
    k1, k2 = jax.random.split(key)
    pos = jax.random.randint(k1, (cfg.n_agents, 2), 0, 5)       # local coords
    cells = jnp.asarray(item_cells(cfg))
    g = cfg.grid
    spawn0 = jax.random.bernoulli(k2, 0.2, (g, g))
    shelf = jnp.zeros((g, g), bool)
    shelf = shelf.at[cells[..., 0].reshape(-1), cells[..., 1].reshape(-1)] \
        .set(True)
    ages = jnp.where(shelf & spawn0, 1, 0).astype(jnp.int32)
    return {"pos": pos, "ages": ages, "t": jnp.zeros((), jnp.int32)}


def _abs_pos(pos, cfg: WarehouseConfig):
    return pos + jnp.asarray(region_origin(cfg))                # (N, 2)


def gs_influence(pos, cfg: WarehouseConfig):
    """u (N, 12): another robot sits on region i's item cell c.
    Computed from CURRENT (post-move) absolute positions."""
    cells = jnp.asarray(item_cells(cfg))                        # (N, 12, 2)
    ap = _abs_pos(pos, cfg)                                     # (N, 2)
    same = jnp.all(cells[:, :, None, :] == ap[None, None, :, :], axis=-1)
    # exclude the region's own robot
    own = jnp.eye(cfg.n_agents, dtype=bool)[:, None, :]
    return jnp.any(same & ~own, axis=-1)                        # (N, 12)


def gs_step_given(state, actions, spawn_grid, cfg: WarehouseConfig):
    """spawn_grid: (G, G) bool item-appearance draws."""
    n = cfg.n_agents
    cells = jnp.asarray(item_cells(cfg))                        # (N, 12, 2)

    # 1. all robots move (region_step handles the local move; here we move
    #    globally first to compute the influence bits all regions agree on).
    move = jnp.asarray(_MOVES)[actions]
    new_pos = jnp.clip(state["pos"] + move, 0, 4)
    u = gs_influence(new_pos, cfg)                              # (N, 12)

    # 2. per-region transitions on region-local views of the item grid.
    region_ages = state["ages"][cells[..., 0], cells[..., 1]]   # (N, 12)
    spawn = spawn_grid[cells[..., 0], cells[..., 1]]            # (N, 12)
    rp, ra, rewards, on_item = jax.vmap(region_step)(
        state["pos"], region_ages, actions, u, spawn)
    assert rp.shape == new_pos.shape

    # 3. write back: shared cells receive identical values from both owners
    #    (same u/spawn/ages inputs), so scatter order is irrelevant.
    ages = state["ages"].at[cells[..., 0].reshape(-1),
                            cells[..., 1].reshape(-1)] \
        .set(ra.reshape(-1), mode="drop")

    obs = jax.vmap(_obs)(rp, ra)
    new_state = {"pos": rp, "ages": ages, "t": state["t"] + 1}
    done = new_state["t"] >= cfg.horizon
    return new_state, obs, rewards, u.astype(jnp.float32), done


def gs_exo(key, cfg: WarehouseConfig):
    """Exogenous draws: item-appearance bits on the global grid (G, G)."""
    g = cfg.grid
    return jax.random.bernoulli(key, cfg.p_item, (g, g))


def exo_locals(spawn_grid, cfg: WarehouseConfig):
    """Per-region restriction: each region's 12 item-cell spawn bits."""
    cells = jnp.asarray(item_cells(cfg))
    return spawn_grid[cells[..., 0], cells[..., 1]]          # (N, 12)


def region_partition(cfg: WarehouseConfig, n_blocks: int):
    """Contiguous row bands of the k×k region grid. Robots are confined
    to their own 5×5 region and shelves are shared only with 4-adjacent
    regions (diagonals can never reach a neighbour's item cells), so
    one-hop block adjacency holds iff bands are whole region rows:
    ``n_blocks`` must divide k."""
    if cfg.k % n_blocks:
        raise ValueError(
            f"warehouse region grid side {cfg.k} cannot split into "
            f"{n_blocks} row bands")
    return contiguous_partition(cfg.n_agents, n_blocks)


def boundary_influence(states, actions, spawn_grid, cfg: WarehouseConfig):
    """Agent-major restatement of the occupancy influence: u (N, 12)
    from post-move absolute positions. Zero rows are inert — a zeroed
    robot sits on its own region's corner, and corners (both coords ≡ 0
    mod 4) are never item cells (exactly one coord ≡ 0 mod 4)."""
    del spawn_grid
    move = jnp.asarray(_MOVES)[actions]
    new_pos = jnp.clip(states["pos"] + move, 0, 4)
    return gs_influence(new_pos, cfg).astype(jnp.float32)


def gs_step(state, actions, key, cfg: WarehouseConfig):
    return gs_step_given(state, actions, gs_exo(key, cfg), cfg)


def gs_obs(state, cfg: WarehouseConfig):
    cells = jnp.asarray(item_cells(cfg))
    region_ages = state["ages"][cells[..., 0], cells[..., 1]]
    return jax.vmap(_obs)(state["pos"], region_ages)


def gs_locals(state, cfg: WarehouseConfig):
    cells = jnp.asarray(item_cells(cfg))
    return {"pos": state["pos"],
            "ages": state["ages"][cells[..., 0], cells[..., 1]]}


# ---------------------------------------------------------------------------
# Local simulator
# ---------------------------------------------------------------------------
def ls_init(key, cfg: WarehouseConfig):
    k1, k2 = jax.random.split(key)
    return {"pos": jax.random.randint(k1, (2,), 0, 5),
            "ages": jnp.where(jax.random.bernoulli(k2, 0.2, (12,)), 1, 0)
            .astype(jnp.int32),
            "t": jnp.zeros((), jnp.int32)}


def ls_step(local, action, u, key, cfg: WarehouseConfig):
    spawn = jax.random.bernoulli(key, cfg.p_item, (12,))
    return ls_step_given(local, action, u, spawn, cfg)


def ls_step_given(local, action, u, spawn, cfg: WarehouseConfig):
    pos, ages, reward, _ = region_step(local["pos"], local["ages"],
                                       action, u, spawn)
    new = {"pos": pos, "ages": ages, "t": local["t"] + 1}
    done = new["t"] >= cfg.horizon
    return new, _obs(pos, ages), reward, done


def ls_obs(local, cfg: WarehouseConfig):
    return _obs(local["pos"], local["ages"])



def config_from_side(side: int) -> WarehouseConfig:
    """The configuration of a ``side`` x ``side`` grid of robots."""
    return WarehouseConfig(k=side)
