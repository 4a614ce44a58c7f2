"""Plain reference copy of the environment protocol's static facts."""
from __future__ import annotations

import dataclasses

import numpy as np


def contiguous_partition(n_agents: int, n_blocks: int) -> np.ndarray:
    if n_agents % n_blocks:
        raise ValueError(f"{n_agents} agents cannot tile {n_blocks} blocks")
    return (np.arange(n_agents) // (n_agents // n_blocks)).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class EnvInfo:
    name: str
    n_agents: int
    obs_dim: int
    n_actions: int
    n_influence: int
    horizon: int
    alsh_dim: int
