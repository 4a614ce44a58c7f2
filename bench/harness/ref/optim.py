"""Plain reference Adam and global-norm clipping (copies of the program's
``repro.optim.adamw`` without weight decay, and ``repro.optim.clip``).
Moments and master weights are float32 whatever the parameters' type."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def adam_init(params):
    f32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"mu": jax.tree.map(f32, params), "nu": jax.tree.map(f32, params),
            "master": jax.tree.map(
                lambda p: jnp.array(p, dtype=jnp.float32, copy=True), params),
            "step": jnp.zeros((), jnp.int32)}


def adam_update(grads, state, lr, *, b1, b2, eps=1e-8):
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def upd(g, mu, nu, m):
        g = g.astype(jnp.float32)
        mu = b1 * mu + (1.0 - b1) * g
        nu = b2 * nu + (1.0 - b2) * jnp.square(g)
        return mu, nu, m - lr * ((mu / c1) / (jnp.sqrt(nu / c2) + eps))

    treedef = jax.tree.structure(state["mu"])
    out = [upd(*xs) for xs in zip(jax.tree.leaves(grads),
                                  jax.tree.leaves(state["mu"]),
                                  jax.tree.leaves(state["nu"]),
                                  jax.tree.leaves(state["master"]))]
    mu, nu, master = (jax.tree.unflatten(treedef, [o[i] for o in out])
                      for i in range(3))
    return master, {"mu": mu, "nu": nu, "master": master, "step": step}


def cast_like(master, params):
    return jax.tree.map(lambda m, p: m.astype(p.dtype), master, params)


def clip_by_global_norm(tree, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(
        lambda x: (x.astype(jnp.float32) * scale).astype(x.dtype), tree)
