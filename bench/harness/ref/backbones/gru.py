"""The GRU backbone: a GRU of ``gru_hidden`` units after the MLP trunk
(``repro.nn.gru``, jnp path, as of the benchmark's first version), its
parameters under the tree's ``"gru"`` key; and the operations and bytes
of the recurrence kernels ``gru_fwd`` / ``gru_bwd``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nets

F32 = 4


def gru_init(key, din, hidden):
    ki, kh = jax.random.split(key)
    return {"wi": nets.fan_in_normal(ki, (din, 3 * hidden)),
            "wh": nets.orthogonal(kh, (hidden, 3 * hidden)),
            "bi": jnp.zeros((3 * hidden,), jnp.float32),
            "bh": jnp.zeros((3 * hidden,), jnp.float32)}


def _dot(x, w):
    y = jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def gru_cell(p, h, x):
    gi = _dot(x, p["wi"]) + p["bi"].astype(x.dtype)
    gh = _dot(h, p["wh"]) + p["bh"].astype(h.dtype)
    i_r, i_z, i_n = jnp.split(gi, 3, axis=-1)
    h_r, h_z, h_n = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid((i_r + h_r).astype(jnp.float32))
    z = jax.nn.sigmoid((i_z + h_z).astype(jnp.float32))
    n = jnp.tanh((i_n + r * h_n).astype(jnp.float32))
    new_h = (1.0 - z) * n + z * h.astype(jnp.float32)
    return new_h.astype(h.dtype)


def gru_sequence(p, xs, h0, reset_mask):
    def step(h, inp):
        x, m = inp
        h = h * (1.0 - m[:, None].astype(h.dtype))
        h = gru_cell(p, h, x)
        return h, h

    xs_t = jnp.swapaxes(xs, 0, 1)
    ms_t = jnp.swapaxes(reset_mask, 0, 1).astype(xs.dtype)
    h_last, hs = jax.lax.scan(step, h0, (xs_t, ms_t))
    return jnp.swapaxes(hs, 0, 1), h_last


# -- the backbone's interface (``backbones/__init__.py``) ----------------------
def init(key, din, net):
    return {"gru": gru_init(key, din, net["gru_hidden"])}, net["gru_hidden"]


def hidden0(net, batch, dtype):
    return jnp.zeros(tuple(batch) + (net["gru_hidden"],), dtype)


def step(params, x, h, net):
    del net
    flat = x.reshape(-1, x.shape[-1])
    h = gru_cell(params["gru"], h.reshape(-1, h.shape[-1]), flat) \
        .reshape(h.shape)
    return h, h


def sequence(params, xs, h0, resets, net):
    del net
    return gru_sequence(params["gru"], xs, h0, resets)[0]


def fwd_flops(din, net):
    h = net["gru_hidden"]
    return 2 * din * 3 * h + 2 * h * 3 * h, h


def kernel_ops(net, rows, steps, backward):
    h = net["gru_hidden"]
    ops = [gru_fwd(rows, steps, h)]
    if backward:
        ops.append(gru_bwd(rows, steps, h))
    return ops


# -- kernel operations ------------------------------------------------------------
def gru_fwd(b: int, t: int, h: int):
    """(flops, bytes) of the GRU recurrence over b rows and t steps with
    hidden size h: per row and step gh = h_prev @ W_h (2*h*3h) and ~14h
    elementwise (gates, reset mask, update); reads gi (3h), the reset
    flag, W_h, b_h, h0 and writes hs (h)."""
    flops = b * t * (6.0 * h * h + 14.0 * h)
    bytes_ = F32 * (b * t * (3 * h + 1 + h) + 3 * h * h + 3 * h + b * h)
    return flops, bytes_


def gru_bwd(b: int, t: int, h: int):
    """(flops, bytes) of the recurrence's backward: per row and step the
    two adjoint matmuls dh_prev = dgh @ W_h^T and dW_h += h_prev^T dgh
    (2 x 2*h*3h) and ~30h elementwise; the forward it recomputes does not
    count. Reads gi, h_prev, the cotangent, the reset flag and W_h, b_h;
    writes dgi (3h per row and step), dW_h, db_h and dh0."""
    flops = b * t * (12.0 * h * h + 30.0 * h)
    bytes_ = F32 * (b * t * (3 * h + h + h + 1 + 3 * h)
                    + 2 * (3 * h * h + 3 * h) + b * h)
    return flops, bytes_
