"""The FNN backbone: nothing after the MLP trunk (``repro.marl.policy``,
``repro.core.influence``). The program still carries a zero state of
``gru_hidden`` units through every step, which nothing reads."""
from __future__ import annotations

import jax.numpy as jnp


def init(key, din, net):
    del key, net
    return {}, din


def hidden0(net, batch, dtype):
    return jnp.zeros(tuple(batch) + (net["gru_hidden"],), dtype)


def step(params, x, h, net):
    del params, net
    return x, h


def sequence(params, xs, h0, resets, net):
    del params, h0, resets, net
    return xs


def fwd_flops(din, net):
    del net
    return 0, din


def kernel_ops(net, rows, steps, backward):
    del net, rows, steps, backward
    return []
