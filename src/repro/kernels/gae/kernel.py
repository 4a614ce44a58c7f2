"""GAE-λ reverse-scan Pallas kernels.

Forward: the advantage recursion is strictly sequential in t but
embarrassingly parallel over the (agents × envs) batch. Each block is a
whole ``(T, bt)`` time-major slab — batch on the 128-wide lanes, time on
the sublanes — and the kernel walks it T-1→0 with an in-kernel
``fori_loop`` over single-row slices, the carry riding in vregs. One
fused multiply-add per step instead of a scan of tiny XLA kernels.
Blocks are the full array dims when the batch fits the VMEM budget, else
128-multiple lane tiles over a ``"parallel"`` grid axis (the batch is
zero-padded to a whole number of tiles), so the block layout always
meets the TPU's (8, 128) tiling rule.

Backward: the recursion is LINEAR in (r, v, nv), so the adjoint is the
transposed recurrence — a FORWARD-time scan of the advantage cotangent
ā_t = g_t + γλ(1-d_{t-1})·ā_{t-1}, from which every input cotangent is
elementwise: dr = ā, dv = -ā, dnv = γ(1-d)·ā. :func:`gae_reverse_scan`
carries a ``jax.custom_vjp`` running that adjoint as a second Pallas
kernel (no residuals beyond the dones mask).

The two kernels are named ``gae_fwd`` and ``gae_bwd``; a profile shows
the names in their operations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import batch_major

# Scoped VMEM the double-buffered blocks of one grid step may take: half
# of the TPU v5e's 16 MiB default scoped limit.
_VMEM_BUDGET = 8 * 2**20


def _lane_tile(t: int, b: int, n_arrays: int) -> int:
    """Block width over the batch: all of B while ``n_arrays``
    double-buffered (T, B) f32 blocks fit the budget, else the widest
    multiple of 128 lanes that does."""
    per_lane = 2 * n_arrays * t * 4
    if b * per_lane <= _VMEM_BUDGET:
        return b
    return max(128, _VMEM_BUDGET // per_lane // 128 * 128)


def _lane_tiled_call(kernel, inputs, n_out: int, interpret: bool,
                     name: str):
    """Run ``kernel`` over (T, B) f32 ``inputs`` in (T, bt) blocks, one
    "parallel" grid step per lane tile; returns ``n_out`` (T, B) outputs."""
    inputs = batch_major(*inputs)
    t, b = inputs[0].shape
    bt = _lane_tile(t, b, len(inputs) + n_out)
    nb = -(-b // bt)
    pad = nb * bt - b
    if pad:
        inputs = [jnp.pad(x, ((0, 0), (0, pad))) for x in inputs]
    spec = pl.BlockSpec((t, bt), lambda j: (0, j))
    outs = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[spec] * len(inputs),
        out_specs=[spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((t, nb * bt), jnp.float32)] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=name,
    )(*inputs)
    return [o[:, :b] for o in outs] if pad else list(outs)


def _gae_kernel(r_ref, v_ref, nv_ref, d_ref, adv_ref, *,
                gamma: float, lam: float):
    t_len = r_ref.shape[0]

    def step(i, carry):                                     # carry (1, bt)
        row = pl.ds(t_len - 1 - i, 1)                       # reverse time
        r, v, nv, d = r_ref[row, :], v_ref[row, :], nv_ref[row, :], \
            d_ref[row, :]
        nd = 1.0 - d
        delta = r + gamma * nv * nd - v
        adv = delta + gamma * lam * nd * carry
        adv_ref[row, :] = adv
        return adv

    jax.lax.fori_loop(0, t_len, step,
                      jnp.zeros((1, r_ref.shape[1]), jnp.float32))


def _gae_forward(rewards, values, next_values, dones, *,
                 gamma: float, lam: float, interpret: bool):
    (adv,) = _lane_tiled_call(
        functools.partial(_gae_kernel, gamma=gamma, lam=lam),
        [rewards, values, next_values, dones], 1, interpret, "gae_fwd")
    return adv


def _gae_bwd_kernel(g_ref, d_ref, dr_ref, dnv_ref, *,
                    gamma: float, lam: float):
    """Adjoint scan, forward in time. carry holds γλ(1-d_{t-1})·ā_{t-1}."""

    def step(t, carry):
        row = pl.ds(t, 1)
        g, d = g_ref[row, :], d_ref[row, :]
        nd = 1.0 - d
        abar = g + carry
        dr_ref[row, :] = abar
        dnv_ref[row, :] = gamma * nd * abar
        return gamma * lam * nd * abar

    jax.lax.fori_loop(0, g_ref.shape[0], step,
                      jnp.zeros((1, g_ref.shape[1]), jnp.float32))


def _gae_backward(g, dones, *, gamma: float, lam: float, interpret: bool):
    return _lane_tiled_call(
        functools.partial(_gae_bwd_kernel, gamma=gamma, lam=lam),
        [g, dones], 2, interpret, "gae_bwd")


@functools.lru_cache(maxsize=None)
def _gae_scan_with_vjp(gamma: float, lam: float, interpret: bool):
    @jax.custom_vjp
    def scan_fn(rewards, values, next_values, dones):
        return _gae_forward(rewards, values, next_values, dones,
                            gamma=gamma, lam=lam, interpret=interpret)

    def fwd(rewards, values, next_values, dones):
        adv = _gae_forward(rewards, values, next_values, dones,
                           gamma=gamma, lam=lam, interpret=interpret)
        return adv, dones

    def bwd(dones, g):
        dr, dnv = _gae_backward(g, dones, gamma=gamma, lam=lam,
                                interpret=interpret)
        return dr, -dr, dnv, jnp.zeros_like(dones)

    scan_fn.defvjp(fwd, bwd)
    return scan_fn


def gae_reverse_scan(rewards, values, next_values, dones, *,
                     gamma: float, lam: float, interpret: bool = True):
    """All inputs (T, B) fp32, time-major. Returns advantages (T, B).
    Differentiable w.r.t. (rewards, values, next_values) through the
    linear-adjoint Pallas kernel; dones get a zero cotangent."""
    return _gae_scan_with_vjp(float(gamma), float(lam), bool(interpret))(
        rewards, values, next_values, dones)
