"""GRU sequence Pallas kernels — the AIP / recurrent-policy hot spot.

Forward: the input-side gate matmul (x_t · W_i for all t) is one big
MXU-friendly batched matmul done OUTSIDE the kernel by XLA. The kernel
fuses what XLA handles poorly: the strictly sequential per-step recurrent
matmul h·W_h (B×H · H×3H on the MXU) plus the gate nonlinearities and
state update, keeping h and W_h resident in VMEM across all T steps
(grid iterates over T with "arbitrary" semantics; h lives in scratch,
W_h is re-fetched from the same block every step so it stays cached).

Backward: :func:`gru_scan` carries a ``jax.custom_vjp`` whose reverse
pass is a second Pallas kernel walking the grid T-1→0 (reverse-indexed
BlockSpec maps). Gates are RECOMPUTED from the saved forward inputs and
hidden states rather than stashed — one extra h·W_h per step buys not
materialising (r, z, n) for all T. The adjoint carry dh, the weight
accumulator dW_h, and the bias accumulator db_h all stay resident in
VMEM across the whole scan; per-step gate gradients stream out as dgi,
which XLA then turns into dx/dW_i through the outer matmul's own VJP.

The biases ride as (1, 3H) rows inside this module — a 1-D block
breaks the TPU's (8, 128) tiling rule once ``vmap`` adds the agent axis —
while :func:`gru_scan` keeps the public (3H,) shapes.

VMEM at B=256, H=128: h(B·H) + gi(B·3H) + Wh(H·3H) fp32 ≈ 0.7 MB
forward; backward adds the dWh/dbh accumulators (+0.2 MB).

The two kernels are named ``gru_fwd`` and ``gru_bwd``; a profile shows
the names in their operations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import batch_major


def _gates(gi, gh, hdim):
    """Shared gate math: returns (r, z, n) from input/recurrent halves."""
    i_r, i_z, i_n = gi[:, :hdim], gi[:, hdim:2 * hdim], gi[:, 2 * hdim:]
    h_r, h_z, h_n = gh[:, :hdim], gh[:, hdim:2 * hdim], gh[:, 2 * hdim:]
    r = jax.nn.sigmoid(i_r + h_r)
    z = jax.nn.sigmoid(i_z + h_z)
    n = jnp.tanh(i_n + r * h_n)
    return r, z, n, h_n


def _gru_kernel(gi_ref, wh_ref, bh_ref, reset_ref, h0_ref, hs_ref, h_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = h0_ref[...]

    h = h_ref[...]                                        # (B, H)
    m = reset_ref[0]                                      # (B, 1)
    h = h * (1.0 - m)
    gh = jax.lax.dot_general(h, wh_ref[...], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        + bh_ref[...]                                     # (B, 3H)
    r, z, n, _h_n = _gates(gi_ref[0], gh, h.shape[-1])
    new_h = (1.0 - z) * n + z * h
    h_ref[...] = new_h
    hs_ref[0] = new_h.astype(hs_ref.dtype)


def _gru_forward(gi, wh, bh, h0, resets, interpret: bool):
    t, bsz, h3 = gi.shape
    gi, wh, bh, h0, resets = batch_major(gi, wh, bh.reshape(1, h3), h0,
                                         resets)
    hdim = h3 // 3
    return pl.pallas_call(
        _gru_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, bsz, h3), lambda ti: (ti, 0, 0)),
            pl.BlockSpec((hdim, h3), lambda ti: (0, 0)),
            pl.BlockSpec((1, h3), lambda ti: (0, 0)),
            pl.BlockSpec((1, bsz, 1), lambda ti: (ti, 0, 0)),
            pl.BlockSpec((bsz, hdim), lambda ti: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bsz, hdim), lambda ti: (ti, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, bsz, hdim), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bsz, hdim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gru_fwd",
    )(gi, wh, bh, resets, h0)


def _gru_bwd_kernel(gi_ref, hprev_ref, reset_ref, wh_ref, bh_ref, g_ref,
                    dgi_ref, dwh_ref, dbh_ref, dh0_ref, dh_ref):
    """One reverse-time step: grid index t visits actual time T-1-t
    (through the BlockSpec index maps). dh_ref carries the hidden-state
    adjoint; dwh/dbh accumulate in their (constant-index) output blocks.
    """
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dwh_ref[...] = jnp.zeros_like(dwh_ref)
        dbh_ref[...] = jnp.zeros_like(dbh_ref)

    m = reset_ref[0]                                      # (B, 1)
    hp = hprev_ref[0] * (1.0 - m)                         # masked h_{t-1}
    gh = jax.lax.dot_general(hp, wh_ref[...], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        + bh_ref[...]
    r, z, n, h_n = _gates(gi_ref[0], gh, hp.shape[-1])

    d = g_ref[0] + dh_ref[...]          # total adjoint on h_t
    dn = d * (1.0 - z)
    dz = d * (hp - n)
    dhp = d * z
    da_n = dn * (1.0 - n * n)
    dr = da_n * h_n
    da_z = dz * z * (1.0 - z)
    da_r = dr * r * (1.0 - r)
    dgi_ref[0] = jnp.concatenate([da_r, da_z, da_n], axis=-1)
    dgh = jnp.concatenate([da_r, da_z, da_n * r], axis=-1)
    dhp = dhp + jax.lax.dot_general(
        dgh, wh_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dwh_ref[...] += jax.lax.dot_general(
        hp, dgh, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dbh_ref[...] += dgh.sum(axis=0, keepdims=True)
    dh_ref[...] = dhp * (1.0 - m)       # adjoint on h_{t-1}

    @pl.when(t == nt - 1)
    def _final():
        dh0_ref[...] = dh_ref[...]


def _gru_backward(gi, wh, bh, h0, resets, hs, g, interpret: bool):
    t, bsz, h3 = gi.shape
    hdim = h3 // 3
    # h_{t-1} for every step: [h0, hs[0], ..., hs[T-2]]
    hprev = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    gi, hprev, resets, wh, bh, g = batch_major(
        gi, hprev, resets, wh, bh.reshape(1, h3), g)
    rev3 = lambda ti: (t - 1 - ti, 0, 0)
    const2 = lambda ti: (0, 0)
    return pl.pallas_call(
        _gru_bwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, bsz, h3), rev3),             # gi
            pl.BlockSpec((1, bsz, hdim), rev3),           # hprev
            pl.BlockSpec((1, bsz, 1), rev3),              # resets
            pl.BlockSpec((hdim, h3), const2),             # wh
            pl.BlockSpec((1, h3), const2),                # bh
            pl.BlockSpec((1, bsz, hdim), rev3),           # g (dL/dhs)
        ],
        out_specs=[
            pl.BlockSpec((1, bsz, h3), rev3),             # dgi
            pl.BlockSpec((hdim, h3), const2),             # dwh
            pl.BlockSpec((1, h3), const2),                # dbh
            pl.BlockSpec((bsz, hdim), const2),            # dh0
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, bsz, h3), jnp.float32),
            jax.ShapeDtypeStruct((hdim, h3), jnp.float32),
            jax.ShapeDtypeStruct((1, h3), jnp.float32),
            jax.ShapeDtypeStruct((bsz, hdim), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bsz, hdim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gru_bwd",
    )(gi, hprev, resets, wh, bh, g)


@functools.lru_cache(maxsize=None)
def _gru_scan_with_vjp(interpret: bool):
    """Build the differentiable scan once per interpret flag — the flag
    never enters a jit static argument, so there is exactly one compile
    per (shape, interpret) pair process-wide."""

    @jax.custom_vjp
    def scan_fn(gi, wh, bh, h0, resets):
        return _gru_forward(gi, wh, bh, h0, resets, interpret)

    def fwd(gi, wh, bh, h0, resets):
        hs = _gru_forward(gi, wh, bh, h0, resets, interpret)
        return hs, (gi, wh, bh, h0, resets, hs)

    def bwd(res, g):
        gi, wh, bh, h0, resets, hs = res
        dgi, dwh, dbh, dh0 = _gru_backward(
            gi, wh, bh, h0, resets, hs, g, interpret)
        return dgi, dwh, dbh.reshape(bh.shape), dh0, jnp.zeros_like(resets)

    scan_fn.defvjp(fwd, bwd)
    return scan_fn


def gru_scan(gi, wh, bh, h0, resets, *, interpret: bool = True):
    """gi: (T, B, 3H) precomputed x·W_i + b_i (fp32); wh: (H, 3H);
    bh: (3H,); h0: (B, H); resets: (T, B, 1). Returns hs (T, B, H).
    Differentiable w.r.t. (gi, wh, bh, h0) through the Pallas backward
    kernel; resets receive a zero cotangent (they are data, not weights).
    """
    return _gru_scan_with_vjp(bool(interpret))(gi, wh, bh, h0, resets)
