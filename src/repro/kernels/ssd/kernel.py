"""Mamba-2 SSD intra-chunk Pallas kernel.

Fuses the per-chunk work of the SSD algorithm — cumulative log-decay,
the (L×L) decay·CBᵀ gating matrix, the masked (L×L)·(L×P) output matmul,
and the (N×L)·(L×P) chunk-state reduction — into one VMEM-resident block.
The (cheap, O(T/L)-step) inter-chunk recurrence and the off-diagonal
correction stay in XLA (``ops.py``), which is the right split on TPU: the
MXU does the L² work; the serial scan is latency-bound either way.

Grid = (B, H, num_chunks). VMEM per step at L=128, P=64, N=128:
x(L·P) + b/c(2·L·N) + decay(L·L) + cb(L·L) + y(L·P) + state(P·N) fp32
≈ 0.36 MB — comfortably double-bufferable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, la_ref, b_ref, c_ref, y_ref, st_ref, cd_ref, *,
                      chunk: int):
    """One (batch, head, chunk) cell.

    x_ref:  (1, L, 1, P)  dt-weighted inputs
    la_ref: (1, L, 1)     per-step log decay (dt·a)
    b_ref:  (1, L, N)     input projection
    c_ref:  (1, L, N)     output projection
    y_ref:  (1, L, 1, P)  intra-chunk output
    st_ref: (1, 1, 1, P, N) chunk-end state contribution
    cd_ref: (1, 1, 1)     total chunk decay exp(cs_L)
    """
    x = x_ref[0, :, 0, :].astype(jnp.float32)            # (L, P)
    la = la_ref[0, :, 0].astype(jnp.float32)             # (L,)
    b = b_ref[0].astype(jnp.float32)                     # (L, N)
    c = c_ref[0].astype(jnp.float32)                     # (L, N)

    cs = jnp.cumsum(la)                                  # (L,)
    seg = cs[:, None] - cs[None, :]                      # (L, L)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = li >= lj
    decay = jnp.where(tri, jnp.exp(seg), 0.0)            # (L, L)

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    y = jax.lax.dot_general(cb * decay, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, P)
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # chunk state: S = Σ_j exp(cs_L - cs_j) b_j x_j^T  -> (P, N)
    w = jnp.exp(cs[-1] - cs)                             # (L,)
    st = jax.lax.dot_general(x, b * w[:, None],
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (P, N)
    st_ref[0, 0, 0] = st
    cd_ref[0, 0, 0] = jnp.exp(cs[-1])


def ssd_intra_chunk(xw, la, b, c, *, chunk: int, interpret: bool = True):
    """xw: (B, T, H, P) dt-weighted inputs; la: (B, T, H) log decays;
    b, c: (B, T, N). Returns (y_diag (B,T,H,P), states (B,nc,H,P,N),
    chunk_decay (B,nc,H), cum_logdecay (B,nc,H,L))."""
    bsz, t, h, p = xw.shape
    n = b.shape[-1]
    nc = t // chunk

    grid = (bsz, h, nc)
    y, st, cd = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda bi, hi, ci: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, hi, ci: (bi, ci, hi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, h, p), xw.dtype),
            jax.ShapeDtypeStruct((bsz, nc, h, p, n), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xw, la, b, c)
    return y, st, cd
