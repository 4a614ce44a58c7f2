"""Flash attention (Dao et al.) — Pallas TPU kernel.

Blockwise online-softmax attention. Grid = (batch·heads, num_q_blocks,
num_k_blocks); the k dimension is the innermost, sequentially-iterated
("arbitrary") axis, carrying the running max / normalizer / accumulator in
VMEM scratch — the canonical TPU flash pattern. Block shapes default to
(128, 128): MXU-aligned on both matmul dims, and the VMEM working set is
q(128·D) + k(128·D) + v(128·D) + acc(128·D) fp32 ≈ 0.4 MB at D=128, far
under the ~16 MB/core budget, leaving room for double buffering.

GQA is handled in the index maps: the kv grid row is h // group — repeated
K/V heads are never materialized. Causal and sliding-window masks skip
fully-masked k-blocks with ``pl.when`` (no MXU work issued).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, sliding_window: Optional[int],
                 softcap: Optional[float], block_q: int, block_k: int,
                 num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # Is any element of this (q-block, k-block) pair unmasked?
    q_max = qi * block_q + block_q - 1
    k_min = ki * block_k
    relevant = True
    if causal:
        relevant = jnp.logical_and(relevant, k_min <= q_max)
    if sliding_window is not None:
        k_max = ki * block_k + block_k - 1
        q_min = qi * block_q
        relevant = jnp.logical_and(relevant, k_max > q_min - sliding_window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                     # (bq, D)
        k = k_ref[0].astype(jnp.float32)                     # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= k_pos <= q_pos
        if sliding_window is not None:
            mask &= k_pos > q_pos - sliding_window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # (bq,)
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        v = v_ref[0].astype(jnp.float32)                     # (bk, D)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]) \
            .astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         sliding_window: Optional[int] = None,
                         softcap: Optional[float] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = True):
    """q: (BH, Tq, D); k, v: (BH_kv, Tk, D) with BH = BH_kv · group.

    The caller flattens batch×heads; GQA group = BH // BH_kv.
    """
    bh, tq, d = q.shape
    bh_kv, tk, _ = k.shape
    group = bh // bh_kv
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    nq = pl.cdiv(tq, block_q)
    nk = pl.cdiv(tk, block_k)
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal,
        sliding_window=sliding_window, softcap=softcap,
        block_q=block_q, block_k=block_k, num_k_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki, g=group: (b // g, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki, g=group: (b // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
