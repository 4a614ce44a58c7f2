"""GRU sequence Pallas kernels — the AIP / recurrent-policy hot spot.

Forward: the input-side gate matmul (x_t · W_i for all t) is one big
MXU-friendly batched matmul done OUTSIDE the kernel by XLA. The kernel
fuses what XLA handles poorly: the strictly sequential per-step recurrent
matmul h·W_h (B×H · H×3H on the MXU) plus the gate nonlinearities and
state update, keeping h and W_h resident in VMEM across all T steps
(time is the inner "arbitrary" grid axis; h lives in scratch, W_h's
block index does not change with time, so it is fetched once).

Backward: :func:`gru_scan` carries a ``jax.custom_vjp`` whose reverse
pass is a second Pallas kernel walking time T-1→0 (reverse-indexed
BlockSpec maps). Gates are RECOMPUTED from the saved forward inputs and
hidden states rather than stashed — one extra h·W_h per step buys not
materialising (r, z, n) for all T. The adjoint carry dh, the weight
accumulator dW_h, and the bias accumulator db_h all stay resident in
VMEM across the whole scan; per-step gate gradients stream out as dgi,
which XLA then turns into dx/dW_i through the outer matmul's own VJP.

Agent blocks. DIALS runs one GRU per agent under ``vmap``. Both
launchers take a leading agent axis A and sit inside a
``jax.custom_batching.custom_vmap`` (under the ``custom_vjp``) whose rule
folds a ``vmap``'s axis into A, from whichever ``in_axes`` it came; a
nested ``vmap`` folds again. (``pallas_call``'s own batching rule would
put the axis in front of the grid: one agent per grid step.) An
unbatched call is the A=1 case. The blocks keep the agent axis:

  gi, dgi          (A_blk, 1, B, 3H)   one time step of A_blk agents
  hs, hprev, g     (A_blk, 1, B, H)
  resets           (A_blk, 1, B, 1)
  wh, dwh          (A_blk, H, 3H)      one block index per agent block
  bh, dbh          (A_blk, 1, 3H)
  h0, dh0          (A_blk, B, H); scratch h / dh (A_blk, B, H)

The grid is (ceil(A / A_blk), T): agent blocks outer, time inner. A
grid step issues A_blk independent per-agent matmuls, as one
``dot_general`` batched over the block's agent axis with each agent's
contraction unchanged, so a grid step's fixed cost is paid once a
block, not once an agent.

A_blk (:func:`agent_block`) is the most agents whose double-buffered
blocks, each tile padded to (8, 128) f32, fit ``_VMEM_BUDGET``;
A is then split into the fewest such blocks, of equal size, and
zero-padded to whole blocks (zero agents stay zero). At warehouse side
10 (A=100):

  AIP, B=7, H=64       fwd 188 KiB an agent -> 3 blocks of 34 (102)
                       bwd 356 KiB an agent -> 5 blocks of 20
  policy, B=4, H=128   fwd 460 KiB an agent -> 6 blocks of 17 (102)
                       bwd 900 KiB an agent -> 12 blocks of 9 (108)

(W_h alone is 64 KiB an agent at H=64 and 192 KiB at H=128 a buffer:
all 100 agents' policy W_h, 19.7 MB, would not fit one step.)

The biases ride as (1, 3H) rows inside this module — a 1-D block
breaks the TPU's (8, 128) tiling rule — while :func:`gru_scan` keeps the
public (3H,) shapes.

The two kernels are named ``gru_fwd`` and ``gru_bwd``; a profile shows
the names in their operations. Their operands keep ``gi`` first and
the launch signatures 5 -> 1 (forward) and 6 -> 4 (backward).
:func:`launch_stats` tallies the agent-blocked launches traced.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM the double-buffered blocks of one grid step may take: half
# of the TPU v5e's 16 MiB default scoped limit (the rest holds the
# step's intermediates).
_VMEM_BUDGET = 8 * 2**20

# (kernel, A, A_blk, grid steps) -> launches the vmap rule traced
_LAUNCHES: collections.Counter = collections.Counter()
_last_launch = None         # the key of the launch traced last
_rules_run = 0              # vmap rule calls so far


def launch_stats() -> dict:
    """Trace-time tally of the GRU launches that ``vmap`` folded into
    agent blocks: ``{(kernel, A, A_blk, grid steps): count}``. A launch
    that a further ``vmap`` folds again counts once, at its final A. An
    unbatched call is not counted."""
    return dict(_LAUNCHES)


def _tile_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of one agent's (rows, cols) f32 block slice, padded to
    whole (8, 128) tiles."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def agent_block(n_agents: int, agent_bytes: int) -> int:
    """Agents a grid step advances: ``n_agents`` split into the fewest
    equal blocks whose ``agent_bytes`` each fit the VMEM budget."""
    most = max(1, _VMEM_BUDGET // agent_bytes)
    n_blocks = -(-n_agents // most)
    return -(-n_agents // n_blocks)


def _agent_grid_call(kernel, name, ins, out_shapes, scratch, *,
                     reverse, interpret):
    """Run ``kernel`` over a (agent blocks, T) grid. ``ins`` and
    ``out_shapes`` are agent-major f32: (A, T, B, X) arrays move one
    time step a grid step (backward in time if ``reverse``), (A, R, C)
    arrays are one block per agent block. ``scratch``: per-agent
    (R, C) VMEM scratch shapes."""
    a, t = ins[0].shape[:2]
    slices = [x.shape[-2:] for x in ins] + [s[-2:] for s in out_shapes]
    blk = agent_block(a, 2 * sum(_tile_bytes(*s) for s in slices)
                      + sum(_tile_bytes(*s) for s in scratch))
    n_blocks = -(-a // blk)
    pad = n_blocks * blk - a
    if pad:
        ins = [jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
               for x in ins]
    step = (lambda ti: t - 1 - ti) if reverse else (lambda ti: ti)

    def spec(shape):
        if len(shape) == 4:
            return pl.BlockSpec((blk, None) + tuple(shape[2:]),
                                lambda i, ti: (i, step(ti), 0, 0))
        return pl.BlockSpec((blk,) + tuple(shape[1:]),
                            lambda i, ti: (i, 0, 0))

    global _last_launch
    _last_launch = (name, a, blk, n_blocks * t)
    outs = pl.pallas_call(
        kernel,
        grid=(n_blocks, t),
        in_specs=[spec(x.shape) for x in ins],
        out_specs=[spec(s) for s in out_shapes],
        out_shape=[jax.ShapeDtypeStruct((a + pad,) + tuple(s[1:]),
                                        jnp.float32) for s in out_shapes],
        scratch_shapes=[pltpu.VMEM((blk,) + tuple(s), jnp.float32)
                        for s in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*ins)
    return [o[:a] for o in outs] if pad else outs


def _matmul(x, w, contract):
    """Each agent's 2-D ``dot_general`` (``contract`` in 3-D axes),
    batched over the block's leading agent axis."""
    return jax.lax.dot_general(x, w, (contract, ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _gates(gi, gh, hdim):
    """Shared gate math: returns (r, z, n) from input/recurrent halves."""
    i_r, i_z, i_n = gi[..., :hdim], gi[..., hdim:2 * hdim], gi[..., 2 * hdim:]
    h_r, h_z, h_n = gh[..., :hdim], gh[..., hdim:2 * hdim], gh[..., 2 * hdim:]
    r = jax.nn.sigmoid(i_r + h_r)
    z = jax.nn.sigmoid(i_z + h_z)
    n = jnp.tanh(i_n + r * h_n)
    return r, z, n, h_n


def _gru_kernel(gi_ref, wh_ref, bh_ref, reset_ref, h0_ref, hs_ref, h_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_ref[...] = h0_ref[...]

    h = h_ref[...]                                        # (A_blk, B, H)
    m = reset_ref[...]                                   # (A_blk, B, 1)
    h = h * (1.0 - m)
    # the bias row goes first: Mosaic rejects the batched matmul's
    # result plus a broadcast row at B > 8 in the other order
    gh = bh_ref[...] + _matmul(h, wh_ref[...], ((2,), (1,)))
    r, z, n, _h_n = _gates(gi_ref[...], gh, h.shape[-1])
    new_h = (1.0 - z) * n + z * h
    h_ref[...] = new_h
    hs_ref[...] = new_h.astype(hs_ref.dtype)


def _gru_forward(gi, wh, bh, h0, resets, *, interpret: bool):
    """gi (A, T, B, 3H), wh (A, H, 3H), bh (A, 1, 3H), h0 (A, B, H),
    resets (A, T, B, 1) -> hs (A, T, B, H)."""
    a, t, bsz, _ = gi.shape
    hdim = h0.shape[-1]
    (hs,) = _agent_grid_call(
        _gru_kernel, "gru_fwd", [gi, wh, bh, resets, h0],
        [(a, t, bsz, hdim)], [(bsz, hdim)],
        reverse=False, interpret=interpret)
    return hs


def _gru_bwd_kernel(gi_ref, hprev_ref, reset_ref, wh_ref, bh_ref, g_ref,
                    dgi_ref, dwh_ref, dbh_ref, dh0_ref, dh_ref):
    """One reverse-time step: grid index t visits actual time T-1-t
    (through the BlockSpec index maps). dh_ref carries the hidden-state
    adjoint; dwh/dbh accumulate in their (time-constant) output blocks.
    """
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dwh_ref[...] = jnp.zeros_like(dwh_ref)
        dbh_ref[...] = jnp.zeros_like(dbh_ref)

    m = reset_ref[...]                                   # (A_blk, B, 1)
    hp = hprev_ref[...] * (1.0 - m)                      # masked h_{t-1}
    gh = bh_ref[...] + _matmul(hp, wh_ref[...], ((2,), (1,)))
    r, z, n, h_n = _gates(gi_ref[...], gh, hp.shape[-1])

    d = g_ref[...] + dh_ref[...]       # total adjoint on h_t
    dn = d * (1.0 - z)
    dz = d * (hp - n)
    dhp = d * z
    da_n = dn * (1.0 - n * n)
    dr = da_n * h_n
    da_z = dz * z * (1.0 - z)
    da_r = dr * r * (1.0 - r)
    dgi_ref[...] = jnp.concatenate([da_r, da_z, da_n], axis=-1)
    dgh = jnp.concatenate([da_r, da_z, da_n * r], axis=-1)
    dhp = dhp + _matmul(dgh, wh_ref[...], ((2,), (2,)))
    dwh_ref[...] += _matmul(hp, dgh, ((1,), (1,)))
    dbh_ref[...] += dgh.sum(axis=1, keepdims=True)
    dh_ref[...] = dhp * (1.0 - m)       # adjoint on h_{t-1}

    @pl.when(t == nt - 1)
    def _final():
        dh0_ref[...] = dh_ref[...]


def _gru_backward(gi, wh, bh, h0, resets, hs, g, *, interpret: bool):
    """Agent-major as :func:`_gru_forward`, plus hs and its cotangent g
    (A, T, B, H) -> (dgi, dwh, dbh, dh0)."""
    a, t, bsz, h3 = gi.shape
    hdim = h3 // 3
    # h_{t-1} for every step: [h0, hs[0], ..., hs[T-2]]
    hprev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)
    return tuple(_agent_grid_call(
        _gru_bwd_kernel, "gru_bwd", [gi, hprev, resets, wh, bh, g],
        [(a, t, bsz, h3), (a, hdim, h3), (a, 1, h3), (a, bsz, hdim)],
        [(bsz, hdim)], reverse=True, interpret=interpret))


def _fold_agents(launch):
    """``launch`` (operands with a leading agent axis) as a
    ``custom_vmap`` whose rule folds the mapped axis into the agent
    axis: N mapped calls of A agents are one call of N·A agents.
    Unmapped operands are broadcast to the N calls."""
    fn = jax.custom_batching.custom_vmap(launch)

    @fn.def_vmap
    def _rule(axis_size, in_batched, *args):
        global _rules_run
        _rules_run += 1
        mark = _rules_run
        args = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for b, x in zip(in_batched, args)]
        outs = fn(*[x.reshape((-1,) + x.shape[2:]) for x in args])
        if _rules_run == mark:      # no vmap further out folded it again
            _LAUNCHES[_last_launch] += 1
        outs = jax.tree.map(
            lambda o: o.reshape((axis_size, -1) + o.shape[1:]), outs)
        return outs, jax.tree.map(lambda _: True, outs)

    return fn


@functools.lru_cache(maxsize=None)
def _gru_scan_with_vjp(interpret: bool):
    """Build the differentiable scan once per interpret flag — the flag
    never enters a jit static argument, so there is exactly one compile
    per (shape, interpret) pair process-wide."""
    forward = _fold_agents(functools.partial(_gru_forward,
                                             interpret=interpret))
    backward = _fold_agents(functools.partial(_gru_backward,
                                              interpret=interpret))

    def agents(gi, wh, bh, h0, resets):
        """The one-agent operands with an agent axis of 1."""
        return gi[None], wh[None], bh.reshape(1, 1, -1), h0[None], \
            resets[None]

    def primal(gi, wh, bh, h0, resets):
        return forward(*agents(gi, wh, bh, h0, resets))[0]

    def fwd(gi, wh, bh, h0, resets):
        hs = primal(gi, wh, bh, h0, resets)
        return hs, (gi, wh, bh, h0, resets, hs)

    def bwd(res, g):
        gi, wh, bh, h0, resets, hs = res
        dgi, dwh, dbh, dh0 = backward(*agents(gi, wh, bh, h0, resets),
                                      hs[None], g[None])
        return dgi[0], dwh[0], dbh.reshape(bh.shape), dh0[0], \
            jnp.zeros_like(resets)

    scan_fn = jax.custom_vjp(primal)
    scan_fn.defvjp(fwd, bwd)
    return scan_fn


def gru_scan(gi, wh, bh, h0, resets, *, interpret: bool = True):
    """gi: (T, B, 3H) precomputed x·W_i + b_i (fp32); wh: (H, 3H);
    bh: (3H,); h0: (B, H); resets: (T, B, 1). Returns hs (T, B, H).
    Differentiable w.r.t. (gi, wh, bh, h0) through the Pallas backward
    kernel; resets receive a zero cotangent (they are data, not weights).
    Under ``vmap`` (nested or not, any ``in_axes``) every mapped call
    shares one agent-blocked launch.
    """
    return _gru_scan_with_vjp(bool(interpret))(gi, wh, bh, h0, resets)
