"""Compile checks of the main path's Pallas kernels for a TPU v5e chip.

Each case compiles a kernel for a described (not attached) v5e chip —
vmapped over agents, as the DIALS trainer calls it — and asserts that
the Mosaic kernel is in the compiled program. Nothing runs: compiling
needs only shapes, so the interpret-mode tests in ``test_kernels.py``
cannot catch what these do (block layouts the TPU compiler refuses).

Shapes are those of ``chip_smoke.py``'s configs at ``DIALSConfig``
defaults (16 IALS streams × 16 steps, PPO minibatches of 4 streams,
8 collect streams × 128 steps with one held out): traffic side 10
(100 agents) and warehouse side 5 (25 agents; GRU policy H=128 and GRU
AIP H=64 over 128-wide trunks), plus warehouse side 10's own GRU shapes
(100 agents, the benchmark's ``warehouse10.f50``), an odd batch for each
kernel and a GAE batch wide enough to be split into lane tiles. The GS
collect's policy step maps the agent axis 1 of stream-major arrays;
that case checks that the GRU launch's vmap rule moves the axis to the
front. Every GRU launch keeps the operand signature the benchmark's
trace reader knows it by.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gae import ops as gae_ops
from repro.kernels.gru import ops as gru_ops
from repro.nn import gru as gru_mod


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU program written to the persistent cache could not be read
    # back without a chip; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (case, agents, streams E, steps T): per agent the op folds E into the
# kernel's lane batch
GAE_CASES = [("traffic-ials", 100, 16, 16),
             ("odd-batch", 25, 6, 16),
             ("lane-tiled", 4, 4096, 128)]


@pytest.mark.parametrize("case,n,e,t", GAE_CASES,
                         ids=[c[0] for c in GAE_CASES])
def test_gae_compiles_for_v5e(one_chip, case, n, e, t):
    def agent(r, v, d, lv):
        def loss(r, v, lv):
            adv, ret = gae_ops.gae(r, v, d, lv, interpret=False)
            return adv.sum() + (ret ** 2).sum()
        adv, _ = gae_ops.gae(r, v, d, lv, interpret=False)
        return adv, jax.grad(loss, argnums=(0, 1, 2))(r, v, lv)

    hlo = _compile(jax.vmap(agent),
                   [(n, e, t), (n, e, t), (n, e, t), (n, e)], one_chip)
    assert "tpu_custom_call" in hlo


def _gru_params(n, din, h):
    return jax.eval_shape(lambda k: jax.vmap(lambda kk: gru_mod.gru_init(
        kk, gru_mod.GRUConfig(in_dim=din, hidden=h)))(
        jax.random.split(k, n)), jax.random.PRNGKey(0))


def _compile_with_params(fn, params, shapes, sharding):
    p_args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        params)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(p_args, *args).compile().as_text()


# (case, agents, batch B, steps T, in, H)
GRU_SEQ_CASES = [("warehouse-ppo-policy", 25, 4, 16, 128, 128),
                 ("warehouse-aip-train", 25, 7, 128, 128, 64),
                 ("warehouse-aip-eval", 25, 1, 128, 128, 64),
                 ("odd-batch", 25, 6, 16, 128, 128),
                 ("warehouse10-ppo-policy", 100, 4, 16, 128, 128),
                 ("warehouse10-aip-train", 100, 7, 128, 128, 64)]

_F32 = re.compile(r"f32\[([0-9,]*)\]")


def _gru_signatures(hlo):
    """(operand shapes, result shapes) of each Mosaic call in compiled
    HLO text."""
    out = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        head = line.split(" custom-call(")[0]
        ops = re.search(r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=",
                        line).group(1)
        shapes = lambda text: [tuple(int(d) for d in m.split(",") if d)
                               for m in _F32.findall(text)]
        out.append((shapes(ops), shapes(head)))
    return out


def _assert_gru_signatures(hlo, want):
    """The launches are ``want`` (``fwd``/``bwd`` names, in any order):
    gi first, 5 -> 1 with gi's last axis 3x the result's; 6 -> 4."""
    kinds = []
    for ops, res in _gru_signatures(hlo):
        if (len(ops), len(res)) == (5, 1):
            assert ops[0][-1] == 3 * res[0][-1], (ops, res)
            kinds.append("fwd")
        else:
            assert (len(ops), len(res)) == (6, 4), (ops, res)
            assert ops[0] == res[0], (ops, res)       # gi and dgi
            kinds.append("bwd")
    assert sorted(kinds) == sorted(want)


@pytest.mark.parametrize("case,n,b,t,din,h", GRU_SEQ_CASES,
                         ids=[c[0] for c in GRU_SEQ_CASES])
def test_gru_sequence_grad_compiles_for_v5e(one_chip, case, n, b, t, din,
                                            h):
    def agent(p, xs, h0, resets):
        def loss(p, xs, h0):
            hs, last = gru_ops.gru_sequence(p, xs, h0, reset_mask=resets,
                                            interpret=False)
            return (hs ** 2).sum() + last.sum()
        return jax.grad(loss, argnums=(0, 1, 2))(p, xs, h0)

    hlo = _compile_with_params(jax.vmap(agent), _gru_params(n, din, h),
                               [(n, b, t, din), (n, b, h), (n, b, t)],
                               one_chip)
    assert "tpu_custom_call" in hlo
    _assert_gru_signatures(hlo, ["fwd", "bwd"])


# (case, agents, batch B, in, H, agent axis of h and x): the GS collect
# and eval step all agents of stream-major (S, N, ...) arrays
GRU_CELL_CASES = [("warehouse-ials-policy", 25, 16, 128, 128, 0),
                  ("warehouse-ials-aip", 25, 16, 128, 64, 0),
                  ("warehouse-collect-policy", 25, 8, 128, 128, 1),
                  ("odd-batch", 25, 6, 128, 128, 0),
                  ("warehouse10-ials-policy", 100, 16, 128, 128, 0),
                  ("warehouse10-collect-policy", 100, 8, 128, 128, 1)]


@pytest.mark.parametrize("case,n,b,din,h,axis", GRU_CELL_CASES,
                         ids=[c[0] for c in GRU_CELL_CASES])
def test_gru_cell_compiles_for_v5e(one_chip, case, n, b, din, h, axis):
    cell = jax.vmap(lambda p, hh, x: gru_ops.gru_cell(p, hh, x,
                                                      interpret=False),
                    in_axes=(0, axis, axis), out_axes=axis)
    lead = (b, n) if axis else (n, b)
    hlo = _compile_with_params(cell, _gru_params(n, din, h),
                               [lead + (h,), lead + (din,)], one_chip)
    assert "tpu_custom_call" in hlo
    _assert_gru_signatures(hlo, ["fwd"])
