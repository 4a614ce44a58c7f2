"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
ref.py oracle, swept over shapes and dtypes — forward AND backward (the
gru/gae kernels carry custom_vjp Pallas reverse passes), plus the
dispatch layer that routes the MARL hot spots onto them."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.gae import ops as gae_ops
from repro.kernels.gae import ref as gae_ref
from repro.kernels.gru import kernel as gru_kernel
from repro.kernels.gru import ops as gru_ops
from repro.kernels.gru import ref as gru_ref
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd import ref as ssd_ref
from repro.nn import gru as gru_mod


def tree_maxdiff(a, b):
    return max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,t,h,hkv,d", [
    (1, 128, 4, 4, 64),          # MHA
    (2, 256, 8, 2, 64),          # GQA 4:1
    (1, 128, 4, 1, 128),         # MQA, wide head
    (2, 384, 6, 6, 64),          # T not a block multiple
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, t, h, hkv, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, t, hkv, d), dtype)
    out = fa_ops.flash_attention(q, k, v, causal=True, interpret=True)
    ref = fa_ref.attention(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [64, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, t, h, d = 1, 256, 4, 64
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, d), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, causal=True,
                                 sliding_window=window, interpret=True)
    ref = fa_ref.attention(q, k, v, causal=True, sliding_window=window)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_softcap():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, t, h, d = 1, 128, 2, 64
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32) * 3
    k = jax.random.normal(ks[1], (b, t, h, d), jnp.float32) * 3
    v = jax.random.normal(ks[2], (b, t, h, d), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, causal=True, softcap=50.0,
                                 interpret=True)
    ref = fa_ref.attention(q, k, v, causal=True, softcap=50.0)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)
    # softcap must actually change the answer
    ref_nocap = fa_ref.attention(q, k, v, causal=True)
    assert not np.allclose(ref, ref_nocap, atol=1e-3)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    b, t, h, d = 2, 128, 4, 64
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, d), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, causal=False, interpret=True)
    ref = fa_ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# GRU
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,t,din,h", [
    (2, 16, 8, 16), (4, 33, 12, 32), (1, 64, 32, 64),
])
def test_gru_kernel_matches_ref(b, t, din, h):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    params = gru_mod.gru_init(k1, gru_mod.GRUConfig(in_dim=din, hidden=h))
    xs = jax.random.normal(k2, (b, t, din), jnp.float32)
    h0 = jax.random.normal(k3, (b, h), jnp.float32)
    out_k, last_k = gru_ops.gru_sequence(params, xs, h0, interpret=True)
    out_r, last_r = gru_ref.gru_sequence(params, xs, h0)
    np.testing.assert_allclose(out_k, out_r, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(last_k, last_r, atol=1e-5, rtol=1e-5)


def test_gru_kernel_reset_mask():
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(6), 4)
    b, t, din, h = 3, 24, 8, 16
    params = gru_mod.gru_init(k1, gru_mod.GRUConfig(in_dim=din, hidden=h))
    xs = jax.random.normal(k2, (b, t, din), jnp.float32)
    h0 = jax.random.normal(k3, (b, h), jnp.float32)
    resets = jax.random.bernoulli(k4, 0.2, (b, t)).astype(jnp.float32)
    out_k, _ = gru_ops.gru_sequence(params, xs, h0, reset_mask=resets,
                                    interpret=True)
    out_r, _ = gru_ref.gru_sequence(params, xs, h0, reset_mask=resets)
    np.testing.assert_allclose(out_k, out_r, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("with_resets", [False, True])
@pytest.mark.parametrize("b,t,din,h", [(2, 16, 8, 16), (4, 33, 12, 32)])
def test_gru_kernel_grad_matches_ref(b, t, din, h, with_resets):
    """custom_vjp through the Pallas backward-scan kernel vs jax.grad of
    the jnp oracle — w.r.t. params (incl. wh/bh accumulation), xs, h0."""
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(11), 5)
    params = gru_mod.gru_init(k1, gru_mod.GRUConfig(in_dim=din, hidden=h))
    xs = jax.random.normal(k2, (b, t, din), jnp.float32)
    h0 = jax.random.normal(k3, (b, h), jnp.float32)
    resets = (jax.random.bernoulli(k4, 0.2, (b, t)).astype(jnp.float32)
              if with_resets else None)
    g = jax.random.normal(k5, (b, t, h), jnp.float32)

    def loss(seq_fn):
        def f(p, x, h0_):
            hs, h_last = seq_fn(p, x, h0_)
            return (hs * g).sum() + (h_last ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2))

    grads_k = loss(lambda p, x, h0_: gru_ops.gru_sequence(
        p, x, h0_, reset_mask=resets, interpret=True))(params, xs, h0)
    grads_r = loss(lambda p, x, h0_: gru_ref.gru_sequence(
        p, x, h0_, reset_mask=resets))(params, xs, h0)
    assert tree_maxdiff(grads_k, grads_r) < 1e-5


def test_gru_kernel_grad_under_vmap():
    """The sharded runtime vmaps the kernelized sequence over the agent
    axis (stacked params) — grads must survive jit(vmap(grad(...)))."""
    n, b, t, din, h = 3, 2, 9, 6, 8
    params = jax.vmap(lambda k: gru_mod.gru_init(
        k, gru_mod.GRUConfig(in_dim=din, hidden=h)))(
        jax.random.split(jax.random.PRNGKey(0), n))
    xs = jax.random.normal(jax.random.PRNGKey(1), (n, b, t, din))
    h0 = jnp.zeros((n, b, h))
    resets = jax.random.bernoulli(
        jax.random.PRNGKey(2), 0.2, (n, b, t)).astype(jnp.float32)

    def one(seq_fn):
        def f(p, x, h0_, r):
            hs, _ = seq_fn(p, x, h0_, r)
            return (hs ** 2).mean()
        return jax.jit(jax.vmap(jax.grad(f)))

    gk = one(lambda p, x, h0_, r: gru_ops.gru_sequence(
        p, x, h0_, reset_mask=r, interpret=True))(params, xs, h0, resets)
    gr = one(lambda p, x, h0_, r: gru_ref.gru_sequence(
        p, x, h0_, reset_mask=r))(params, xs, h0, resets)
    assert tree_maxdiff(gk, gr) < 1e-6


def _stacked_gru_params(key, agents, din, h):
    """GRU params stacked over the leading ``agents`` axes."""
    n = int(np.prod(agents))
    p = jax.vmap(lambda k: gru_mod.gru_init(
        k, gru_mod.GRUConfig(in_dim=din, hidden=h)))(jax.random.split(key, n))
    return jax.tree.map(lambda x: x.reshape(agents + x.shape[1:]), p)


# (case, agent axes (two: a nested vmap), B, T, agent axis of xs/h0/g,
# with resets, VMEM budget or None). The budget of 168 KiB gives 3 agents
# a forward step and 2 a backward one (B=3, H=8: every block slice is one
# 4 KiB tile), so A=5 is zero-padded in both.
GRU_VMAP_CASES = [
    ("ragged-blocks", (5,), 3, 6, 0, False, 168 * 1024),
    ("in-axes-1", (4,), 3, 5, 1, False, None),
    ("nested-vmap", (2, 3), 3, 5, 0, False, None),
    ("t1", (5,), 4, 1, 0, False, None),
    ("odd-b7", (3,), 7, 4, 0, False, None),
    ("resets", (4,), 3, 8, 0, True, None),
]


@pytest.mark.parametrize("case,agents,b,t,axis,with_resets,budget",
                         GRU_VMAP_CASES, ids=[c[0] for c in GRU_VMAP_CASES])
def test_gru_kernel_vmapped_matches_ref(monkeypatch, case, agents, b, t,
                                        axis, with_resets, budget):
    """The agent-blocked launch under vmap (per-agent params, as DIALS
    calls it): forward and grads w.r.t. params, xs and h0 match the
    oracle per agent."""
    if budget:
        monkeypatch.setattr(gru_kernel, "_VMEM_BUDGET", budget)
    din, h = 5, 8
    ks = jax.random.split(jax.random.PRNGKey(21), 5)
    params = _stacked_gru_params(ks[0], agents, din, h)
    n = agents[-1]
    lead = agents[:-1] + ((b, n) if axis else (n, b))
    xs = jax.random.normal(ks[1], lead + (t, din))
    h0 = jax.random.normal(ks[2], lead + (h,))
    g = jax.random.normal(ks[3], lead + (t, h))
    resets = (jax.random.bernoulli(ks[4], 0.3, lead + (t,))
              .astype(jnp.float32) if with_resets else None)

    def run(seq_fn):
        def f(p, x, h0_, g_, r):
            hs, last = seq_fn(p, x, h0_, r)
            return (hs * g_).sum() + (last ** 2).sum()
        fn = jax.vmap(jax.value_and_grad(f, argnums=(0, 1, 2)),
                      in_axes=(0, axis, axis, axis,
                               axis if with_resets else None))
        for _ in agents[:-1]:
            fn = jax.vmap(fn)
        return jax.jit(fn)(params, xs, h0, g, resets)

    before = gru_kernel.launch_stats()
    got = run(lambda p, x, h0_, r: gru_ops.gru_sequence(
        p, x, h0_, reset_mask=r, interpret=True))
    want = run(lambda p, x, h0_, r: gru_ref.gru_sequence(
        p, x, h0_, reset_mask=r))
    assert tree_maxdiff(got, want) < 1e-5
    new = {k for k, c in gru_kernel.launch_stats().items()
           if c > before.get(k, 0)}
    # every agent of every vmapped axis rides in one launch each way
    assert {(k, a) for k, a, _, _ in new} == {
        ("gru_fwd", int(np.prod(agents))), ("gru_bwd", int(np.prod(agents)))}
    if budget:
        assert {(k, blk) for k, _, blk, _ in new} == {
            ("gru_fwd", 3), ("gru_bwd", 2)}


def _pallas_eqns(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, bodies included."""
    from repro.analysis import walker
    for eqn in walker.raw_jaxpr(jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for _, sub in walker.sub_jaxprs(eqn):
            yield from _pallas_eqns(sub)


def test_gru_vmapped_launch_is_agent_blocked():
    """At warehouse side 10's AIP shapes (A=100 agents, B=7, T=128,
    H=64) a vmapped sequence and its grad launch ``gru_fwd`` and
    ``gru_bwd`` once each, over ceil(A / A_blk) x T grid steps with
    A_blk > 1, not A x T. The launches keep the operand signatures the
    benchmark's trace reader knows them by: gi first, 5 -> 1 with
    gi's last axis 3x the result's, and 6 -> 4."""
    a, b, t, din, h = 100, 7, 128, 16, 64
    params = jax.eval_shape(
        lambda: _stacked_gru_params(jax.random.PRNGKey(0), (a,), din, h))
    xs = jax.ShapeDtypeStruct((a, b, t, din), jnp.float32)

    def loss(p, x):
        hs, _ = gru_ops.gru_sequence(p, x, interpret=True)
        return (hs ** 2).sum()

    before = gru_kernel.launch_stats()
    jaxpr = jax.make_jaxpr(jax.vmap(jax.grad(loss)))(params, xs)
    eqns = {e.params["name"]: e
            for e in _pallas_eqns(jaxpr)}
    assert sorted(eqns) == ["gru_bwd", "gru_fwd"]
    blocks = {}
    for name, sig in (("gru_fwd", (5, 1)), ("gru_bwd", (6, 4))):
        eqn = eqns[name]
        assert (len(eqn.invars), len(eqn.outvars)) == sig
        gi = eqn.invars[0].aval.shape                 # (A padded, T, B, 3H)
        assert gi[1:] == (t, b, 3 * h)
        n_blocks, steps = eqn.params["grid_mapping"].grid
        assert steps == t and gi[0] % n_blocks == 0
        blk = gi[0] // n_blocks
        assert 1 < blk and gi[0] - a < blk
        blocks[name] = (blk, n_blocks * t)
    assert eqns["gru_fwd"].outvars[0].aval.shape[-1] == h
    assert blocks == {"gru_fwd": (34, 3 * t), "gru_bwd": (20, 5 * t)}
    after = gru_kernel.launch_stats()
    assert {k: c - before.get(k, 0) for k, c in after.items()
            if c != before.get(k, 0)} == {
        ("gru_fwd", a, 34, 3 * t): 1, ("gru_bwd", a, 20, 5 * t): 1}


def test_dials_round_launches_every_gru_agent_blocked():
    """The launch tally over a whole kernelized DIALS round (warehouse,
    GRU AIP and policy, the agent-sharded runner's per-shard body on
    one shard): every GRU launch, AIP training, PPO, the T=1 rollout
    and collect cells, carries all 4 agents in one agent block."""
    from repro.core import dials, dials_sharded, influence
    from repro.envs import registry
    from repro.marl import policy as policy_mod, ppo as ppo_mod
    env_mod, env_cfg = registry.make("warehouse", side=2, horizon=16)
    info = env_cfg.info()
    pc = policy_mod.PolicyConfig(obs_dim=info.obs_dim,
                                 n_actions=info.n_actions, kind="gru",
                                 hidden=(16,), gru_hidden=8)
    ac = influence.AIPConfig(in_dim=info.alsh_dim,
                             n_sources=info.n_influence, kind="gru",
                             hidden=(16,), gru_hidden=8, epochs=2, batch=8)
    runner = dials_sharded.ShardedDIALSRunner(
        env_mod, env_cfg, pc, ac, ppo_mod.PPOConfig(epochs=1, minibatches=2),
        dials.DIALSConfig(outer_rounds=1, aip_refresh=2, collect_envs=2,
                          collect_steps=8, n_envs=2, rollout_steps=8,
                          use_kernels="on"),
        n_shards=1)
    before = gru_kernel.launch_stats()
    jaxpr = runner.inner_jaxpr()
    new = {k: c - before.get(k, 0)
           for k, c in gru_kernel.launch_stats().items()
           if c > before.get(k, 0)}
    assert {k[0] for k in new} == {"gru_fwd", "gru_bwd"}
    assert all((a, blk) == (info.n_agents, info.n_agents)
               for _, a, blk, _ in new), new
    grids = [e.params["grid_mapping"].grid for e in _pallas_eqns(jaxpr)
             if e.params["name"].startswith("gru_")]
    assert grids and all(g[0] == 1 for g in grids), grids


def test_gru_agent_block_fits_the_vmem_budget():
    """A_blk: the fewest equal blocks that fit the budget. All 100
    agents' H=128 W_h (double-buffered) cannot share one step."""
    wh = 2 * gru_kernel._tile_bytes(128, 384)
    assert 100 * wh > gru_kernel._VMEM_BUDGET
    assert gru_kernel.agent_block(100, wh) == 20          # 5 blocks of 20
    assert gru_kernel.agent_block(1, wh) == 1
    assert gru_kernel.agent_block(100, 10 ** 9) == 1      # never 0
    most = gru_kernel._VMEM_BUDGET // wh
    assert gru_kernel.agent_block(most + 1, wh) == -(-(most + 1) // 2)


@pytest.mark.parametrize("xs_dtype,h0_dtype,want", [
    (jnp.float32, jnp.float32, jnp.float32),
    (jnp.bfloat16, None, jnp.bfloat16),       # oracle: h0 inherits xs dtype
    (jnp.bfloat16, jnp.float32, jnp.float32),  # oracle: hs threads h0 dtype
])
def test_gru_kernel_dtype_contract(xs_dtype, h0_dtype, want):
    """No silent upcasting: hs/h_last come back in the oracle's output
    dtype (h0.dtype when given, else xs.dtype)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(12), 3)
    b, t, din, h = 2, 8, 4, 8
    params = gru_mod.gru_init(k1, gru_mod.GRUConfig(in_dim=din, hidden=h))
    xs = jax.random.normal(k2, (b, t, din), xs_dtype)
    h0 = (jax.random.normal(k3, (b, h), h0_dtype)
          if h0_dtype is not None else None)
    hs_k, last_k = gru_ops.gru_sequence(params, xs, h0, interpret=True)
    hs_r, last_r = gru_ref.gru_sequence(params, xs, h0)
    assert hs_k.dtype == hs_r.dtype == want
    assert last_k.dtype == last_r.dtype == want
    # bf16 anywhere on the path (inputs or outputs) loosens the tolerance:
    # the oracle rounds the input-gate matmul through bf16, the kernel
    # computes it in fp32
    tol = 3e-2 if jnp.bfloat16 in (xs_dtype, want) else 1e-5
    np.testing.assert_allclose(hs_k.astype(jnp.float32),
                               hs_r.astype(jnp.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# SSD (Mamba2 state-space duality)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,t,h,p,n,chunk", [
    (1, 128, 2, 16, 16, 32),
    (2, 256, 4, 32, 32, 64),
    (1, 64, 1, 8, 64, 64),       # single chunk
])
def test_ssd_kernel_matches_ref(b, t, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (b, t, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h))) * 0.1
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bmat = jax.random.normal(ks[3], (b, t, n), jnp.float32)
    c = jax.random.normal(ks[4], (b, t, n), jnp.float32)
    y_k, s_k = ssd_ops.ssd(x, dt, a, bmat, c, chunk=chunk, interpret=True)
    y_r, s_r = ssd_ref.ssd(x, dt, a, bmat, c, chunk=chunk)
    np.testing.assert_allclose(y_k, y_r, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s_k, s_r, atol=2e-4, rtol=2e-4)


def test_ssd_kernel_initial_state():
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    b, t, h, p, n, chunk = 1, 64, 2, 8, 16, 32
    x = jax.random.normal(ks[0], (b, t, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h))) * 0.1
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bmat = jax.random.normal(ks[3], (b, t, n), jnp.float32)
    c = jax.random.normal(ks[4], (b, t, n), jnp.float32)
    s0 = jax.random.normal(ks[5], (b, h, p, n), jnp.float32)
    y_k, s_k = ssd_ops.ssd(x, dt, a, bmat, c, chunk=chunk,
                           initial_state=s0, interpret=True)
    y_r, s_r = ssd_ref.ssd(x, dt, a, bmat, c, chunk=chunk, initial_state=s0)
    np.testing.assert_allclose(y_k, y_r, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s_k, s_r, atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# GAE
def _pallas_names(fn, *args):
    """Names of the ``pallas_call``s in the jaxpr of ``fn(*args)``, read
    from the analyzer's body paths (``pallas_call:<name>``)."""
    from repro.analysis import walker
    jaxpr = jax.make_jaxpr(fn)(*args)
    return {c.split(":", 1)[1] for site in walker.walk(jaxpr.jaxpr)
            for c in site.path if c.startswith("pallas_call:")}


def test_gru_kernels_carry_their_names():
    """The forward and backward GRU kernels are named, so that a profile
    of the program shows ``gru_fwd`` / ``gru_bwd`` in their operations."""
    params = gru_mod.gru_init(jax.random.PRNGKey(0),
                              gru_mod.GRUConfig(in_dim=8, hidden=16))
    xs = jnp.ones((2, 8, 8), jnp.float32)

    def loss(p):
        hs, _ = gru_ops.gru_sequence(p, xs, interpret=True)
        return hs.sum()

    assert _pallas_names(loss, params) == {"gru_fwd"}
    assert _pallas_names(jax.grad(loss), params) == {"gru_fwd", "gru_bwd"}


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(4, 16), (2, 3, 32), (8,)])
def test_gae_kernel_matches_ref(shape):
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    rewards = jax.random.normal(ks[0], shape)
    values = jax.random.normal(ks[1], shape)
    dones = jax.random.bernoulli(ks[2], 0.1, shape).astype(jnp.float32)
    last_value = jax.random.normal(ks[3], shape[:-1])
    adv_k, ret_k = gae_ops.gae(rewards, values, dones, last_value,
                               interpret=True)
    adv_r, ret_r = gae_ref.gae(rewards, values, dones, last_value)
    # fp32 in, same op sequence: the interpret-mode kernel is bitwise
    np.testing.assert_array_equal(np.asarray(adv_k), np.asarray(adv_r))
    np.testing.assert_array_equal(np.asarray(ret_k), np.asarray(ret_r))


@pytest.mark.parametrize("shape", [(4, 16), (2, 3, 32)])
def test_gae_kernel_grad_matches_ref(shape):
    """Linear-adjoint Pallas reverse pass vs jax.grad of the oracle —
    w.r.t. rewards, values (incl. the next_values shift), last_value."""
    ks = jax.random.split(jax.random.PRNGKey(10), 5)
    rewards = jax.random.normal(ks[0], shape)
    values = jax.random.normal(ks[1], shape)
    dones = jax.random.bernoulli(ks[2], 0.15, shape).astype(jnp.float32)
    last_value = jax.random.normal(ks[3], shape[:-1])
    g = jax.random.normal(ks[4], shape)

    def loss(gae_fn):
        def f(r, v, lv):
            adv, ret = gae_fn(r, v, lv)
            return (adv * g).sum() + (ret ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2))

    gk = loss(lambda r, v, lv: gae_ops.gae(r, v, dones, lv,
                                           interpret=True))(
        rewards, values, last_value)
    gr = loss(lambda r, v, lv: gae_ref.gae(r, v, dones, lv))(
        rewards, values, last_value)
    assert tree_maxdiff(gk, gr) < 1e-5


def test_gae_kernel_lane_tiled_matches_ref(monkeypatch):
    """A batch too wide for the VMEM budget is split into 128-lane tiles
    over a parallel grid axis (zero-padded to whole tiles): forward and
    grads still match the oracle."""
    from repro.kernels.gae import kernel as gae_kernel
    monkeypatch.setattr(gae_kernel, "_VMEM_BUDGET", 2 * 5 * 16 * 4 * 128)
    shape = (300, 16)                     # B=300 -> 3 tiles of 128
    assert gae_kernel._lane_tile(16, 300, 5) == 128
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    rewards = jax.random.normal(ks[0], shape)
    values = jax.random.normal(ks[1], shape)
    dones = jax.random.bernoulli(ks[2], 0.1, shape).astype(jnp.float32)
    last_value = jax.random.normal(ks[3], shape[:-1])
    g = jax.random.normal(ks[4], shape)

    def loss(gae_fn):
        def f(r, v, lv):
            adv, ret = gae_fn(r, v, lv)
            return (adv * g).sum() + (ret ** 2).sum()
        return jax.value_and_grad(f, argnums=(0, 1, 2))

    vk, gk = loss(lambda r, v, lv: gae_ops.gae(r, v, dones, lv,
                                               interpret=True))(
        rewards, values, last_value)
    vr, gr = loss(lambda r, v, lv: gae_ref.gae(r, v, dones, lv))(
        rewards, values, last_value)
    np.testing.assert_allclose(float(vk), float(vr), rtol=1e-6)
    assert tree_maxdiff(gk, gr) < 1e-5


def test_gae_kernels_carry_their_names():
    """The GAE reverse scan and its adjoint are named ``gae_fwd`` and
    ``gae_bwd``."""
    x = jnp.ones((4, 16), jnp.float32)
    last = jnp.ones((4,), jnp.float32)

    def loss(r):
        adv, _ = gae_ops.gae(r, x, 0 * x, last, interpret=True)
        return adv.sum()

    assert _pallas_names(loss, x) == {"gae_fwd"}
    assert _pallas_names(jax.grad(loss), x) == {"gae_fwd", "gae_bwd"}


def test_gae_oracle_traces_and_round_trips_bf16():
    """The oracle used to desync its scan carry dtype under bf16 inputs
    (the (1 - d) masking promotes to f32) and crash at trace time; it
    now accumulates in f32 and casts back, so bf16 in means bf16 out —
    the DtypeRoundTrip contract."""
    from repro.marl import gae as gae_mod
    shape = (3, 8)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    args = (jax.random.normal(ks[0], shape, jnp.bfloat16),
            jax.random.normal(ks[1], shape, jnp.bfloat16),
            jax.random.bernoulli(ks[2], 0.1, shape).astype(jnp.bfloat16),
            jax.random.normal(ks[3], shape[:-1], jnp.bfloat16))
    adv, ret = gae_mod.gae(*args)
    assert adv.dtype == jnp.bfloat16 and ret.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(adv.astype(jnp.float32)).all())
    # f32 numerics untouched by the accumulate-then-cast rewrite
    f32 = tuple(a.astype(jnp.float32) for a in args)
    adv32, _ = gae_mod.gae(*f32)
    np.testing.assert_allclose(np.asarray(adv.astype(jnp.float32)),
                               np.asarray(adv32), atol=0.15, rtol=0.15)


def test_gae_kernel_path_round_trips_bf16():
    """The kernel dispatch path scans in f32 and used to return f32 for
    bf16 inputs — a silent upcast; it now casts back to values.dtype."""
    shape = (2, 8)
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    args = (jax.random.normal(ks[0], shape, jnp.bfloat16),
            jax.random.normal(ks[1], shape, jnp.bfloat16),
            jax.random.bernoulli(ks[2], 0.1, shape).astype(jnp.bfloat16),
            jax.random.normal(ks[3], shape[:-1], jnp.bfloat16))
    adv_k, ret_k = gae_ops.gae(*args, interpret=True)
    assert adv_k.dtype == jnp.bfloat16 and ret_k.dtype == jnp.bfloat16
    adv_r, _ = gae_ref.gae(*args)
    np.testing.assert_allclose(
        np.asarray(adv_k.astype(jnp.float32)),
        np.asarray(adv_r.astype(jnp.float32)), atol=0.1, rtol=0.1)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------
def test_dispatch_resolve_modes():
    on_cpu = dispatch.resolve("on", backend="cpu")
    assert on_cpu.use and on_cpu.interpret
    off_cpu = dispatch.resolve("off", backend="cpu")
    assert not off_cpu.use and off_cpu.interpret
    auto_cpu = dispatch.resolve("auto", backend="cpu")
    assert not auto_cpu.use          # auto on CPU: oracle, no interp cost
    auto_tpu = dispatch.resolve("auto", backend="tpu")
    assert auto_tpu.use and not auto_tpu.interpret
    assert dispatch.resolve("on", backend="tpu") == auto_tpu
    # pre-resolved decisions pass through unchanged
    assert dispatch.resolve(on_cpu) is on_cpu
    with pytest.raises(ValueError, match="use_kernels"):
        dispatch.resolve("yes")


def test_dispatch_override_mode():
    from repro.core import influence
    cfg = influence.AIPConfig(in_dim=4, n_sources=2)
    assert cfg.use_kernels == "auto"
    assert dispatch.override_mode(cfg, "auto") is cfg       # driver defers
    on = dispatch.override_mode(cfg, "on")
    assert on.use_kernels == "on" and on.in_dim == cfg.in_dim
    assert dispatch.override_mode(on, "on") is on           # idempotent
    with pytest.raises(ValueError, match="use_kernels"):
        dispatch.override_mode(cfg, "maybe")


def test_nn_gru_sequence_routes_to_kernel():
    """use_kernels='on' through the nn-level entry point returns the
    kernel's numbers (and 'off' the oracle's) — the route the AIP and
    policy configs thread."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(13))
    params = gru_mod.gru_init(k1, gru_mod.GRUConfig(in_dim=4, hidden=8))
    xs = jax.random.normal(k2, (2, 6, 4), jnp.float32)
    hs_on, _ = gru_mod.gru_sequence(params, xs, use_kernels="on")
    hs_off, _ = gru_mod.gru_sequence(params, xs, use_kernels="off")
    hs_k, _ = gru_ops.gru_sequence(params, xs, interpret=True)
    np.testing.assert_array_equal(np.asarray(hs_on), np.asarray(hs_k))
    np.testing.assert_allclose(hs_on, hs_off, atol=1e-5, rtol=1e-5)


def test_nn_gru_cell_routes_to_kernel():
    """The single-step rollout path (policy_apply / aip_apply inside the
    GS and LS rollouts) dispatches to the T=1 Pallas cell: 'on' matches
    the op-level kernel exactly and the oracle to fp32 tolerance, under
    plain calls AND vmapped over an agent axis (how the rollouts run
    it); dtype contract follows the hidden state."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(14), 3)
    params = gru_mod.gru_init(k1, gru_mod.GRUConfig(in_dim=5, hidden=8))
    h = jax.random.normal(k2, (4, 8), jnp.float32)
    x = jax.random.normal(k3, (4, 5), jnp.float32)
    on = gru_mod.gru_cell(params, h, x, use_kernels="on")
    off = gru_mod.gru_cell(params, h, x)                  # oracle default
    kern = gru_ops.gru_cell(params, h, x, interpret=True)
    np.testing.assert_array_equal(np.asarray(on), np.asarray(kern))
    np.testing.assert_allclose(on, off, atol=1e-5, rtol=1e-5)
    assert on.dtype == h.dtype
    # a kernel step equals one step of the kernel scan (shared kernel)
    hs, _ = gru_ops.gru_sequence(params, x[:, None, :], h, interpret=True)
    np.testing.assert_array_equal(np.asarray(on), np.asarray(hs[:, 0]))
    # vmapped over agents, as the stacked-policy rollout step runs it
    stack = lambda t: jax.tree.map(lambda a: jnp.stack([a] * 3), t)
    v_on = jax.vmap(lambda p, hh, xx: gru_mod.gru_cell(
        p, hh, xx, use_kernels="on"))(stack(params), stack(h), stack(x))
    v_off = jax.vmap(gru_mod.gru_cell)(stack(params), stack(h), stack(x))
    np.testing.assert_allclose(v_on, v_off, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end: the kernelized hot paths match the oracle training paths
# ---------------------------------------------------------------------------
def _aip_setup(use_kernels):
    from repro.core import influence
    cfg = influence.AIPConfig(in_dim=6, n_sources=3, kind="gru",
                              hidden=(12,), gru_hidden=8, epochs=20,
                              batch=8, lr=1e-3, use_kernels=use_kernels)
    ks = jax.random.split(jax.random.PRNGKey(20), 4)
    data = {"feats": jax.random.normal(ks[0], (12, 16, 6)),
            "u": jax.random.bernoulli(
                ks[1], 0.4, (12, 16, 3)).astype(jnp.float32),
            "resets": jax.random.bernoulli(
                ks[2], 0.1, (12, 16)).astype(jnp.float32)}
    params = influence.aip_init(ks[3], cfg)
    return cfg, params, data


def test_train_aip_kernel_path_matches_oracle():
    """Full train_aip (minibatch Adam over epochs, grads through the
    custom_vjp) with use_kernels='on' lands on the oracle path's params
    and loss to 1e-5."""
    from repro.core import influence
    (cfg_on, p0, data), (cfg_off, _, _) = _aip_setup("on"), _aip_setup("off")
    key = jax.random.PRNGKey(21)
    p_on, loss_on = influence.train_aip(p0, data, key, cfg_on)
    p_off, loss_off = influence.train_aip(p0, data, key, cfg_off)
    assert tree_maxdiff(p_on, p_off) < 1e-5
    assert abs(float(loss_on) - float(loss_off)) < 1e-5
    # the loss curves agree too: held-out CE from either param set matches
    ce_on = influence.eval_ce(p_on, data, cfg_on)
    ce_off = influence.eval_ce(p_off, data, cfg_off)
    assert abs(float(ce_on) - float(ce_off)) < 1e-5


def test_ppo_update_kernel_path_matches_oracle():
    """One PPO update on a synthetic GRU-policy trajectory: the Pallas
    policy-GRU recompute (custom_vjp inside ppo_loss grads) matches the
    oracle to 1e-5."""
    from repro.marl import policy as policy_mod
    from repro.marl import ppo as ppo_mod
    from repro.optim import adamw
    e, t, obs_dim, n_act = 8, 10, 5, 3
    ks = jax.random.split(jax.random.PRNGKey(30), 8)
    traj = {
        "obs": jax.random.normal(ks[0], (e, t, obs_dim)),
        "actions": jax.random.randint(ks[1], (e, t), 0, n_act),
        "logp_old": -jnp.abs(jax.random.normal(ks[2], (e, t))),
        "adv": jax.random.normal(ks[3], (e, t)),
        "ret": jax.random.normal(ks[4], (e, t)),
        "values_old": jax.random.normal(ks[5], (e, t)),
        "resets": jax.random.bernoulli(
            ks[6], 0.15, (e, t)).astype(jnp.float32),
    }
    outs = {}
    for mode in ("on", "off"):
        pc = policy_mod.PolicyConfig(obs_dim=obs_dim, n_actions=n_act,
                                     kind="gru", hidden=(8,), gru_hidden=8,
                                     use_kernels=mode)
        params = policy_mod.policy_init(ks[7], pc)
        batch = {**traj, "h0": jnp.zeros((e, pc.gru_hidden))}
        cfg = ppo_mod.PPOConfig(epochs=2, minibatches=2, use_kernels=mode)
        new_params, _, metrics = ppo_mod.ppo_update(
            params, adamw.init(params), batch,
            jax.random.PRNGKey(31), pc, cfg)
        outs[mode] = (new_params, metrics)
    assert tree_maxdiff(outs["on"][0], outs["off"][0]) < 1e-5
    assert abs(float(outs["on"][1]["loss"])
               - float(outs["off"][1]["loss"])) < 1e-5
