"""Optimizer substrate: AdamW math, clipping, schedules, int8 compression
with error feedback, and the DIALS-outer (pod-local) optimizer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # dev extra absent: property tests skip
    from _hypothesis_stub import given, settings, st


from repro.optim import adamw, clip, compress, outer, schedule


def test_adamw_matches_manual_math():
    cfg = adamw.AdamWConfig()
    # 2-D param -> decoupled weight decay applies
    params = {"w": jnp.array([[1.0, -2.0, 3.0]])}
    g = np.array([[0.1, 0.2, -0.3]])
    grads = {"w": jnp.asarray(g, jnp.float32)}
    state = adamw.init(params)
    new_master, new_state = adamw.update(grads, state, 1e-2, cfg)

    m = (1 - cfg.b1) * g
    v = (1 - cfg.b2) * g ** 2
    mhat = m / (1 - cfg.b1)
    vhat = v / (1 - cfg.b2)
    delta = mhat / (np.sqrt(vhat) + cfg.eps) \
        + cfg.weight_decay * np.array([[1.0, -2.0, 3.0]])
    expect = np.array([[1.0, -2.0, 3.0]]) - 1e-2 * delta
    np.testing.assert_allclose(new_master["w"], expect, rtol=1e-5)
    assert int(new_state["step"]) == 1


def test_adamw_no_decay_on_vectors():
    cfg = adamw.AdamWConfig()
    params = {"b": jnp.array([2.0])}          # 1-D: no decay
    grads = {"b": jnp.array([0.0])}
    master, _ = adamw.update(grads, adamw.init(params), 1e-2, cfg)
    np.testing.assert_allclose(master["b"], 2.0, atol=1e-7)


def test_adamw_bf16_params_fp32_master():
    params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    state = adamw.init(params)
    assert state["master"]["w"].dtype == jnp.float32
    grads = {"w": jnp.full((4, 4), 1e-3, jnp.bfloat16)}
    master, state = adamw.update(grads, state, 1e-3)
    assert master["w"].dtype == jnp.float32     # master stays fp32
    cast = adamw.cast_like(master, params)
    assert cast["w"].dtype == jnp.bfloat16
    for _ in range(5):
        master, state = adamw.update(grads, state, 1e-3)
    assert not np.allclose(np.asarray(state["master"]["w"]), 1.0)


def test_clip_by_global_norm():
    tree = {"a": jnp.array([3.0]), "b": jnp.array([4.0])}
    clipped, norm = clip.clip_by_global_norm(tree, 1.0)
    assert norm == pytest.approx(5.0)
    total = jnp.sqrt((clipped["a"] ** 2 + clipped["b"] ** 2).sum())
    assert float(total) == pytest.approx(1.0, rel=1e-5)
    # under the cap: unchanged
    same, _ = clip.clip_by_global_norm(tree, 10.0)
    np.testing.assert_allclose(same["a"], tree["a"])


def test_sanitize_kills_nans():
    tree = {"a": jnp.array([1.0, jnp.nan, jnp.inf])}
    out = clip.sanitize(tree)
    assert np.all(np.isfinite(np.asarray(out["a"])))


def test_schedules():
    f = schedule.warmup_cosine(1.0, warmup=10, total=100)
    assert float(f(0)) == pytest.approx(0.0, abs=1e-6)
    assert float(f(10)) == pytest.approx(1.0, rel=1e-5)
    assert float(f(100)) == pytest.approx(0.0, abs=1e-5)
    assert float(f(55)) < 1.0
    g = schedule.warmup_linear(2.0, warmup=4, total=8)
    assert float(g(4)) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# int8 compression + error feedback
# ---------------------------------------------------------------------------
@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_compress_roundtrip_bounded_error(seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (8, 16)) * 3.0
    err0 = jnp.zeros_like(x)
    q, scale, err = compress.compress(x, err0)
    assert q.dtype == jnp.int8
    deq = compress.decompress(q, scale, x.shape)
    # per-row max error <= scale/2 (+ rounding slack)
    row_max = np.abs(np.asarray(x)).max(axis=1)
    bound = row_max / 127.0 * 0.51 + 1e-6
    assert np.all(np.abs(np.asarray(deq - x)).max(axis=1) <= bound * 1.5)
    # error feedback: err == x - deq
    np.testing.assert_allclose(err, x - deq, atol=1e-6)


def test_error_feedback_unbiased_over_steps():
    """Summing compressed values with EF tracks the true sum (the defining
    property of error feedback)."""
    key = jax.random.PRNGKey(0)
    xs = jax.random.normal(key, (50, 4, 8)) * 0.1
    err = jnp.zeros((4, 8))
    acc = jnp.zeros((4, 8))
    for i in range(50):
        q, s, err = compress.compress(xs[i], err)
        acc = acc + compress.decompress(q, s, (4, 8))
    true = xs.sum(0)
    # residual error is the final err, bounded by one quantization step
    np.testing.assert_allclose(np.asarray(acc + err), np.asarray(true),
                               atol=1e-4)


def test_tree_compress_roundtrip():
    tree = {"a": jnp.ones((4, 4)), "b": {"c": jnp.linspace(-1, 1, 8)}}
    err = compress.init_error(tree)
    q, s, err2 = compress.tree_compress(tree, err)
    back = compress.tree_decompress(q, s, tree)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        x, y, atol=2e-2), tree, back)


# ---------------------------------------------------------------------------
# DIALS-outer optimizer
# ---------------------------------------------------------------------------
def test_outer_step_moves_anchor_toward_local():
    cfg = outer.OuterConfig(outer_lr=1.0, momentum=0.0, nesterov=False,
                            compress_int8=False)
    params = {"w": jnp.ones((4,))}
    state = outer.init(params)
    local = {"w": jnp.full((4,), 2.0)}      # local made +1 of progress
    new_params, state2, _ = outer.outer_step(local, state, cfg)
    # delta = anchor - local = -1; anchor' = anchor - lr*delta = 2
    np.testing.assert_allclose(new_params["w"], 2.0, atol=1e-6)


def test_outer_step_momentum_accumulates():
    cfg = outer.OuterConfig(outer_lr=0.5, momentum=0.9, nesterov=True,
                            compress_int8=False)
    params = {"w": jnp.zeros((2,))}
    state = outer.init(params)
    p = params
    for step in range(3):
        local = jax.tree.map(lambda x: x - 1.0, p)   # constant descent
        p, state, _ = outer.outer_step(local, state, cfg)
    # with momentum, displacement exceeds plain 3 * lr * 1
    assert float(-p["w"][0]) > 1.5


def test_outer_step_int8_path_close_to_fp32():
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (16, 16))}
    local = jax.tree.map(lambda x: x - 0.01 * jnp.sign(x), params)
    cfg_fp = outer.OuterConfig(compress_int8=False)
    cfg_q = outer.OuterConfig(compress_int8=True)
    p_fp, _, _ = outer.outer_step(local, outer.init(params), cfg_fp)
    p_q, _, err = outer.outer_step(local, outer.init(params), cfg_q)
    np.testing.assert_allclose(np.asarray(p_fp["w"]), np.asarray(p_q["w"]),
                               atol=1e-3)
    assert err is not None


def test_outer_step_cross_pod_mean_under_shard_map():
    """Multi-pod reconciliation: 1-device mesh sanity (the collective path
    compiles and equals the local path when P=1)."""
    from jax.sharding import Mesh
    import numpy as onp
    mesh = Mesh(onp.array(jax.devices()[:1]), ("pod",))
    params = {"w": jnp.ones((8,))}
    local = {"w": jnp.full((8,), 1.5)}
    cfg = outer.OuterConfig(compress_int8=True)
    state = outer.init(params)

    from functools import partial
    from jax.sharding import PartitionSpec as P

    shard_map = partial(jax.shard_map, check_vma=False)

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P())
    def f(lp, anchor_vel_w):
        st = {"anchor": {"w": anchor_vel_w[0]},
              "velocity": {"w": anchor_vel_w[1]}}
        new_p, _, _ = outer.outer_step({"w": lp}, st, cfg, pod_axis="pod")
        return new_p["w"]

    got = f(local["w"], jnp.stack([state["anchor"]["w"],
                                   state["velocity"]["w"]]))
    want, _, _ = outer.outer_step(local, state, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want["w"]),
                               atol=1e-3)
