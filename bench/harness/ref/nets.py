"""Plain reference networks: the policy and influence predictor (AIP),
their initialisers and the AIP's loss, training and held-out CE, written
in straightforward ``jax.numpy`` with no kernels.

A network is an MLP trunk, a backbone and its heads. The backbone is
found by the ``kind`` its configuration names, in
``backbones/<kind>.py``, so a new kind is a new file there. A copy of
the program's jnp paths (``repro.nn.init``, ``repro.marl.policy``,
``repro.core.influence``) as of the benchmark's first version. Every
function takes ``dtype``: the float type the networks compute in. The
reference runs at float32 under ``highest`` matmul precision; the
precision control runs the same code at bfloat16 (parameters and
activations), which is what a lower-precision program would compute.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import backbones, optim


# -- initialisers (repro.nn.init) --------------------------------------------
def fan_in_normal(key, shape):
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (1.0 / math.sqrt(max(shape[0], 1))) * x


def orthogonal(key, shape, scale=1.0):
    rows, cols = shape[-2], shape[-1]
    n = max(rows, cols)
    flat = jax.random.normal(key, shape[:-2] + (n, n), jnp.float32)
    q, r = jnp.linalg.qr(flat)
    q = q * jnp.sign(jnp.diagonal(r, axis1=-2, axis2=-1))[..., None, :]
    return scale * q[..., :rows, :cols]


def _dense_init(key, din, dout, scale):
    return {"w": orthogonal(key, (din, dout), scale),
            "b": jnp.zeros((dout,), jnp.float32)}


def _dense(p, x):
    return x @ p["w"] + p["b"]


def cast(tree, dtype):
    """Float leaves of ``tree`` in ``dtype``."""
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


# -- policy (repro.marl.policy) ----------------------------------------------
def policy_init(key, net, dtype):
    keys = jax.random.split(key, 6)
    params, din, trunk = {}, net["obs_dim"], []
    for i, h in enumerate(net["hidden"]):
        trunk.append(_dense_init(keys[i], din, h, math.sqrt(2.0)))
        din = h
    params["trunk"] = trunk
    core, din = backbones.get(net["kind"]).init(keys[3], din, net)
    params.update(core)
    params["pi"] = _dense_init(keys[4], din, net["n_actions"], 0.01)
    params["v"] = _dense_init(keys[5], din, 1, 1.0)
    return cast(params, dtype)


def hidden0(net, *batch, dtype=jnp.float32):
    return backbones.get(net["kind"]).hidden0(net, batch, dtype)


def _trunk(params, x):
    for p in params["trunk"]:
        x = jax.nn.relu(_dense(p, x))
    return x


def policy_apply(params, obs, h, net):
    x = _trunk(params, obs.astype(params["pi"]["w"].dtype))
    x, h = backbones.get(net["kind"]).step(params, x, h, net)
    return _dense(params["pi"], x), _dense(params["v"], x)[..., 0], h


def policy_sequence(params, obs_seq, h0, reset_mask, net):
    x = _trunk(params, obs_seq.astype(params["pi"]["w"].dtype))
    x = backbones.get(net["kind"]).sequence(params, x, h0, reset_mask, net)
    return _dense(params["pi"], x), _dense(params["v"], x)[..., 0]


def sample_action(key, logits):
    a = jax.random.categorical(key, logits)
    logp = jax.nn.log_softmax(logits)
    return a, jnp.take_along_axis(logp, a[..., None], axis=-1)[..., 0]


# -- influence predictor (repro.core.influence) ------------------------------
def aip_init(key, net, dtype):
    keys = jax.random.split(key, 5)
    params, din, trunk = {}, net["in_dim"], []
    for i, h in enumerate(net["hidden"]):
        trunk.append(_dense_init(keys[i], din, h, math.sqrt(2.0)))
        din = h
    params["trunk"] = trunk
    core, din = backbones.get(net["kind"]).init(keys[3], din, net)
    params.update(core)
    params["heads"] = _dense_init(keys[4], din, net["n_sources"],
                                  math.sqrt(2.0))
    return cast(params, dtype)


def aip_apply(params, feat, h, net):
    x = _trunk(params, feat.astype(params["heads"]["w"].dtype))
    x, h = backbones.get(net["kind"]).step(params, x, h, net)
    return _dense(params["heads"], x), h


def aip_sequence(params, feats, h0, resets, net):
    x = _trunk(params, feats.astype(params["heads"]["w"].dtype))
    x = backbones.get(net["kind"]).sequence(params, x, h0, resets, net)
    return _dense(params["heads"], x)


def sample_sources(key, logits):
    return jax.random.bernoulli(key, jax.nn.sigmoid(logits)) \
        .astype(jnp.float32)


def _bce(logits, targets):
    logits = logits.astype(jnp.float32)
    return jnp.maximum(logits, 0) - logits * targets + \
        jnp.log1p(jnp.exp(-jnp.abs(logits)))


def bce_loss(params, feats, targets, resets, net):
    h0 = hidden0(net, feats.shape[0],
                 dtype=params["heads"]["w"].dtype)
    return _bce(aip_sequence(params, feats, h0, resets, net), targets).mean()


def _minibatches(perm, batch):
    n_seq = perm.shape[0]
    n_mb = -(-n_seq // batch)
    pad = n_mb * batch - n_seq
    if pad:
        perm = jnp.concatenate([perm, perm[:pad]])
    return perm.reshape(n_mb, batch)


def train_aip(params, data, key, net, train):
    """Minibatch Adam on the BCE. Returns (params, first-gradient norm of
    every leaf): the second is what the comparison's leaf rule reads."""
    opt = optim.adam_init(params)
    n_seq = data["feats"].shape[0]
    batch = min(train["batch"], n_seq)

    def one_mb(carry, idx):
        params, opt = carry
        fb, ub, rb = (jnp.take(data[k], idx, axis=0)
                      for k in ("feats", "u", "resets"))
        grads = jax.grad(bce_loss)(params, fb, ub, rb, net)
        norms = [jnp.linalg.norm(g.astype(jnp.float32))
                 for g in jax.tree.leaves(grads)]
        master, opt = optim.adam_update(grads, opt, train["lr"], b1=0.9,
                                        b2=0.999)
        return (optim.cast_like(master, params), opt), jnp.stack(norms)

    def one_epoch(carry, ekey):
        perm = jax.random.permutation(ekey, n_seq)
        return jax.lax.scan(one_mb, carry, _minibatches(perm, batch))

    (params, _), norms = jax.lax.scan(
        one_epoch, (params, opt), jax.random.split(key, train["epochs"]))
    return params, norms[0, 0]


def eval_ce(params, data, net, chunk):
    """Held-out CE, in sequence chunks of ``chunk`` (same sums as one
    batch; the chunks bound the reference's working set)."""
    feats, u, resets = data["feats"], data["u"], data["resets"]
    n_seq, t_len = feats.shape[0], feats.shape[1]
    if n_seq <= chunk:
        return bce_loss(params, feats, u, resets, net)
    n_chunks = -(-n_seq // chunk)
    pad = n_chunks * chunk - n_seq

    def chunked(x):
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        return x.reshape((n_chunks, chunk) + x.shape[1:])

    valid = chunked(jnp.ones((n_seq,), jnp.float32))

    def one_chunk(args):
        f, uu, rr, w = args
        logits = aip_sequence(params, f, hidden0(
            net, chunk, dtype=params["heads"]["w"].dtype), rr, net)
        return (_bce(logits, uu).sum(axis=(1, 2)) * w).sum()

    sums = jax.lax.map(one_chunk,
                       (chunked(feats), chunked(u), chunked(resets), valid))
    return sums.sum() / (n_seq * t_len * u.shape[-1])
