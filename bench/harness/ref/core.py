"""Plain reference of one DIALS round's training layers: the state at
initialisation, the GS collect (Algorithm 2), the AIP round (held-out CE
and AIP training) and the inner IALS + PPO step (Algorithm 3) with a
plain GAE. Copies of the program's jnp paths (``repro.core.{env_pool,
gs,ials,dials}``, ``repro.marl.{gae,ppo}``) as of the benchmark's first
version, with the same key derivations, so that a program that is right
draws the same random numbers and differs only by rounding.

``Ref(job, dtype)`` builds it for one job (a dict made by
``harness.job``); every method is jitted. The environment is the plain
reference that the job's ``env`` names, ``ref/<env>.py``: a module with
``config_from_side(side)`` and the environment protocol's functions
(``gs_*`` for the global simulator, ``ls_*`` for an agent's local one),
so a new environment is a new file there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import catalog
from . import nets, optim


def env_module(name: str):
    """The plain reference of environment ``name``."""
    return catalog.module(__package__, name)


def stream_keys(key, n):
    return jax.vmap(lambda s: jax.random.fold_in(key, s))(jnp.arange(n))


def step_keys(skeys, t, n):
    ks = jax.vmap(lambda k: jax.random.split(
        jax.random.fold_in(k, t + 1), n))(skeys)
    return jnp.moveaxis(ks, 1, 0)


def reset_where(done, fresh, current):
    def sel(f, c):
        return jnp.where(done.reshape(done.shape + (1,) * (c.ndim - done.ndim)),
                         f, c)
    return jax.tree.map(sel, fresh, current)


def zero_on_done(done, tree):
    return reset_where(done, jax.tree.map(jnp.zeros_like, tree), tree)


def gae(rewards, values, dones, last_value, gamma, lam):
    """(..., T) reverse scan in float32."""
    out_dtype = values.dtype
    t = rewards.ndim - 1
    rw = jnp.moveaxis(rewards, t, 0).astype(jnp.float32)
    vl = jnp.moveaxis(values, t, 0).astype(jnp.float32)
    dn = jnp.moveaxis(dones.astype(jnp.float32), t, 0)
    nv = jnp.concatenate([vl[1:], last_value[None].astype(jnp.float32)], 0)

    def step(carry, inp):
        r, v, n, d = inp
        adv = r + gamma * n * (1.0 - d) - v + gamma * lam * (1.0 - d) * carry
        return adv, adv

    _, advs = jax.lax.scan(step, jnp.zeros(last_value.shape, jnp.float32),
                           (rw, vl, nv, dn), reverse=True)
    advs = jnp.moveaxis(advs, 0, t).astype(out_dtype)
    return advs, advs + values


def ppo_loss(params, batch, net, ppo):
    logits, values = nets.policy_sequence(params, batch["obs"], batch["h0"],
                                          batch["resets"], net)
    logits = logits.astype(jnp.float32)
    values = values.astype(jnp.float32)
    logp_all = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(logp_all, batch["actions"][..., None],
                               axis=-1)[..., 0]
    ratio = jnp.exp(logp - batch["logp_old"])
    adv = batch["adv"]
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    eps = ppo["clip_eps"]
    pi_loss = -jnp.minimum(ratio * adv,
                           jnp.clip(ratio, 1.0 - eps, 1.0 + eps) * adv).mean()
    v_clip = batch["values_old"] + jnp.clip(values - batch["values_old"],
                                            -eps, eps)
    v_loss = 0.5 * jnp.maximum((values - batch["ret"]) ** 2,
                               (v_clip - batch["ret"]) ** 2).mean()
    entropy = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
    return pi_loss + ppo["value_coef"] * v_loss - ppo["entropy_coef"] * entropy


def ppo_update(params, opt, traj, key, net, ppo):
    """Returns (params, opt, mean loss, first-gradient norm per leaf)."""
    n_envs = traj["obs"].shape[0]
    n_mb = ppo["minibatches"]
    mb = max(1, n_envs // n_mb)

    def one_minibatch(carry, idx):
        params, opt = carry
        batch = jax.tree.map(lambda x: jnp.take(x, idx, axis=0), traj)
        loss, grads = jax.value_and_grad(ppo_loss)(params, batch, net, ppo)
        norms = jnp.stack([jnp.linalg.norm(g.astype(jnp.float32))
                           for g in jax.tree.leaves(grads)])
        grads = optim.clip_by_global_norm(grads, ppo["max_grad_norm"])
        master, opt = optim.adam_update(grads, opt, ppo["lr"], b1=0.9,
                                        b2=0.999)
        return (optim.cast_like(master, params), opt), (loss, norms)

    def one_epoch(carry, ekey):
        perm = jax.random.permutation(ekey, n_envs)
        return jax.lax.scan(one_minibatch, carry,
                            perm[:n_mb * mb].reshape(n_mb, mb))

    (params, opt), (losses, norms) = jax.lax.scan(
        one_epoch, (params, opt), jax.random.split(key, ppo["epochs"]))
    return params, opt, losses.mean(), norms[0, 0]


class Ref:
    """The reference for one job, computing in ``dtype``."""

    def __init__(self, job, dtype=jnp.float32):
        self.job, self.dtype = job, dtype
        self.env = env_module(job["env"])
        self.env_cfg = self.env.config_from_side(job["side"])
        info = self.env_cfg.info()
        self.n = info.n_agents
        self.n_actions = info.n_actions
        self.pnet = dict(job["policy"], obs_dim=info.obs_dim,
                         n_actions=info.n_actions)
        self.anet = dict(job["aip"], in_dim=info.alsh_dim,
                         n_sources=info.n_influence)
        self.info = info

    # -- state at initialisation (DIALSTrainer.init) -------------------------
    @functools.partial(jax.jit, static_argnums=0)
    def init(self, key):
        job, env, cfg, n = self.job, self.env, self.env_cfg, self.n
        e = job["ials_streams"]
        k1, k2 = jax.random.split(key)
        kp, ke, kr = jax.random.split(k1, 3)
        params = jax.vmap(lambda k: nets.policy_init(k, self.pnet, self.dtype))(
            jax.random.split(kp, n))
        opt = jax.vmap(optim.adam_init)(params)

        def agent_locals(ka):
            ik = jax.vmap(lambda k: jax.random.fold_in(k, 0))(
                stream_keys(ka, e))
            return jax.vmap(lambda k: env.ls_init(k, cfg))(ik)

        locals_ = jax.vmap(agent_locals)(stream_keys(ke, n))
        obs = jax.vmap(jax.vmap(lambda l: env.ls_obs(l, cfg)))(locals_)
        ials = {"params": params, "opt": opt, "locals": locals_, "obs": obs,
                "h": nets.hidden0(self.pnet, n, e, dtype=self.dtype),
                "aip_h": nets.hidden0(self.anet, n, e, dtype=self.dtype),
                "prev_a": jnp.zeros((n, e), jnp.int32),
                "key": jax.vmap(lambda i: jax.random.fold_in(kr, i))(
                    jnp.arange(n)),
                "iter": jnp.zeros((n,), jnp.int32)}
        aips = jax.vmap(lambda k: nets.aip_init(k, self.anet, self.dtype))(
            jax.random.split(k2, n))
        return ials, aips

    # -- GS collect (repro.core.gs) ------------------------------------------
    @functools.partial(jax.jit, static_argnums=0)
    def collect(self, params, key):
        env, cfg, n, a = self.env, self.env_cfg, self.n, self.n_actions
        s, steps = self.job["collect_streams"], self.job["collect_steps"]
        skeys = stream_keys(key, s)
        v_init = jax.vmap(lambda k: env.gs_init(k, cfg))
        v_step = jax.vmap(lambda st, ac, k: env.gs_step(st, ac, k, cfg))
        v_obs = jax.vmap(lambda st: env.gs_obs(st, cfg))
        apply_agents = jax.vmap(
            lambda p, o, h: nets.policy_apply(p, o, h, self.pnet),
            in_axes=(0, 1, 1), out_axes=(1, 1, 1))
        sample = jax.vmap(nets.sample_action)
        st = v_init(jax.vmap(lambda k: jax.random.fold_in(k, 0))(skeys))
        carry = (st, v_obs(st), nets.hidden0(self.pnet, s, n, dtype=self.dtype),
                 jnp.zeros((s, n), jnp.int32), jnp.ones((s,), bool))

        def step(carry, t):
            st, obs, h, prev_a, prev_done = carry
            k_act, k_env, k_reset = step_keys(skeys, t, 3)
            feat = jnp.concatenate([obs, jax.nn.one_hot(prev_a, a)], axis=-1)
            logits, _, h2 = apply_agents(params, obs, h)
            action, _ = sample(k_act, logits)
            st2, obs2, _rew, u, done = v_step(st, action, k_env)
            fresh = v_init(k_reset)
            st3 = reset_where(done, fresh, st2)
            obs3 = reset_where(done, v_obs(st3), obs2)
            h3, prev3 = zero_on_done(done, (h2, action))
            rec = {"feats": feat, "u": u,
                   "resets": jnp.broadcast_to(prev_done[:, None], (s, n))
                   .astype(jnp.float32)}
            return (st3, obs3, h3, prev3, done), rec

        _, recs = jax.lax.scan(step, carry, jnp.arange(steps))
        # (T, S, N, ...) -> (N, S, T, ...)
        return jax.tree.map(lambda x: jnp.moveaxis(x, (0, 1, 2), (2, 1, 0)),
                            recs)

    # -- AIP round (repro.core.dials._make_aip_round) -------------------------
    @functools.partial(jax.jit, static_argnums=0)
    def aip_round(self, aips, data, keys):
        """Returns (aips', ce_before, ce_after, first-gradient leaf norms
        summed over agents in quadrature)."""
        s = data["feats"].shape[1]
        n_eval = max(0, min(self.job["collect_holdout"], s - 1))
        if n_eval:
            train = jax.tree.map(lambda x: x[:, :s - n_eval], data)
            held = jax.tree.map(lambda x: x[:, s - n_eval:], data)
        else:
            train = held = data
        chunk = self.job["aip_train"]["eval_chunk"]
        ce = jax.vmap(lambda p, d: nets.eval_ce(p, d, self.anet, chunk))
        ce_before = ce(aips, held)
        new, g0 = jax.vmap(lambda p, d, k: nets.train_aip(
            p, d, k, self.anet, self.job["aip_train"]))(aips, train, keys)
        return new, ce_before, ce(new, held), jnp.sqrt((g0 ** 2).sum(0))

    # -- inner IALS + PPO step (repro.core.ials) -------------------------------
    @functools.partial(jax.jit, static_argnums=0)
    def ials_step(self, state, aips):
        """Returns (state', mean PPO loss, mean rollout reward,
        first-gradient leaf norms)."""
        new, loss, reward, g0 = jax.vmap(self._agent_step)(state, aips)
        return new, loss.mean(), reward.mean(), jnp.sqrt((g0 ** 2).sum(0))

    def _agent_step(self, ast, aip_params):
        env, cfg, a = self.env, self.env_cfg, self.n_actions
        e, steps, ppo = (self.job["ials_streams"], self.job["rollout_steps"],
                         self.job["ppo"])
        k_roll, k_ppo = jax.random.split(
            jax.random.fold_in(ast["key"], ast["iter"]))
        skeys = stream_keys(k_roll, e)
        v_init = jax.vmap(lambda k: env.ls_init(k, cfg))
        v_step = jax.vmap(lambda l, ac, u, k: env.ls_step(l, ac, u, k, cfg))
        v_obs = jax.vmap(lambda l: env.ls_obs(l, cfg))
        sample_a = jax.vmap(nets.sample_action)
        sample_u = jax.vmap(nets.sample_sources)

        def step(carry, t):
            locals_, obs, h, aip_h, prev_a, prev_done = carry
            k_act, k_u, k_env, k_reset = step_keys(skeys, t, 4)
            feat = jnp.concatenate([obs, jax.nn.one_hot(prev_a, a)], axis=-1)
            u_logits, aip_h2 = nets.aip_apply(aip_params, feat, aip_h,
                                              self.anet)
            u = sample_u(k_u, u_logits)
            logits, value, h2 = nets.policy_apply(ast["params"], obs, h,
                                                  self.pnet)
            action, logp = sample_a(k_act, logits)
            l2, obs2, rew, done = v_step(locals_, action, u, k_env)
            l3 = reset_where(done, v_init(k_reset), l2)
            obs3 = reset_where(done, v_obs(l3), obs2)
            h3, aip_h3, prev3 = zero_on_done(done, (h2, aip_h2, action))
            tr = {"obs": obs, "action": action, "logp": logp, "value": value,
                  "reward": rew, "done": done, "h_pre": h,
                  "reset_pre": prev_done}
            return (l3, obs3, h3, aip_h3, prev3, done), tr

        carry0 = (ast["locals"], ast["obs"], ast["h"], ast["aip_h"],
                  ast["prev_a"], jnp.zeros((e,), bool))
        carry, traj = jax.lax.scan(step, carry0, jnp.arange(steps))
        locals_, obs, h, aip_h, prev_a, _ = carry
        _, last_value, _ = nets.policy_apply(ast["params"], obs, h, self.pnet)
        et = lambda x: jnp.swapaxes(x, 0, 1)
        adv, ret = gae(et(traj["reward"]), et(traj["value"]),
                       et(traj["done"]), last_value, ppo["gamma"],
                       ppo["lam"])
        batch = {"obs": et(traj["obs"]),
                 "actions": et(traj["action"]).astype(jnp.int32),
                 "logp_old": et(traj["logp"]).astype(jnp.float32),
                 "values_old": et(traj["value"]).astype(jnp.float32),
                 "adv": adv.astype(jnp.float32),
                 "ret": ret.astype(jnp.float32),
                 "resets": et(traj["reset_pre"]).astype(jnp.float32),
                 "h0": traj["h_pre"][0]}
        params, opt, loss, g0 = ppo_update(ast["params"], ast["opt"], batch,
                                           k_ppo, self.pnet, ppo)
        return ({**ast, "params": params, "opt": opt, "locals": locals_,
                 "obs": obs, "h": h, "aip_h": aip_h, "prev_a": prev_a,
                 "iter": ast["iter"] + 1}, loss, traj["reward"].mean(), g0)
