"""End-to-end LM pretraining driver with the DIALS-outer optimizer —
the paper's pattern (local regions + periodic compact reconciliation)
applied to the multi-pod training layer.

Trains a ~small tinyllama-family model on synthetic zipf data for a few
hundred steps on CPU, with:
  * AdamW inner steps (the "local region" work — on a real 2-pod mesh
    these carry NO cross-pod collective),
  * every F steps a DIALS-outer reconciliation (int8-compressed delta
    exchange + Nesterov outer step — the only cross-pod traffic),
  * gradient clipping, warmup-cosine schedule, checkpoint/restart.

Run:  PYTHONPATH=src python examples/train_lm_outer.py [--steps 200]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.checkpoint.manager import CheckpointManager
from repro.configs import registry
from repro.data import pipeline
from repro.models import api
from repro.optim import adamw, clip, outer, schedule


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--sync-every", type=int, default=25)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()
    compile_cache.enable()

    spec = registry.get(args.arch, reduced=True)
    cfg = spec.cfg.decoder if spec.kind == "encdec" else spec.cfg
    params = api.init(jax.random.PRNGKey(0), spec)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{args.arch} (reduced): {n_params/1e6:.2f}M params")

    opt = adamw.init(params)
    out_state = outer.init(params)
    err = None
    lr_fn = schedule.warmup_cosine(3e-3, warmup=20, total=args.steps)
    loss_fn = api.loss_fn(spec)
    mgr = CheckpointManager(args.ckpt, keep=2)

    @jax.jit
    def train_step(params, opt, batch, lr):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch), has_aux=True)(params)
        grads, gnorm = clip.clip_by_global_norm(clip.sanitize(grads), 1.0)
        master, opt = adamw.update(grads, opt, lr)
        return adamw.cast_like(master, params), opt, loss, gnorm

    it = pipeline.lm_iterator(seed=0, batch=args.batch, seq=args.seq,
                              vocab=cfg.vocab)
    # restart support: resume from the newest valid checkpoint
    state_tree = {"params": params, "opt": opt, "outer": out_state}
    restored, start = mgr.restore_latest(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state_tree))
    if restored is not None:
        params, opt, out_state = (restored["params"], restored["opt"],
                                  restored["outer"])
        print(f"resumed from step {start}")
    start = max(0, start)

    t0 = time.time()
    for step in range(start, args.steps):
        batch = next(it)
        params, opt, loss, gnorm = train_step(
            params, opt, batch, lr_fn(step))
        if (step + 1) % args.sync_every == 0:
            # DIALS-outer reconciliation (pod_axis=None on 1 host: the
            # compression/outer math runs; on the 2x16x16 mesh this is the
            # only cross-pod collective)
            params, out_state, err = outer.outer_step(
                params, out_state, outer.OuterConfig(
                    sync_every=args.sync_every), err_tree=err)
            mgr.save(step + 1, {"params": params, "opt": opt,
                                "outer": out_state})
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(loss):.4f}  "
                  f"gnorm {float(gnorm):.2f}  "
                  f"({(time.time()-t0):.0f}s)")
    mgr.wait()
    print("done — final loss should be well below ln(vocab) =",
          f"{jnp.log(jnp.asarray(float(cfg.vocab))):.2f}")


if __name__ == "__main__":
    main()
