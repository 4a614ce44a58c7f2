"""Batched serving example: prefill a prompt batch, then decode tokens
with the position-tracking KV cache — the path the decode_32k/long_500k
dry-run cells lower at production shape.

Run:  PYTHONPATH=src python examples/serve_decode.py [--arch tinyllama-1.1b]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import registry
from repro.models import api, lm as lm_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    compile_cache.enable()

    spec = registry.get(args.arch, reduced=True)
    if not spec.has_decode or spec.kind == "encdec":
        raise SystemExit(f"{args.arch} has no plain LM decode path")
    cfg = spec.cfg
    params = api.init(jax.random.PRNGKey(0), spec)
    max_len = args.prompt_len + args.new_tokens

    binp = {}
    if spec.kind == "vlm":
        binp["patches"] = jnp.zeros(
            (args.batch, spec.n_patches, spec.vision_dim), jnp.bfloat16)
    caches = api.init_caches(params, spec, args.batch, max_len,
                             batch_inputs=binp)

    @jax.jit
    def decode(params, token, caches, index):
        return lm_mod.decode_step(params, token, caches, index, cfg)

    # "prefill" by decoding the prompt token-by-token (tiny model: fine;
    # production prefill lowers the dedicated prefill_32k program)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0, cfg.vocab)
    t0 = time.time()
    logits = None
    for i in range(args.prompt_len):
        logits, caches = decode(params, prompt[:, i:i + 1],
                                caches, jnp.asarray(i, jnp.int32))
    print(f"prefilled {args.prompt_len} positions in {time.time()-t0:.1f}s")

    # greedy decode
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.prompt_len, max_len - 1):
        logits, caches = decode(params, tok, caches,
                                jnp.asarray(i, jnp.int32))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    dt = time.time() - t0
    seqs = jnp.concatenate(out, axis=1)
    print(f"decoded {seqs.shape[1]} tokens x {args.batch} seqs "
          f"in {dt:.1f}s ({args.batch*seqs.shape[1]/dt:.0f} tok/s)")
    print("sample ids:", seqs[0, :12].tolist())


if __name__ == "__main__":
    main()
