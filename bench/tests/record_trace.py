"""Records ``data/tiny.xplane.pb``, the TPU trace the reducer's tests
read, on a machine with a TPU:

    python3 bench/tests/record_trace.py --out bench/tests/data/tiny.xplane.pb

Three jitted programs of the program's own kernels and a matmul, each
compiled and run once before the trace: the gradient of the GAE op
(``repro.kernels.gae.ops.gae``, 256 rows of 16 steps; its backward
kernel ``gae_bwd``), the gradient of the GRU sequence op
(``repro.kernels.gru.ops.gru_sequence``, 8 rows of 16 steps, 64 inputs
and units; ``gru_fwd`` and ``gru_bwd``) and a 512 x 512 float32 matmul.
Under the profiler, between the harness's window marks, each runs twice,
and the newest ``.xplane.pb`` is copied to ``--out``.
"""
import argparse
import glob
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
for p in (HERE.parent, HERE.parents[1] / "src"):
    sys.path.insert(0, str(p))


def programs():
    import jax
    import jax.numpy as jnp
    from repro.kernels.gae.ops import gae
    from repro.kernels.gru.ops import gru_sequence

    k = jax.random.split(jax.random.PRNGKey(0), 7)
    r, v = (jax.random.normal(k[i], (256, 16)) for i in (0, 1))
    zeros = jnp.zeros((256, 16))
    gae_grad = jax.jit(lambda r, v: jax.grad(
        lambda r, v: gae(r, v, zeros, zeros[:, 0])[0].sum(),
        argnums=(0, 1))(r, v))
    p = {"wi": jax.random.normal(k[2], (64, 192)) / 8,
         "wh": jax.random.normal(k[3], (64, 192)) / 8,
         "bi": jnp.zeros((192,)), "bh": jnp.zeros((192,))}
    xs = jax.random.normal(k[4], (8, 16, 64))
    gru_grad = jax.jit(lambda p, xs: jax.grad(
        lambda p: gru_sequence(p, xs)[0].sum())(p))
    x = jax.random.normal(k[5], (512, 512))
    matmul = jax.jit(lambda x: x @ x)
    return [(gae_grad, (r, v)), (gru_grad, (p, xs)), (matmul, (x,))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import run
    from harness import trace
    fault = run.device_fault(jax.devices(), 1)
    if fault:
        print(f"record_trace: {fault}", file=sys.stderr)
        return 2
    progs = programs()
    jax.block_until_ready([f(*a) for f, a in progs])
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(trace.START):
            pass
        for _ in range(2):
            jax.block_until_ready([f(*a) for f, a in progs])
        with jax.profiler.TraceAnnotation(trace.END):
            pass
        jax.profiler.stop_trace()
        path = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1]
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, args.out)
    print(f"record_trace: wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
