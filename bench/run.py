"""DIALS training benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload traffic10.f50 --seed 7 --seconds 30 \
        --trace 0

Run from the root of a checkout on a machine that holds the cell's chips.
A run drives ``repro.core.dials.DIALSTrainer.run`` (the entry users call,
at the cell's configuration and traffic files) in one process:

1. set-up: imports, the persistent compile cache (``<checkout>/.jax_cache``),
   the trainer with its weights made from ``--seed`` on the device, and
   ``WARMUP_ROUNDS`` whole rounds, after which every program the window
   uses has been compiled or loaded (counted by ``harness.compiles``).
   ``setup_s`` runs from process start to the end of the warm-up. The
   first steps of these rounds are recorded for the correctness check.
2. the window: whole outer rounds, timed by the host clock between the
   trainer's per-round ``log`` callbacks (each record fetch waits for its
   round), until ``--seconds`` have passed; the callback then stops the
   run. ``agent_steps_per_s`` is the IALS agent env-steps of the window's
   rounds (N x E x T x F each) over the window's wall time. With
   ``--trace 1`` the window is ``TRACE_ROUNDS`` rounds under
   ``jax.profiler`` instead, and the result carries the per-layer metrics
   read from the trace by ``bench/metrics/<name>.py``.
3. after the window, with the program's state freed: the plain reference
   follows the first steps from the same seed (``harness.correct``).

Earlier lines of stdout report the rounds, compilations inside the
window, peak device memory and the compile cache's counts; the numbers
compared for ``correct`` (and the count of programs compiled inside the
window, held to 0) are the last lines of stderr; the last line of
stdout is the result. Without a TPU holding the cell's chips the run
exits 2 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import catalog, correct, job as job_mod  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / "bench" / "out"
# round 0 collects into fresh buffers, rounds 1 and 2 fill the ring's two
# slots with the donating collect: after three rounds every program the
# window runs has been compiled or loaded
WARMUP_ROUNDS = 3
TRACE_ROUNDS = 3


class StopRun(Exception):
    """Raised from the round callback to end ``DIALSTrainer.run``."""


def enable_cache():
    """The program's persistent compile cache, in ``<checkout>/.jax_cache``
    and with no size limit: an environment that caps the cache's size
    would evict programs between runs, and every run would compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_compilation_cache_max_size", -1)


def seed_key(seed: int):
    import jax
    s = seed % 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def device_fault(devices, chips: int):
    """Why these devices cannot run a cell of ``chips`` chips, or None."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        return f"no TPU: JAX's backend is {plat!r}"
    if len(devices) < chips:
        return f"{len(devices)} TPU chip(s), the cell needs {chips}"
    return None


class Window:
    """The round callback: ends the warm-up, times the window, starts and
    stops the profiler, and stops the run. Beside each round's end it
    keeps the main thread's CPU time and the process's involuntary
    context switches, and in the window the garbage collector's pauses,
    to tell what a slow round waited for."""

    def __init__(self, warmup, seconds, trace_rounds, trace_dir, capture):
        self.warmup, self.seconds = warmup, seconds
        self.trace_rounds, self.trace_dir = trace_rounds, trace_dir
        self.capture, self.stamps = capture, []
        self.cpu, self.switches, self.pauses = [], [], []
        self.t_setup = self.t0 = self.t1 = None
        self.rounds = self.compiles = 0
        self.last = None
        self._gc_start = None

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.pauses.append((self._gc_start,
                                time.perf_counter() - self._gc_start,
                                info["generation"]))

    def slowest(self) -> str:
        """The window's slowest round: its seconds, the main thread's CPU
        seconds and the collector's pauses in it, and the involuntary
        context switches; then the collector's pauses in the window."""
        w = self.warmup
        k = max(range(w, w + self.rounds),
                key=lambda j: self.stamps[j] - self.stamps[j - 1])
        a, b = self.stamps[k - 1], self.stamps[k]
        in_round = sum(d for s, d, _ in self.pauses if a <= s < b)
        full = [d for _, d, g in self.pauses if g == 2]
        return (f"slowest round {b - a!r} s (main thread CPU "
                f"{self.cpu[k] - self.cpu[k - 1]!r} s, garbage collection "
                f"{in_round!r} s, {self.switches[k] - self.switches[k - 1]} "
                f"involuntary context switches); garbage collection in the "
                f"window: {len(self.pauses)} pauses, {len(full)} full, "
                f"longest {max((d for _, d, _ in self.pauses), default=0)!r}"
                f" s, total {sum(d for _, d, _ in self.pauses)!r} s")

    def log(self, rec):
        import jax
        from harness import compiles
        now = time.perf_counter()
        self.stamps.append(now)
        self.cpu.append(time.thread_time())
        self.switches.append(
            resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw)
        self.last = rec
        i = len(self.stamps)
        if i == self.warmup:
            if not self.capture.done:
                raise RuntimeError("warm-up ended before the first steps "
                                   "were recorded")
            self.t_setup, self._c0 = now, compiles.new_programs()
            gc.callbacks.append(self._gc)
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                with jax.profiler.TraceAnnotation("bench_window_start"):
                    pass
            self.t0 = time.perf_counter()
        elif i > self.warmup:
            n = i - self.warmup
            if (n >= self.trace_rounds if self.trace_dir
                    else now - self.t0 >= self.seconds):
                self.t1, self.rounds = now, n
                self.compiles = compiles.new_programs() - self._c0
                gc.callbacks.remove(self._gc)
                if self.trace_dir:
                    with jax.profiler.TraceAnnotation("bench_window_end"):
                        pass
                    jax.profiler.stop_trace()
                raise StopRun


class RunView:
    """What a per-layer metric's reader sees."""

    def __init__(self, trace, rounds, job, info, chips, device_kind):
        self.trace, self.rounds, self.job, self.info = trace, rounds, job, info
        self.chips, self.device_kind = chips, device_kind


def measure(job, key, seconds, trace_dir=None, warmup=WARMUP_ROUNDS):
    """Set-up and window of one run: builds the trainer, runs its
    ``warmup`` rounds and the window, and returns ``(Window, first steps
    recorded, the reference that follows them)``. Nothing of the trainer
    outlives the call."""
    from repro.core import dials
    trainer = dials.DIALSTrainer(*job_mod.program(job, outer_rounds=10 ** 9))
    n_shards = trainer._select_shards()
    if n_shards:
        capture = correct.RoundCapture(trainer, n_shards)
        reference = correct.reference_rounds
    else:
        capture = correct.LoopCapture(trainer)
        reference = correct.reference_steps
    win = Window(warmup, seconds, TRACE_ROUNDS, trace_dir, capture)
    try:
        trainer.run(key, log=win.log)
    except StopRun:
        pass
    win.capture = None
    return win, capture.got, reference


def result_line(ok, rounds, metrics, device, checks, breakdown=None):
    """The result: ``correct, attempted, failed, metrics, device``, then
    ``breakdown`` for a traced run, and last the numbers compared with
    their limits (``checks``). A round that fails raises, so none of the
    attempted rounds counts as failed."""
    out = {"correct": bool(ok), "attempted": rounds, "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = catalog.workload(args.workload)
    chips = cell["chips"]
    job = job_mod.make_job(cell["config_file"], cell["traffic_file"], chips)
    if job["aip_refresh"] < correct.INNER_STEPS:
        raise ValueError("the check reads the first inner steps of the "
                         "warm-up; F is too small")

    enable_cache()
    import jax
    import jax.numpy as jnp
    from repro import compile_cache
    from harness import compiles, trace as trace_mod
    from harness.ref.core import Ref

    compiles.install()
    fault = device_fault(jax.devices(), chips)
    if fault:
        print(f"bench: {fault}; refusing to run", file=sys.stderr)
        return 2
    used = jax.devices()[:chips]
    kind = used[0].device_kind

    key = seed_key(args.seed)
    trace_dir = None
    if args.trace:
        trace_dir = str(OUT_DIR / f"trace-{args.workload}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    win, got, reference = measure(job, key, args.seconds, trace_dir)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in used)
    window_s = win.t1 - win.t0
    info = Ref(job).info
    steps = job_mod.agent_steps_per_round(job, info.n_agents) * win.rounds
    cache = compile_cache.stats()
    gaps = sorted(b - a for a, b in zip(win.stamps[WARMUP_ROUNDS - 1:],
                                        win.stamps[WARMUP_ROUNDS:]))
    med = gaps[len(gaps) // 2]
    slow = [g for g in gaps if g > 1.1 * med]
    print(f"bench: {args.workload} seed {args.seed}: {win.rounds} rounds in "
          f"{window_s!r} s (round s min {gaps[0]!r} median {med!r} max "
          f"{gaps[-1]!r}; {len(slow)} rounds over 1.1x the median, "
          f"{sum(slow) - len(slow) * med!r} s over it), "
          f"compilations inside the window {win.compiles}, "
          f"peak device memory {mem} bytes, compile cache "
          f"{cache['loaded']} loaded / {cache['written']} written, "
          f"kernels {win.last['kernels']}, "
          f"n_shards {win.last['n_shards']}", flush=True)
    print(f"bench: {win.slowest()}", flush=True)
    # measure() has returned: the program's state is freed
    gc.collect()
    t_ref = time.perf_counter()
    nums = correct.readings(got, reference(job, key, jnp.float32, "highest"))
    print(f"bench: reference took {time.perf_counter() - t_ref!r} s",
          flush=True)
    ok = correct.verdict(nums, cell["limits"]) and win.compiles == 0

    device = {"platform": used[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": mem}
    breakdown = None
    if args.trace:
        tr = trace_mod.load(trace_dir)
        view = RunView(tr, win.rounds, job, info, chips, kind)
        metrics = {}
        for m in cell["per_layer"]:
            value = catalog.reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        breakdown = tr.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {
            "agent_steps_per_s": {"value": steps / window_s,
                                  "unit": "steps/s"},
            "setup_s": {"value": win.t_setup - T0, "unit": "s"}}
    checks = correct.report(nums, cell["limits"])
    checks["window_compiles"] = {"value": win.compiles, "limit": 0}
    result = result_line(ok, win.rounds, metrics, device, checks, breakdown)
    print(f"bench: {time.perf_counter() - T0!r} s from start to result",
          flush=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
