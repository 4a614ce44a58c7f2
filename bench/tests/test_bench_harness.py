"""The benchmark harness without a chip: loading by name, the operation
and byte counts, the trace reducer on a trace recorded on a TPU v5e, the
result line, and the refusal to run anywhere but on a TPU."""
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import run
import tiny
from harness import catalog, correct, counts, job as job_mod, peaks, trace
from harness.ref.backbones import gru
from harness.ref.core import Ref

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


# -- loading by name ----------------------------------------------------------
@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_loads_by_name(name):
    cell = catalog.workload(name)
    job = job_mod.make_job(cell["config_file"], cell["traffic_file"],
                           cell["chips"])
    assert cell["config_file"]["name"] == cell["config"]
    numbers = correct.NUMBERS if cell["chips"] == 1 else \
        correct.ROUND_NUMBERS
    assert set(cell["limits"]) == set(numbers)
    assert all(v > 0 for v in cell["limits"].values())
    names = {m["name"] for m in cell["end_to_end"]}
    assert names == {"agent_steps_per_s", "setup_s"}
    assert cell["per_layer"], "every cell reports a per-layer metric"
    assert job["aip_refresh"] >= correct.INNER_STEPS
    assert job["shards"] == (1 if cell["chips"] == 1 else None)
    assert run.WARMUP_ROUNDS >= 3   # round 0 collect, two ring fills
    assert run.WARMUP_ROUNDS >= correct.ROUND_STEPS


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(catalog.reader(metric))


def test_config_files_are_the_configs_entries():
    for c in SPEC["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"] == []
        assert (data["dtype"], data["matmul_precision"]) == ("float32",
                                                             "default")


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        catalog.workload("no-such-cell")
    with pytest.raises(KeyError):
        job_mod.make_job({}, {}, 1)


def test_program_objects_follow_the_job():
    env_mod, env_cfg, pc, ac, ppo_cfg, cfg = job_mod.program(
        tiny.job(), outer_rounds=7)
    assert env_cfg.info().n_agents == 4
    assert pc.hidden == (16, 8) and ac.epochs == 3 and ppo_cfg.epochs == 2
    assert (cfg.aip_refresh, cfg.outer_rounds, cfg.collect_envs,
            cfg.n_envs, cfg.rollout_steps) == (3, 7, 3, 4, 4)


# -- counts ---------------------------------------------------------------------
def test_network_flops_by_hand():
    info = Ref(tiny.job()).info      # obs 34, 2 actions, ALSH 36, 4 sources
    # policy 34 -> 16 -> 8 -> (2 logits + 1 value), 2 flops a multiply-add
    assert counts.policy_fwd_flops(tiny.job(), info) == \
        2 * (34 * 16 + 16 * 8) + 2 * 8 * 3
    # AIP 36 -> 8 -> 8 -> 4 heads
    assert counts.aip_fwd_flops(tiny.job(), info) == \
        2 * (36 * 8 + 8 * 8) + 2 * 8 * 4
    g = tiny.gru_job()
    ginfo = Ref(g).info              # obs 37, 5 actions, ALSH 42, 12 sources
    assert counts.policy_fwd_flops(g, ginfo) == \
        2 * (37 * 16 + 16 * 8) + 2 * (8 * 24 + 8 * 24) + 2 * 8 * 6


def test_round_flops_by_hand():
    info = Ref(tiny.job()).info
    pol, aip = 1392, 768
    collect = 3 * 8 * 4 * pol                  # S x T x N policy steps
    # held-out CE before and after (1 stream), then 3 epochs of one
    # minibatch of the 2 training streams, forward + backward
    aip_round = 2 * 1 * 8 * 4 * aip + 3 * 1 * 2 * 8 * 4 * 3 * aip
    # per inner step and agent: E x T rollout steps (policy + AIP), the
    # bootstrap value over E streams, 2 epochs x 2 minibatches of 2
    # streams x 4 steps of PPO forward + backward
    inner = 3 * 4 * (4 * 4 * (pol + aip) + 4 * pol + 2 * 2 * 2 * 4 * 3 * pol)
    evaluate = 1 * 100 * 4 * pol               # episodes x horizon x N
    assert counts.round_matmul_flops(tiny.job(), info) == \
        collect + aip_round + inner + evaluate == 3267072


def test_kernel_counts_by_hand():
    assert counts.gae_fwd(16, 4) == (8 * 64, 4 * 5 * 64)
    # b=2, t=3, h=4: 6 row-steps of (6 h^2 + 14 h) flops; bytes: gi 3h,
    # reset flag, hs h per row-step, W_h, b_h and h0 once
    assert gru.gru_fwd(2, 3, 4) == (6 * (6 * 16 + 56),
                                    4 * (6 * 17 + 48 + 12 + 8))
    assert gru.gru_bwd(2, 3, 4) == (6 * (12 * 16 + 120),
                                    4 * (6 * 33 + 2 * 60 + 8))
    ops = counts.kernel_ops("gru", tiny.gru_job(), Ref(tiny.gru_job()).info)
    # policy: collect steps, (T + 1) x F rollout cells, PPO fwd + bwd
    # per minibatch, eval steps; AIP: T x F cells, 2 held-out CEs,
    # fwd + bwd per training minibatch
    assert len(ops) == 8 + 5 * 3 + 2 * 3 * 2 * 2 + 100 + 4 * 3 + 2 + 2 * 3
    assert counts.kernel_ops("gru", tiny.job(), Ref(tiny.job()).info) == []


def test_least_time_takes_the_larger_bound():
    p = peaks.peaks("TPU v5 lite")
    assert peaks.least_seconds(p["flops"], 0.0, "TPU v5 lite") == 1.0
    assert peaks.least_seconds(0.0, p["hbm_bytes_per_s"],
                               "TPU v5 lite") == 1.0
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


# -- trace reducer ----------------------------------------------------------------
def test_union_and_gaps():
    assert trace.union([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert trace.union([]) == 0
    assert trace.merged([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]


def test_opcode_and_kernel_kinds():
    name = ("%all-gather.3 = f32[4,8]{1,0:T(8,128)} all-gather(f32[1,8]"
            "{1,0} %p), replica_groups={{0,1,2,3}}")
    assert trace.opcode(name) == "all-gather"
    tup = ("%while.8 = (s32[]{:T(128)}, f32[100,2]{1,0:T(8,128)}) while("
           "(s32[], f32[100,2]) %tuple), body=%b")
    assert trace.opcode(tup) == "while"
    cc = ', custom_call_target="tpu_custom_call", operand_layout_constraints'
    gae = ("%gae_fwd.3 = f32[16,100]{1,0} custom-call(f32[16,100]{1,0} %a, "
           "f32[16,100]{1,0} %b, f32[16,100]{1,0} %c, f32[16,100]{1,0} %d)"
           + cc)
    gru = ("%gru_bwd.6 = (f32[16,8,192]{2,1,0}, f32[64,192]{1,0}) "
           "custom-call(f32[16,8,192]{2,1,0} %a, f32[64,192]{1,0} %b)" + cc)
    # a made-up kernel with GAE's operands is its own kind, not GAE's
    ssd = gae.replace("%gae_fwd.3", "%ssd_fwd.1")
    assert trace.kernel_kind(gae) == "gae"
    assert trace.kernel_kind(gru) == "gru"
    assert trace.kernel_kind(ssd) == "ssd"
    assert trace.kernel_kind(gae.replace(cc, ", custom_call_target=\"x\""
                                         )) is None


def test_recorded_trace_reduces():
    """A trace recorded on a TPU v5e (``record_trace.py``) of one jitted
    GAE gradient (the GAE backward kernel, named
    ``transpose_jvp_gae_bwd__``), one GRU-sequence gradient (``gru_fwd``
    and ``gru_bwd``) and a matmul, between the harness's window marks."""
    from jax.profiler import ProfileData
    t = trace.reduce(ProfileData.from_file(str(DATA / "tiny.xplane.pb")))
    assert len(t.devices) == 1
    d = t.devices[0]
    assert t.window_s == pytest.approx(2.535031e-3)
    assert t.busy_s() == pytest.approx(2.1254e-5)
    assert d.kernel_events("gae") == 1 and d.kernel_events("gru") == 2
    assert d.kernel_seconds("gae") == pytest.approx(1.41e-7)
    assert d.kernel_seconds("gru") == pytest.approx(1.341e-5)
    assert d.module_seconds("jit__lambda") == pytest.approx(2.1834e-5)
    assert d.collective_seconds() == 0.0
    b = t.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "jit__lambda/gru_bwd.1"
    assert sum(s for _, s in b["device_ops"]) <= t.busy_s() + 1e-12
    gaps = sum(s for _, s in b["idle_gaps"])
    assert gaps <= t.window_s - t.busy_s() + 1e-12


class _View:
    def __init__(self, t, job):
        self.trace, self.rounds, self.job = t, 2, job
        self.info, self.chips = Ref(job).info, 1
        self.device_kind = "TPU v5 lite"


def test_readers_on_the_recorded_trace():
    from jax.profiler import ProfileData
    t = trace.reduce(ProfileData.from_file(str(DATA / "tiny.xplane.pb")))
    view = _View(t, tiny.gru_job())
    idle = catalog.reader("device_idle_share")(view)
    assert idle == pytest.approx(100 * (1 - 2.1254e-5 / 2.535031e-3))
    least = sum(peaks.least_seconds(f, b, "TPU v5 lite")
                for f, b in counts.gae_ops(view.job, view.info))
    assert catalog.reader("gae_roofline")(view) == \
        pytest.approx(100 * least * 2 / 1.41e-7)
    least = sum(peaks.least_seconds(f, b, "TPU v5 lite")
                for f, b in counts.kernel_ops("gru", view.job, view.info))
    assert catalog.reader("gru_roofline")(view) == \
        pytest.approx(100 * least * 2 / 1.341e-5)
    flops = counts.round_matmul_flops(view.job, view.info) * 2
    assert catalog.reader("round_mfu")(view) == \
        pytest.approx(100 * flops / (2.535031e-3 * 197e12))
    # programs that are not in the trace read nothing, not 0
    for name in ("collect_ms", "aip_round_ms", "inner_ms", "gs_eval_ms",
                 "collective_ms"):
        assert catalog.reader(name)(view) is None


# -- the result line and the refusal --------------------------------------------------
def test_result_line_schema():
    checks = correct.report(dict.fromkeys(correct.NUMBERS, 0.0),
                            dict.fromkeys(correct.NUMBERS, 1.0))
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 1}
    line = run.result_line(True, 12, {"setup_s": {"value": 1.0, "unit": "s"}},
                           dev, checks)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    traced = run.result_line(False, 3, {}, dict(dev, busy_s=1.0, window_s=2.0),
                             checks, {"device_ops": [], "idle_gaps": []})
    assert list(traced)[-2:] == ["breakdown", "checks"]
    assert traced["correct"] is False
    json.loads(json.dumps(traced))
    assert all(set(c) == {"value", "limit"} for c in checks.values())


def test_seed_keys_take_large_seeds():
    big, small = run.seed_key(2 ** 33 + 5), run.seed_key(5)
    assert big.shape == small.shape == run.seed_key(3000000001).shape
    assert bool((big != small).any())


def test_device_fault_refuses_anything_but_a_tpu():
    import jax
    assert "no TPU" in run.device_fault(jax.devices(), 1)

    class Tpu:
        platform = "tpu"
    assert run.device_fault([Tpu()], 1) is None
    assert "needs 4" in run.device_fault([Tpu()], 4)


def _run_bench(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "traffic10.f50",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_backend():
    p = _run_bench(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    p = _run_bench(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode not in (0, None)
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert math.isfinite(p.returncode)


def test_compile_counter_sees_new_shapes_only():
    import jax
    import jax.numpy as jnp
    from harness import compiles
    compiles.install()
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones(7)
    f(x)
    before = compiles.new_programs()
    f(x)
    assert compiles.new_programs() == before
    f(jnp.ones(9))
    assert compiles.new_programs() > before
