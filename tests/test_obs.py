"""Runtime telemetry (repro.obs): span tracer, typed round records,
JSONL sinks + cross-process merge, and the driver integration contract.

The load-bearing guarantees pinned here:

* disabled telemetry is genuinely free — no files, no events, spans
  that are bare profiler annotations, and the drivers' histories are
  numerically IDENTICAL with telemetry on vs off;
* both driver paths emit the exact typed key set
  (``metrics.ROUND_KEYS``) — schema drift between the loop and sharded
  drivers is what this PR killed;
* enabling telemetry does not change the sharded driver's traced round
  program (jaxpr equality) — the once-per-round host-sync contract
  cannot regress via observability;
* the per-process JSONL logs round-trip, merge in global ``(t, proc,
  seq)`` order, and tolerate a truncated tail (a SIGKILL'd host).
"""
import json
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import dials, influence
from repro.envs import registry
from repro.marl import policy as policy_mod, ppo as ppo_mod
from repro.obs import metrics, sinks, trace


# ---------------------------------------------------------------------------
# trace: spans, nesting, fencing, disabled mode
# ---------------------------------------------------------------------------
def test_tracer_records_nested_spans_with_depth():
    clock = iter(range(100)).__next__
    tr = trace.Tracer(clock=lambda: float(clock()))
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    # children are appended at exit, before their parent
    assert [e["name"] for e in tr.events] == ["inner", "outer"]
    assert [e["depth"] for e in tr.events] == [1, 0]
    by_name = {e["name"]: e for e in tr.events}
    assert by_name["inner"]["t0"] > by_name["outer"]["t0"]
    assert by_name["outer"]["dur_s"] > by_name["inner"]["dur_s"]


def test_phase_seconds_sums_per_name_and_resets():
    ticks = iter([0.0, 1.0, 10.0, 13.0, 20.0, 25.0]).__next__
    tr = trace.Tracer(clock=ticks)
    for _ in range(2):
        with tr.span("collect"):
            pass
    with tr.span("train"):
        pass
    phases = tr.phase_seconds()
    assert phases == {"collect": 4.0, "train": 5.0}
    tr.reset()
    assert tr.events == [] and tr.phase_seconds() == {}


def test_span_fence_only_blocks_when_tracer_fenced():
    fenced = trace.Tracer(fenced=True)
    x = jax.numpy.ones((4,))
    with fenced.span("s"):
        assert fenced.fence(x) is x       # returns the value either way
    unfenced = trace.Tracer()
    with unfenced.span("s"):
        assert unfenced.fence(x) is x
    assert fenced.fenced and not unfenced.fenced


def test_null_tracer_allocates_nothing():
    """The disabled tracer's span is the bare profiler annotation (so
    spans reach a profiler trace with telemetry off); the tracer itself
    records nothing and keeps no per-span state."""
    tr = trace.NULL_TRACER
    assert not tr.enabled
    s1 = tr.span("a")
    assert type(s1) is jax.profiler.TraceAnnotation
    with s1:
        assert tr.fence(123) == 123
    assert tr.pull("x", jax.numpy.asarray(2.5)) == 2.5
    assert tr.pull("n", jax.numpy.asarray(3), int) == 3
    assert vars(tr) == {}                 # nothing stored per span
    assert tr.events == [] and tr.phase_seconds() == {}
    assert tr.sync_seconds() is None


def test_pull_reads_inside_a_sync_span():
    ticks = iter([0.0, 1.0, 3.0, 3.5, 4.0, 12.0]).__next__
    tr = trace.Tracer(clock=ticks)
    with tr.span("dials.record"):
        v = tr.pull("gs_return", jax.numpy.asarray(1.5))
        n = tr.pull("staleness_max", jax.numpy.asarray(2), int)
    assert (v, n) == (1.5, 2) and type(v) is float and type(n) is int
    assert [e["name"] for e in tr.events] == [
        "dials.sync.gs_return", "dials.sync.staleness_max", "dials.record"]
    assert tr.sync_seconds() == 2.5       # the two reads, not the parent
    tr.reset()
    assert tr.sync_seconds() == 0


def test_profile_none_is_noop():
    with trace.profile(None):
        pass
    with trace.annotate("named"):          # named_scope pass-through
        _ = jax.numpy.zeros(())


# ---------------------------------------------------------------------------
# metrics: the typed round record
# ---------------------------------------------------------------------------
def _full_record(**over):
    base = dict(round=0, gs_return=np.float32(1.5), ials_reward=0.25,
                aip_ce_before=0.7, aip_ce_after=0.6, data_round=0,
                forced_sync=True, stale_forced=0, staleness_min=0,
                staleness_mean=0.0, staleness_max=0, n_shards=1,
                reassigned=0, dead_hosts=[], kernels="policy=oracle",
                collect_s=0.1, env_steps_per_s=None, aip_s=None,
                inner_s=None, eval_s=None, mirror_s=None, sync_s=None,
                round_s=0.5,
                wall_s=0.5)
    base.update(over)
    return base


def test_round_record_coerces_to_host_scalars():
    rec = metrics.round_record(**_full_record(
        round=np.int64(3), gs_return=jax.numpy.asarray(2.0),
        staleness_max=jax.numpy.asarray(1, jax.numpy.int32),
        dead_hosts=[np.int64(1)]))
    assert set(rec) == set(metrics.ROUND_KEYS)
    assert rec["round"] == 3 and type(rec["round"]) is int
    assert rec["gs_return"] == 2.0 and type(rec["gs_return"]) is float
    assert rec["dead_hosts"] == [1] and type(rec["dead_hosts"][0]) is int
    assert rec["aip_s"] is None           # explicit null, key present
    json.dumps(rec)                       # JSON-serializable as built


def test_round_record_rejects_drift():
    with pytest.raises(TypeError, match="unknown"):
        metrics.round_record(**_full_record(), extra_key=1)
    partial = _full_record()
    partial.pop("gs_return")
    with pytest.raises(TypeError, match="missing"):
        metrics.round_record(**partial)
    with pytest.raises(TypeError, match="not.*nullable"):
        metrics.round_record(**_full_record(gs_return=None))
    # nullable fields accept None
    rec = metrics.round_record(**_full_record(ials_reward=None))
    assert rec["ials_reward"] is None


def test_validate_round_catches_type_and_key_problems():
    good = metrics.round_record(**_full_record())
    assert metrics.validate_round(good) == []
    # envelope fields are ignored
    assert metrics.validate_round({**good, "event": "round", "proc": 0,
                                   "seq": 1, "t": 0.0}) == []
    bad = dict(good)
    bad["round"] = True                   # bool is not an int here
    bad["gs_return"] = "high"
    bad.pop("n_shards")
    bad["surprise"] = 1
    problems = "\n".join(metrics.validate_round(bad))
    assert "'round'" in problems and "'gs_return'" in problems
    assert "missing field 'n_shards'" in problems
    assert "unknown field 'surprise'" in problems


def test_staleness_stats_traces_under_jit():
    reports = jax.numpy.asarray([3, 1, 2], jax.numpy.int32)
    stats = jax.jit(lambda r: metrics.staleness_stats(r, 3))(reports)
    assert int(stats["staleness_min"]) == 0
    assert int(stats["staleness_max"]) == 2
    np.testing.assert_allclose(float(stats["staleness_mean"]), 1.0)


def test_kernel_summary_resolves_dispatch():
    pc = policy_mod.PolicyConfig(obs_dim=2, n_actions=2)
    ac = influence.AIPConfig(in_dim=2, n_sources=1)
    ppo_cfg = ppo_mod.PPOConfig()
    s = metrics.kernel_summary(pc, ac, ppo_cfg)
    parts = dict(p.split("=") for p in s.split(","))
    assert set(parts) == {"policy", "aip", "ppo"}
    assert all(v in ("oracle", "pallas", "pallas-interpret")
               for v in parts.values())


def test_validate_bench_row_scaling_and_kernels():
    row = {"label": "t-s2", "scenario": "t", "n_agents": 4, "shards": 2,
           "processes": 1, "streams": 4, "fused": True, "round_s": 1.0,
           "round_s_async": 0.8, "overlap_speedup": 1.25,
           "inner_steps_per_s": 100.0, "inner_steps_per_s_async": 125.0,
           "total_wall_s": 5.0, "total_wall_s_async": 4.0,
           "collect_s": 0.2, "env_steps_per_s": 640.0,
           "collect_s_sharded_gs": None, "gs_speedup": None}
    assert metrics.validate_bench_row(row, metrics.SCALING_ROW_SCHEMA) == []
    bad = {**row, "shards": "2", "mystery": 1, "round_s": None}
    probs = "\n".join(metrics.validate_bench_row(
        bad, metrics.SCALING_ROW_SCHEMA))
    assert "'shards'" in probs and "'mystery'" in probs
    assert "'round_s' is null" in probs
    # gae micro rows legitimately lack the in/H columns
    gae = {"kernel": "gae", "label": "x", "B": 4, "T": 8,
           "fwd_oracle_s": 1e-4, "fwd_kernel_s": 1e-4,
           "fwdbwd_oracle_s": 1e-4, "fwdbwd_kernel_s": 1e-4,
           "speedup_fwd": 1.0, "speedup_fwdbwd": 1.0,
           "roofline_fwd": {}, "roofline_fwdbwd": {}}
    assert metrics.validate_bench_row(
        gae, metrics.KERNELS_MICRO_SCHEMA) == []
    assert metrics.validate_bench_row(
        {"program": "train_aip", "label": "w", "oracle_s": 1.0,
         "kernel_s": 0.5, "speedup": 2.0},
        metrics.KERNELS_E2E_SCHEMA) == []


def test_phase_breakdown_renders_phase_columns():
    row = {"program": "p", "label": "l", "oracle_s": 0.125,
           "kernel_s": None, "speedup": 2.0}
    out = metrics.phase_breakdown(row, metrics.KERNELS_E2E_SCHEMA)
    assert out == "oracle_s=0.125 kernel_s=None"


# ---------------------------------------------------------------------------
# sinks: JSONL round-trip, merge order, truncation tolerance
# ---------------------------------------------------------------------------
def test_jsonl_roundtrip_and_merge_order(tmp_path):
    d = str(tmp_path)
    t0 = obs.Telemetry(d, process_id=0, tracer=trace.Tracer())
    t1 = obs.Telemetry(d, process_id=1, tracer=trace.Tracer())
    # interleave out of file order; merge must re-order globally by
    # (t, proc, seq)
    t1.emit("round", **metrics.round_record(**_full_record(round=0)))
    t0.emit("run_start", path="loop")
    t0.emit("round", **metrics.round_record(**_full_record(round=0)))
    t1.emit("round", **metrics.round_record(**_full_record(round=1)))
    t0.close()
    t1.close()
    merged = sinks.merge_dir(d)
    assert merged == os.path.join(d, sinks.MERGED_NAME)
    events = sinks.read_jsonl(merged)
    assert len(events) == 4
    keys = [(e["t"], e["proc"], e["seq"]) for e in events]
    assert keys == sorted(keys)
    # per-proc seq is monotone from 0
    assert [e["seq"] for e in events if e["proc"] == 0] == [0, 1]
    # a second merge is idempotent (the merged file is not re-ingested)
    events2 = sinks.read_jsonl(sinks.merge_dir(d))
    assert events2 == events


def test_read_jsonl_tolerates_truncated_tail(tmp_path):
    path = str(tmp_path / "telemetry-p0.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"event": "round", "seq": 0}) + "\n")
        f.write(json.dumps({"event": "round", "seq": 1}) + "\n")
        f.write('{"event": "round", "se')        # SIGKILL mid-write
    events = sinks.read_jsonl(path)
    assert [e["seq"] for e in events] == [0, 1]


def test_csv_sink_renders_rounds_only(tmp_path):
    path = str(tmp_path / "rounds.csv")
    sink = sinks.CsvSink(path)
    sink.write({"event": "run_start", "proc": 0})
    sink.write({"event": "round", "proc": 0,
                **metrics.round_record(**_full_record(dead_hosts=[1, 2]))})
    sink.close()
    lines = open(path).read().strip().splitlines()
    assert len(lines) == 2                 # header + one round
    assert lines[0].split(",") == ["proc"] + list(metrics.ROUND_KEYS)
    assert "1;2" in lines[1]               # list serialization


# ---------------------------------------------------------------------------
# the Telemetry facade + disabled mode
# ---------------------------------------------------------------------------
def test_disabled_telemetry_creates_no_files(tmp_path):
    tel = obs.maybe(None)
    assert tel is obs.DISABLED and not tel.enabled
    assert tel.emit("round", x=1) is None
    assert tel.emit_round({"round": 0}) is None
    with tel.tracer.span("phase"):
        assert tel.tracer.fence(5) == 5
    assert tel.tracer.phase_seconds() == {} and tel.merge() is None
    tel.close()
    assert os.listdir(tmp_path) == []      # really nothing written


def test_telemetry_emit_wraps_envelope(tmp_path):
    tel = obs.Telemetry.create(str(tmp_path), process_id=7)
    r1 = tel.emit("run_start", path="loop")
    r2 = tel.emit("run_end", rounds=3)
    tel.close()
    assert (r1["proc"], r1["seq"]) == (7, 0)
    assert (r2["proc"], r2["seq"]) == (7, 1)
    assert r2["t"] >= r1["t"]
    events = sinks.read_jsonl(sinks.proc_path(str(tmp_path), 7))
    assert [e["event"] for e in events] == ["run_start", "run_end"]


# ---------------------------------------------------------------------------
# telemetry_report: the CLI over a synthetic incident log
# ---------------------------------------------------------------------------
def _incident_events():
    events = []
    for rnd, shards in ((0, 4), (1, 4), (2, 2)):
        rec = metrics.round_record(**_full_record(
            round=rnd, n_shards=shards,
            reassigned=2 if rnd == 2 else 0,
            dead_hosts=[1] if rnd == 2 else [],
            mirror_s=0.01))
        events.append({"event": "round", "proc": 0, "seq": rnd,
                       "t": float(rnd), **rec})
    events.insert(2, {"event": "host_death", "proc": 0, "seq": 10,
                      "t": 1.5, "round": 2, "dead_hosts": [1],
                      "timeout_s": 5.0})
    events.insert(3, {"event": "elastic_reassign", "proc": 0, "seq": 11,
                      "t": 1.6, "old_shards": 4, "new_shards": 2,
                      "dead_blocks": [2, 3], "moved": {"2": 1, "3": 1}})
    return events


def test_report_tables_and_check(tmp_path):
    from tools import telemetry_report
    path = str(tmp_path / "run.jsonl")
    with open(path, "w") as f:
        for e in _incident_events():
            f.write(json.dumps(e) + "\n")
    events = telemetry_report.load_events(path)
    assert telemetry_report.check(events) == []
    table = telemetry_report.round_table(events)
    assert table.count("\n") == 3          # header + 3 rounds
    timeline = telemetry_report.elasticity_timeline(events)
    assert "host_death" in timeline
    assert "4->2" in timeline
    assert "resumed on 2-shard mesh" in timeline
    assert telemetry_report.main([path, "--check"]) == 0
    # a corrupted record makes --check fail
    with open(path, "a") as f:
        f.write(json.dumps({"event": "round", "proc": 0, "seq": 99,
                            "t": 9.0, "round": 3}) + "\n")
    assert telemetry_report.main([path, "--check"]) == 1


def test_report_check_rejects_empty_and_non_monotone(tmp_path):
    from tools import telemetry_report
    assert telemetry_report.check([]) == ["no events"]
    assert "no round events" in telemetry_report.check(
        [{"event": "run_start", "proc": 0, "seq": 0, "t": 0.0}])
    rec = metrics.round_record(**_full_record())
    stream = [{"event": "round", "proc": 0, "seq": 0, "t": 0.0,
               **dict(rec, round=1)},
              {"event": "round", "proc": 0, "seq": 1, "t": 1.0,
               **dict(rec, round=0)}]
    assert any("not monotone" in p for p in telemetry_report.check(stream))


# ---------------------------------------------------------------------------
# driver integration (loop path is cheap enough for tier 1)
# ---------------------------------------------------------------------------
def _build_trainer(**kw):
    env_mod, cfg = registry.make("traffic", horizon=16)
    info = cfg.info()
    pc = policy_mod.PolicyConfig(obs_dim=info.obs_dim,
                                 n_actions=info.n_actions, hidden=(16,))
    ac = influence.AIPConfig(in_dim=info.alsh_dim,
                             n_sources=info.n_influence, kind="fnn",
                             hidden=(16,), epochs=2, batch=16)
    ppo_cfg = ppo_mod.PPOConfig(epochs=1, minibatches=2)
    kw.setdefault("shards", 1)
    kw.setdefault("outer_rounds", 2)
    kw.setdefault("aip_refresh", 2)
    dcfg = dials.DIALSConfig(
        collect_envs=2, collect_steps=16,
        n_envs=2, rollout_steps=8, eval_episodes=2, **kw)
    return dials.DIALSTrainer(env_mod, cfg, pc, ac, ppo_cfg, dcfg)


def test_loop_driver_emits_schema_clean_rounds(tmp_path):
    tel_dir = str(tmp_path / "tel")
    _, h_off = _build_trainer().run(jax.random.PRNGKey(0))
    _, h_on = _build_trainer(telemetry_dir=tel_dir).run(
        jax.random.PRNGKey(0))
    # history keys are exactly the typed schema, telemetry on or off
    for rec in h_off + h_on:
        assert set(rec) == set(metrics.ROUND_KEYS)
        assert metrics.validate_round(rec) == []
    # telemetry is observation only: numerics identical
    assert [r["gs_return"] for r in h_on] == \
        [r["gs_return"] for r in h_off]
    assert [r["aip_ce_after"] for r in h_on] == \
        [r["aip_ce_after"] for r in h_off]
    # the event log: run_start, one round per outer round, run_end
    events = sinks.read_jsonl(sinks.proc_path(tel_dir, 0))
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert kinds.count("round") == 2
    rounds = [e for e in events if e["event"] == "round"]
    assert all(metrics.validate_round(e) == [] for e in rounds)
    # loop path measures real phases, and the host seconds of its reads
    assert all(e["collect_s"] > 0 and e["inner_s"] > 0 and
               e["eval_s"] > 0 and 0 < e["sync_s"] < e["round_s"]
               for e in rounds)
    assert all(e["mirror_s"] is None for e in rounds)
    assert all(r["sync_s"] is None for r in h_off)
    from tools import telemetry_report
    assert telemetry_report.check(events) == []


def test_loop_driver_without_inner_steps_emits_null_reward(tmp_path):
    _, hist = _build_trainer(aip_refresh=0, outer_rounds=1).run(
        jax.random.PRNGKey(0))
    assert hist[0]["ials_reward"] is None
    assert set(hist[0]) == set(metrics.ROUND_KEYS)


# ---------------------------------------------------------------------------
# sharded path (1-shard mesh on the single real CPU device)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_sharded_driver_record_parity_and_jaxpr_unchanged(tmp_path):
    """The sharded driver's records carry the same typed key set as the
    loop driver's, and enabling telemetry leaves the traced round
    program structurally identical — observability cannot cost a host
    sync (asserted via the analysis walker's fingerprint plus the
    ScalarSyncBudget contract, not jaxpr string equality)."""
    _, h_loop = _build_trainer().run(jax.random.PRNGKey(0))

    plain = _build_trainer()
    state = plain.restore_or_init(jax.random.PRNGKey(0))
    _, h_plain = plain._run_sharded(state, 1, log=None,
                                    straggler_mask=None)

    tel_dir = str(tmp_path / "tel")
    teled = _build_trainer(telemetry_dir=tel_dir)
    state = teled.restore_or_init(jax.random.PRNGKey(0))
    _, h_tel = teled._run_sharded(state, 1, log=None, straggler_mask=None)

    for rec in h_plain + h_tel:
        assert set(rec) == set(metrics.ROUND_KEYS)
        assert metrics.validate_round(rec) == []
    assert {tuple(sorted(r)) for r in h_loop} == \
        {tuple(sorted(r)) for r in h_plain}          # driver parity
    # telemetry changes nothing the math can see
    assert [r["gs_return"] for r in h_tel] == \
        [r["gs_return"] for r in h_plain]
    # same primitive multiset at every program path — telemetry may not
    # add (or move) a single operation in the traced round
    import jax.numpy as jnp
    from repro.analysis import contracts, walker
    assert walker.fingerprint(teled._sharded.round_jaxpr()) == \
        walker.fingerprint(plain._sharded.round_jaxpr())
    # the once-per-round sync contract: the record half of the round
    # output is scalars from the typed schema, nothing else
    for runner in (plain._sharded, teled._sharded):
        carry = runner._abstract_carry()
        mask = jax.ShapeDtypeStruct(
            (plain.info.n_agents,), jnp.float32)
        prog = contracts.Program(
            name="test/round", roles=("round",), fn=runner.round,
            args=(carry, jax.ShapeDtypeStruct((2,), jnp.uint32),
                  jax.ShapeDtypeStruct((), jnp.int32), mask))
        assert contracts.ScalarSyncBudget().check(prog) == []
    # fused path: phase columns are explicit nulls, staleness on-mesh
    for r in h_plain:
        assert r["collect_s"] is None and r["aip_s"] is None
        assert r["staleness_max"] >= r["staleness_min"] >= 0
    events = sinks.read_jsonl(sinks.proc_path(tel_dir, 0))
    assert [e["event"] for e in events if e["event"] == "round"] != []


@pytest.mark.slow
def test_sharded_async_records_obtain_wait(tmp_path):
    tel_dir = str(tmp_path / "tel")
    tr = _build_trainer(async_collect=True, outer_rounds=3,
                        telemetry_dir=tel_dir)
    state = tr.restore_or_init(jax.random.PRNGKey(0))
    _, hist = tr._run_sharded(state, 1, log=None, straggler_mask=None)
    # async split path: collect_s is the obtain wait, a real number
    assert all(isinstance(r["collect_s"], float) for r in hist)
    events = sinks.read_jsonl(sinks.proc_path(tel_dir, 0))
    obtains = [e for e in events if e["event"] == "collect_obtain"]
    assert len(obtains) == 3
    assert obtains[0]["forced"] is True            # priming round
    assert [e["data_round"] for e in obtains] == [0, 0, 1]
