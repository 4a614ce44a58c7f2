"""Typed record schemas for DIALS runtime telemetry.

This module is the single source of truth for every record the runtime
emits: the per-round record both drivers produce, the envelope the
JSONL sinks wrap events in, and the benchmark-row schemas
``benchmarks/check_bench.py`` gates against. Free-form dicts drifted
between the loop and sharded drivers (the ``aip_refresh == 0`` loop
branch simply dropped keys); everything now goes through
:func:`round_record`, which enforces the exact key set and coerces
values to host scalars.

Round-record schema (one JSONL event per outer Algorithm-1 round, field
order is :data:`ROUND_FIELDS`):

======================  =======  ========  =====================================
field                   type     nullable  meaning
======================  =======  ========  =====================================
``round``               int      no        outer round index (0-based)
``gs_return``           float    no        mean GS evaluation return
``ials_reward``         float    yes       mean inner-loop reward of the last
                                           IALS step (null when
                                           ``aip_refresh == 0`` — no inner
                                           steps ran)
``aip_ce_before``       float    no        influence CE before the AIP refresh
``aip_ce_after``        float    no        influence CE after the AIP refresh
``data_round``          int      no        collection round of the dataset
                                           trained on this round
``forced_sync``         bool     no        async collect fell back to a
                                           synchronous collect
                                           (``max_aip_staleness`` exceeded)
``stale_forced``        int      no        agents force-refreshed by the
                                           freshness gate this round
``staleness_min``       int      no        min over agents of
                                           ``round - report_round`` (data-round
                                           lag), computed on-mesh
``staleness_mean``      float    no        mean data-round lag over agents
``staleness_max``       int      no        max data-round lag over agents
``n_shards``            int      no        shards in the mesh this round
                                           (1 on the unfused loop path)
``reassigned``          int      no        agent blocks moved by elastic
                                           replanning this round
``dead_hosts``          list     no        hosts declared dead this round
                                           (empty most rounds)
``kernels``             str      no        resolved kernel dispatch, e.g.
                                           ``policy=pallas,aip=oracle,...``
``collect_s``           float    yes       GS collect seconds (loop path: real
                                           span; sharded async: obtain wait;
                                           null when fused into the round
                                           program)
``env_steps_per_s``     float    yes       GS env-steps simulated per second,
                                           ``S * collect_steps / collect_s``
                                           (loop sync path only — null when
                                           the collect is async-overlapped or
                                           fused, where the span is not a
                                           throughput)
``aip_s``               float    yes       AIP-refresh seconds (loop path only)
``inner_s``             float    yes       F inner IALS+PPO steps seconds
                                           (loop path only)
``eval_s``              float    yes       GS evaluation seconds (loop path
                                           only)
``mirror_s``            float    yes       host-mirror ``fetch_tree`` seconds —
                                           the elasticity availability tax
                                           (null when elasticity is off)
``sync_s``              float    yes       host seconds inside this round's
                                           ``dials.sync.*`` spans, the
                                           blocking device-to-host reads
                                           (null when telemetry is off)
``round_s``             float    no        wall seconds for this round, up
                                           to and including the reads of
                                           its record
``wall_s``              float    no        cumulative wall seconds since run
                                           start (monotone per process)
======================  =======  ========  =====================================

Null phase columns are *explicit*: the sharded driver runs the whole
round as one fused jitted program, so per-phase host timings do not
exist there — the record says so with ``null`` rather than omitting the
key. The loop path's phase columns read the driver's ``dials.*``
spans (``repro.obs.trace``). Unfenced spans measure dispatch-enqueue
time (JAX is async);
``DIALSConfig.telemetry_fence`` buys honest device timings at the cost
of extra host syncs and is therefore off by default.

Sink envelope: every JSONL line carries ``event`` (record type, e.g.
``"round"``, ``"host_death"``, ``"elastic_reassign"``), ``proc``
(emitting process index), ``seq`` (per-process monotone counter) and
``t`` (unix seconds) in addition to the payload —
:data:`ENVELOPE_FIELDS`, ignored by :func:`validate_round`.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# (name, type, nullable) — order is the canonical (CSV) column order
ROUND_FIELDS: Tuple[Tuple[str, type, bool], ...] = (
    ("round", int, False),
    ("gs_return", float, False),
    ("ials_reward", float, True),
    ("aip_ce_before", float, False),
    ("aip_ce_after", float, False),
    ("data_round", int, False),
    ("forced_sync", bool, False),
    ("stale_forced", int, False),
    ("staleness_min", int, False),
    ("staleness_mean", float, False),
    ("staleness_max", int, False),
    ("n_shards", int, False),
    ("reassigned", int, False),
    ("dead_hosts", list, False),
    ("kernels", str, False),
    ("collect_s", float, True),
    ("env_steps_per_s", float, True),
    ("aip_s", float, True),
    ("inner_s", float, True),
    ("eval_s", float, True),
    ("mirror_s", float, True),
    ("sync_s", float, True),
    ("round_s", float, False),
    ("wall_s", float, False),
)

ROUND_KEYS: Tuple[str, ...] = tuple(f[0] for f in ROUND_FIELDS)

ENVELOPE_FIELDS: Tuple[str, ...] = ("event", "proc", "seq", "t")


def _coerce(name: str, typ: type, value):
    if typ is bool:
        return bool(value)
    if typ is int:
        return int(value)
    if typ is float:
        return float(value)
    if typ is list:
        return [int(v) for v in value]
    return str(value)             # typ is str


def round_record(**fields) -> Dict:
    """Build a validated round record: the key set must be exactly
    :data:`ROUND_KEYS`, nulls only on nullable fields, values coerced to
    host scalars. The drivers pass host scalars, read through
    ``Tracer.pull``; a device scalar is accepted and read here, outside
    any ``dials.sync.*`` span."""
    extra = set(fields) - set(ROUND_KEYS)
    if extra:
        raise TypeError(f"unknown round-record fields: {sorted(extra)}")
    missing = set(ROUND_KEYS) - set(fields)
    if missing:
        raise TypeError(f"missing round-record fields: {sorted(missing)}")
    rec = {}
    for name, typ, nullable in ROUND_FIELDS:
        value = fields[name]
        if value is None:
            if not nullable:
                raise TypeError(f"round-record field {name!r} is not "
                                f"nullable")
            rec[name] = None
        else:
            rec[name] = _coerce(name, typ, value)
    return rec


def validate_round(rec: Dict, *, ignore=ENVELOPE_FIELDS) -> List[str]:
    """Problems (empty list = valid) with a round record, e.g. one read
    back from a JSONL sink. Envelope fields are ignored."""
    problems = []
    got = {k for k in rec if k not in ignore}
    for k in sorted(got - set(ROUND_KEYS)):
        problems.append(f"unknown field {k!r}")
    for k in sorted(set(ROUND_KEYS) - got):
        problems.append(f"missing field {k!r}")
    for name, typ, nullable in ROUND_FIELDS:
        if name not in rec:
            continue
        value = rec[name]
        if value is None:
            if not nullable:
                problems.append(f"field {name!r} is null but not nullable")
            continue
        ok = (isinstance(value, bool) if typ is bool else
              isinstance(value, int) and not isinstance(value, bool)
              if typ is int else
              isinstance(value, (int, float)) and not isinstance(value,
                                                                 bool)
              if typ is float else
              isinstance(value, typ))
        if not ok:
            problems.append(f"field {name!r}: expected {typ.__name__}, "
                            f"got {type(value).__name__} ({value!r})")
    return problems


def staleness_stats(reports, current_round):
    """Per-agent data-round lag distribution, as traced jnp scalars.

    ``reports`` is the on-mesh per-agent vector of collection rounds of
    the newest dataset each agent has trained on (see
    ``fault.freshness_gate``); the lag is ``current_round - reports``.
    Safe inside the fused round program *outside* the ``shard_map`` body
    (a cross-shard reduction, like the CE means) — the results ride the
    existing once-per-round record fetch, adding zero host syncs.
    """
    import jax.numpy as jnp
    lag = jnp.asarray(current_round, jnp.int32) - \
        jnp.asarray(reports, jnp.int32)
    return {"staleness_min": lag.min(), "staleness_mean":
            lag.astype(jnp.float32).mean(), "staleness_max": lag.max()}


def kernel_summary(policy_cfg, aip_cfg, ppo_cfg) -> str:
    """Resolved kernel-dispatch decisions as a compact string, e.g.
    ``"policy=pallas,aip=oracle,ppo=pallas-interpret"``."""
    from repro.kernels import dispatch

    def word(cfg):
        d = dispatch.resolve(cfg.use_kernels)
        if not d.use:
            return "oracle"
        return "pallas-interpret" if d.interpret else "pallas"

    return ",".join(f"{n}={word(c)}" for n, c in
                    (("policy", policy_cfg), ("aip", aip_cfg),
                     ("ppo", ppo_cfg)))


# ---------------------------------------------------------------------------
# benchmark-row schemas (gated by benchmarks/check_bench.py)
# ---------------------------------------------------------------------------
# column -> (allowed types, required, nullable)
_NUM = (int, float)

SCALING_ROW_SCHEMA = {
    "name": "scaling",
    "columns": {
        "label": (str, True, False),
        "scenario": (str, True, False),
        "n_agents": (int, True, False),
        "shards": (int, True, False),
        "processes": (int, True, False),
        "streams": (int, True, False),
        "fused": (bool, True, False),
        "round_s": (_NUM, True, False),
        "round_s_async": (_NUM, True, False),
        "overlap_speedup": (_NUM, True, False),
        "inner_steps_per_s": (_NUM, True, False),
        "inner_steps_per_s_async": (_NUM, True, False),
        "total_wall_s": (_NUM, True, False),
        "total_wall_s_async": (_NUM, True, False),
        "collect_s": (_NUM, True, False),
        "env_steps_per_s": (_NUM, True, False),
        # null where the env topology cannot tile the shard count
        "collect_s_sharded_gs": (_NUM, True, True),
        "gs_speedup": (_NUM, True, True),
        # only present once the shards=1 baseline has run (P=1 cells)
        "speedup_vs_unfused": (_NUM, False, False),
        # jax.devices()[0].platform of the process that measured the row
        "platform": (str, False, False),
    },
    "phases": ("round_s", "round_s_async", "collect_s",
               "collect_s_sharded_gs"),
}

KERNELS_MICRO_SCHEMA = {
    "name": "kernels.micro",
    "columns": {
        "kernel": (str, True, False),
        "label": (str, True, False),
        "B": (int, True, False),
        "T": (int, True, False),
        # gru rows only; gae rows have no input/hidden width
        "in": (int, False, False),
        "H": (int, False, False),
        "fwd_oracle_s": (_NUM, True, False),
        "fwd_kernel_s": (_NUM, True, False),
        "fwdbwd_oracle_s": (_NUM, True, False),
        "fwdbwd_kernel_s": (_NUM, True, False),
        "speedup_fwd": (_NUM, True, False),
        "speedup_fwdbwd": (_NUM, True, False),
        "roofline_fwd": (dict, True, False),
        "roofline_fwdbwd": (dict, True, False),
    },
    "phases": ("fwd_oracle_s", "fwd_kernel_s", "fwdbwd_oracle_s",
               "fwdbwd_kernel_s"),
}

KERNELS_E2E_SCHEMA = {
    "name": "kernels.end_to_end",
    "columns": {
        "program": (str, True, False),
        "label": (str, True, False),
        "oracle_s": (_NUM, True, False),
        "kernel_s": (_NUM, True, False),
        "speedup": (_NUM, True, False),
    },
    "phases": ("oracle_s", "kernel_s"),
}


def validate_bench_row(row: Dict, schema: Dict) -> List[str]:
    """Problems with one benchmark row against a ``*_ROW_SCHEMA`` /
    ``KERNELS_*_SCHEMA``: unknown columns, missing required columns,
    non-null cells of the wrong type, nulls in non-nullable cells."""
    cols = schema["columns"]
    name = schema["name"]
    problems = []
    for k in sorted(set(row) - set(cols)):
        problems.append(f"[{name}] unknown column {k!r}")
    for k, (_, required, _n) in cols.items():
        if required and k not in row:
            problems.append(f"[{name}] missing column {k!r}")
    for k, value in row.items():
        if k not in cols:
            continue
        types, _required, nullable = cols[k]
        if value is None:
            if not nullable:
                problems.append(f"[{name}] column {k!r} is null")
            continue
        if types is bool or types is int:
            ok = isinstance(value, types) and (types is bool or
                                               not isinstance(value, bool))
        elif types is _NUM:
            ok = isinstance(value, _NUM) and not isinstance(value, bool)
        else:
            ok = isinstance(value, types)
        if not ok:
            tn = types.__name__ if isinstance(types, type) else "number"
            problems.append(f"[{name}] column {k!r}: expected {tn}, got "
                            f"{type(value).__name__} ({value!r})")
    return problems


def phase_breakdown(row: Dict, schema: Dict) -> str:
    """Compact ``col=value`` phase summary of a bench row, for
    regression messages ("which cell regressed, and where its time
    goes")."""
    parts = []
    for col in schema.get("phases", ()):
        v = row.get(col)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            parts.append(f"{col}={v:.6g}")
        else:
            parts.append(f"{col}={v}")
    return " ".join(parts)
