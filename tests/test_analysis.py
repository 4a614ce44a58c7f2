"""Tests for the static program-contract analyzer (repro.analysis).

Covers the walker's path/source provenance on nested programs
(scan-in-shard_map-in-pjit, pallas_call kernel bodies), pass/fail
fixtures for every contract rule, the lint rules, the live-primitive
table validation, and — slow — driver parity: the full rule set is
clean over both drivers' traced programs for a real scenario.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import contracts, lint, recompile, walker
from repro.analysis.report import Finding, format_finding
from repro.distributed import runtime


# ---------------------------------------------------------------------------
# walker: paths + provenance
# ---------------------------------------------------------------------------
def _nested_jaxpr():
    """scan inside shard_map inside pjit, with a psum in the scan body."""
    mesh = runtime.shard_mesh(1)

    def shard_body(x):
        def step(carry, v):
            carry = carry + jax.lax.psum(v, runtime.SHARD_AXIS)
            return carry, carry
        out, _ = jax.lax.scan(step, jnp.zeros(()), x)
        return x + out

    fn = jax.jit(runtime.shard_map_nocheck(
        shard_body, mesh, in_specs=(P(runtime.SHARD_AXIS),),
        out_specs=P(runtime.SHARD_AXIS)))
    return jax.make_jaxpr(fn)(jnp.ones((4,)))


def test_walker_nested_path_and_source_info():
    jaxpr = _nested_jaxpr()
    sites = walker.sites(jaxpr, ("psum",))
    assert len(sites) == 1
    s = sites[0]
    # the path names every enclosing structured primitive, outermost
    # first: pjit body -> shard_map body -> scan body
    assert any(c.startswith("pjit") for c in s.path)
    assert "shard_map" in s.path
    assert "scan" in s.path
    assert s.path.index("shard_map") < s.path.index("scan")
    # provenance points at the user line that emitted the psum
    assert s.file and s.file.endswith("test_analysis.py")
    assert s.line and s.line > 0
    assert s.fn == "step"
    assert "psum" in s.describe() and "scan" in s.describe()


def test_walker_primitives_recurse_everywhere():
    jaxpr = _nested_jaxpr()
    prims = walker.primitives(jaxpr)
    assert {"psum", "scan", "shard_map", "add"} <= prims
    # the runtime compatibility shim routes through the walker
    assert runtime.jaxpr_primitives(jaxpr) == prims


def test_walker_sees_pallas_kernel_body():
    """Regression for the pallas_call blindness: the old generic param
    scan missed kernel bodies (raw Jaxpr under the ``jaxpr`` param);
    the walker must descend into them with a ``pallas_call`` path
    component."""
    from repro.kernels.gae import kernel as k_mod
    t, b = 4, 2
    arr = jnp.ones((t, b), jnp.float32)
    fn = lambda r, v, nv, d: k_mod.gae_reverse_scan(
        r, v, nv, d, gamma=0.9, lam=0.9, interpret=True)
    jaxpr = jax.make_jaxpr(fn)(arr, arr, arr, arr)
    assert "pallas_call" in walker.primitives(jaxpr)
    inside = [s for s in walker.walk(walker.raw_jaxpr(jaxpr))
              if any("pallas_call" in c for c in s.path)]
    assert inside, "walker did not descend into the pallas kernel body"
    assert {"mul", "add"} <= {s.prim for s in inside}


def test_walker_sees_gru_kernel_under_custom_vmap():
    """The GRU launch sits in a ``custom_vmap``: unbatched, the kernel
    lies in the ``custom_vmap_call``'s ``call`` body (a known body
    parameter); vmapped, the rule's launch replaces it. The vmapped
    program registered for the dtype contract launches the kernel and
    passes DtypeRoundTrip."""
    from repro.analysis import programs
    from repro.kernels.gru import ops as gru_ops
    d_in, h = 4, 8
    params = {"wi": jnp.ones((d_in, 3 * h)), "wh": jnp.ones((h, 3 * h)),
              "bi": jnp.ones((3 * h,)), "bh": jnp.ones((3 * h,))}
    jaxpr = jax.make_jaxpr(lambda p, x: gru_ops.gru_sequence(
        p, x, interpret=True))(params, jnp.ones((2, 5, d_in)))
    inside = [s for s in walker.walk(jaxpr)
              if "custom_vmap_call" in s.path
              and "pallas_call:gru_fwd" in s.path]
    assert inside, "walker did not descend into the custom_vmap body"
    vmap_eqns = [e for e in _eqns(jaxpr.jaxpr)
                 if e.primitive.name == "custom_vmap_call"]
    assert vmap_eqns and all(
        [label for label, _ in walker.sub_jaxprs(e)] == ["call"]
        for e in vmap_eqns)

    prog, = [p for p in programs.kernel_dtype_programs()
             if p.name == "kernels/gru/pallas-vmapped"]
    paths = {c for s in walker.walk(jax.make_jaxpr(prog.fn)(*prog.args))
             for c in s.path}
    assert "pallas_call:gru_fwd" in paths
    assert contracts.DtypeRoundTrip().check(prog) == []


def _eqns(jaxpr):
    for eqn in walker.raw_jaxpr(jaxpr).eqns:
        yield eqn
        for _, sub in walker.sub_jaxprs(eqn):
            yield from _eqns(sub)


def test_walker_fingerprint_detects_structural_change():
    mesh = runtime.shard_mesh(1)

    def body(x):
        return x * 2.0

    def body2(x):
        return x * 2.0 + jax.lax.psum(x, runtime.SHARD_AXIS)

    mk = lambda f: jax.make_jaxpr(runtime.shard_map_nocheck(
        f, mesh, in_specs=(P(runtime.SHARD_AXIS),),
        out_specs=P(runtime.SHARD_AXIS)))(jnp.ones((4,)))
    assert walker.fingerprint(mk(body)) == walker.fingerprint(mk(body))
    assert walker.fingerprint(mk(body)) != walker.fingerprint(mk(body2))


def test_find_shard_map_jaxprs_still_extracts_bodies():
    jaxpr = _nested_jaxpr()
    bodies = runtime.find_shard_map_jaxprs(jaxpr)
    assert len(bodies) == 1
    assert "psum" in walker.primitives(bodies[0])


# ---------------------------------------------------------------------------
# primitive tables vs the running jax
# ---------------------------------------------------------------------------
def test_collective_tables_cover_live_jax():
    live = runtime.live_collective_prims()
    assert "psum" in live and "ppermute" in live
    assert "axis_index" not in live
    runtime.validate_collective_tables()       # must not raise
    assert runtime.HALO_PRIMS < runtime.COLLECTIVE_PRIMS


# ---------------------------------------------------------------------------
# contract rules: pass/fail fixtures
# ---------------------------------------------------------------------------
def _shard_jaxpr(f, shape=(4,)):
    mesh = runtime.shard_mesh(1)
    return jax.make_jaxpr(runtime.shard_map_nocheck(
        f, mesh, in_specs=(P(runtime.SHARD_AXIS),),
        out_specs=P(runtime.SHARD_AXIS)))(jnp.ones(shape))


def _body(f, shape=(4,)):
    return runtime.find_shard_map_jaxprs(_shard_jaxpr(f, shape))[0]


def test_collective_free_rule():
    rule = contracts.CollectiveFree()
    clean = contracts.Program(name="fix/clean", roles=("train_body",),
                              jaxpr=_body(lambda x: x * 2.0))
    assert rule.check(clean) == []
    dirty = contracts.Program(
        name="fix/psum", roles=("train_body",),
        jaxpr=_body(lambda x: x + jax.lax.psum(x, runtime.SHARD_AXIS)))
    found = rule.check(dirty)
    assert len(found) == 1
    f = found[0]
    assert "psum" in f.message and f.file.endswith("test_analysis.py")
    assert f.line and f.rule == "CollectiveFree"


def test_halo_only_rule():
    rule = contracts.HaloOnly()
    halo = contracts.Program(
        name="fix/halo", roles=("gs_body",),
        jaxpr=_body(lambda x: jax.lax.ppermute(
            x, runtime.SHARD_AXIS, [(0, 0)])))
    assert rule.check(halo) == []
    psum = contracts.Program(
        name="fix/psum", roles=("gs_body",),
        jaxpr=_body(lambda x: x + jax.lax.psum(x, runtime.SHARD_AXIS)))
    found = rule.check(psum)
    assert any("non-halo" in f.message and f.line for f in found)
    silent = contracts.Program(name="fix/none", roles=("gs_body",),
                               jaxpr=_body(lambda x: x * 2.0))
    found = rule.check(silent)
    assert len(found) == 1 and "no halo exchange" in found[0].message


def test_no_host_callback_rule():
    rule = contracts.NoHostCallback()
    clean = contracts.Program(
        name="fix/clean", roles=("round",),
        jaxpr=jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((3,))))
    assert rule.check(clean) == []

    def leaky(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct((3,), jnp.float32), x)

    dirty = contracts.Program(name="fix/callback", roles=("round",),
                              jaxpr=jax.make_jaxpr(leaky)(jnp.ones((3,))))
    found = rule.check(dirty)
    assert len(found) == 1 and "host callback" in found[0].message


def test_donation_used_rule():
    rule = contracts.DonationUsed()
    aval = jax.ShapeDtypeStruct((8,), jnp.float32)

    def used(carry, x):
        return carry + x

    ok = contracts.Program(name="fix/used", roles=("donated",),
                           fn=used, args=(aval, aval),
                           donate_argnums=(0,))
    assert rule.check(ok) == []

    def unused(carry, x):
        return x * 2.0

    bad = contracts.Program(name="fix/unused", roles=("donated",),
                            fn=unused, args=(aval, aval),
                            donate_argnums=(0,))
    found = rule.check(bad)
    assert len(found) == 1
    assert "0 of 1 donated buffers" in found[0].message


def test_dtype_round_trip_rule():
    rule = contracts.DtypeRoundTrip()
    aval = jax.ShapeDtypeStruct((4,), jnp.bfloat16)
    ok = contracts.Program(name="fix/ok", roles=("dtype",),
                           fn=lambda x: x * 2, args=(aval,))
    assert rule.check(ok) == []
    upcast = contracts.Program(
        name="fix/upcast", roles=("dtype",),
        fn=lambda x: x.astype(jnp.float32) * 2, args=(aval,))
    found = rule.check(upcast)
    assert len(found) == 1 and "silent upcast" in found[0].message

    def crashes(x):
        def step(c, v):
            return c + v.astype(jnp.float32), c
        return jax.lax.scan(step, jnp.zeros((), x.dtype), x)

    broken = contracts.Program(name="fix/trace-crash", roles=("dtype",),
                               fn=crashes, args=(aval,))
    found = rule.check(broken)
    assert len(found) == 1
    assert "does not trace at reduced precision" in found[0].message


def test_scalar_sync_budget_rule():
    from repro.obs import metrics
    rule = contracts.ScalarSyncBudget()
    scalar = jnp.zeros(())
    good = contracts.Program(
        name="fix/good", roles=("round",),
        fn=lambda c: (c, {"gs_return": scalar, "ials_reward": scalar}),
        args=(jnp.ones((3,)),))
    assert rule.check(good) == []
    off_schema = contracts.Program(
        name="fix/extra-key", roles=("round",),
        fn=lambda c: (c, {"gs_return": scalar, "surprise": scalar}),
        args=(jnp.ones((3,)),))
    found = rule.check(off_schema)
    assert any("outside the typed round schema" in f.message
               for f in found)
    fat = contracts.Program(
        name="fix/vector", roles=("round",),
        fn=lambda c: (c, {"gs_return": jnp.ones((7,))}),
        args=(jnp.ones((3,)),))
    found = rule.check(fat)
    assert any("scalars only" in f.message for f in found)
    assert metrics.ROUND_KEYS  # schema itself must stay non-empty


def test_run_rules_routes_by_role():
    jaxpr = _body(lambda x: x + jax.lax.psum(x, runtime.SHARD_AXIS))
    # as a train body the psum is a violation; untagged it is ignored
    hit = contracts.run_rules(
        [contracts.Program(name="p", roles=("train_body",), jaxpr=jaxpr)])
    assert hit
    miss = contracts.run_rules(
        [contracts.Program(name="p", roles=("other",), jaxpr=jaxpr)])
    assert miss == []
    with pytest.raises(AssertionError) as e:
        contracts.raise_findings(hit)
    assert "CONTRACT-VIOLATION" in str(e.value)


# ---------------------------------------------------------------------------
# refactored runtime audits keep their contract AND gain provenance
# ---------------------------------------------------------------------------
def test_assert_no_collectives_names_the_line():
    jaxpr = _nested_jaxpr()
    with pytest.raises(AssertionError) as e:
        runtime.assert_no_collectives(jaxpr, what="fixture")
    msg = str(e.value)
    assert "must be collective-free between AIP refreshes" in msg
    assert "psum" in msg and "test_analysis.py" in msg


def test_assert_only_halo_collectives_messages():
    bad = _body(lambda x: x + jax.lax.psum(x, runtime.SHARD_AXIS))
    with pytest.raises(AssertionError,
                       match="only halo-exchange collectives"):
        runtime.assert_only_halo_collectives(bad, what="fixture")
    none = _body(lambda x: x * 2.0)
    with pytest.raises(AssertionError,
                       match="no halo exchange at all"):
        runtime.assert_only_halo_collectives(none, what="fixture")


# ---------------------------------------------------------------------------
# lint rules
# ---------------------------------------------------------------------------
def _lint(src, filename="src/repro/core/fixture.py"):
    return lint.lint_source("import jax\nimport jax.numpy as jnp\n" + src,
                            filename=filename)


def test_lint_prng_reuse():
    found = _lint("def f(key):\n"
                  "    a = jax.random.normal(key, (3,))\n"
                  "    b = jax.random.uniform(key, (3,))\n"
                  "    return a + b\n")
    assert any(f.rule == "prng-reuse" and f.line for f in found)
    clean = _lint("def f(key):\n"
                  "    k1, k2 = jax.random.split(key)\n"
                  "    return jax.random.normal(k1, (3,)) + "
                  "jax.random.uniform(k2, (3,))\n")
    assert clean == []


def test_lint_discarded_split_and_relative_fold():
    found = _lint("def f(key):\n"
                  "    k1, k2 = jax.random.split(key)\n"
                  "    return jax.random.normal(k1, (3,))\n")
    assert any(f.rule == "prng-discarded-split" for f in found)
    # underscore names opt out of the discarded-split rule
    clean = _lint("def f(key):\n"
                  "    k1, _k2 = jax.random.split(key)\n"
                  "    return jax.random.normal(k1, (3,))\n")
    assert clean == []
    found = _lint("def f(key):\n"
                  "    i = jax.lax.axis_index('shards')\n"
                  "    k = jax.random.fold_in(key, i * 4 + 2)\n"
                  "    return jax.random.normal(k, (3,))\n")
    assert any(f.rule == "prng-relative-fold" for f in found)


def test_lint_numpy_random_and_host_time():
    found = _lint("import numpy as np\n"
                  "def f(x):\n"
                  "    def inner(y):\n"
                  "        return y * np.random.rand()\n"
                  "    return inner(x)\n")
    assert any(f.rule == "numpy-random" for f in found)
    found = _lint("import time\n"
                  "def f(x):\n"
                  "    def inner(y):\n"
                  "        return y + time.time()\n"
                  "    return inner(x)\n")
    assert any(f.rule == "host-time" for f in found)


def test_lint_traced_branch_only_in_runtime_dirs():
    src = ("def f(x):\n"
           "    def inner(y):\n"
           "        if y:\n"
           "            return y\n"
           "        return -y\n"
           "    return inner(x)\n")
    hit = _lint(src, filename="src/repro/distributed/fixture.py")
    assert any(f.rule == "traced-branch" for f in hit)
    # host-side code opts out (lint_file flips this off outside
    # core/ and distributed/)
    miss = lint.lint_source("import jax\n" + src,
                            filename="src/repro/envs/fixture.py",
                            branch_rules=False)
    assert not any(f.rule == "traced-branch" for f in miss)


def test_lint_tree_is_clean():
    import os
    src_root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src", "repro")
    findings = lint.lint_paths(lint.default_targets(src_root))
    assert findings == [], "\n".join(
        format_finding(f, github=False) for f in findings)


# ---------------------------------------------------------------------------
# recompile + report plumbing
# ---------------------------------------------------------------------------
def test_check_steady_state():
    assert recompile.check_steady_state([17, 17, 17], what="d") == []
    found = recompile.check_steady_state([17, 19, 19], what="d")
    assert found and found[0].rule == "SteadyStateCompile"
    assert "d" in found[0].message


def test_format_finding_github_annotations():
    f = Finding(tag="CONTRACT-VIOLATION", rule="CollectiveFree",
                message="psum in body\nsecond line",
                file="src/repro/core/x.py", line=12)
    plain = format_finding(f, github=False)
    assert plain.startswith("CONTRACT-VIOLATION src/repro/core/x.py:12")
    gh = format_finding(f, github=True)
    assert gh.startswith("::error file=src/repro/core/x.py,line=12,"
                         "title=CollectiveFree::")
    assert "\n" not in gh


# ---------------------------------------------------------------------------
# driver parity: the full rule set is clean over BOTH drivers' programs
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_driver_parity_contracts_clean_on_traffic():
    from repro.analysis import programs
    progs = programs.scenario_programs("traffic")
    names = {p.name for p in progs}
    assert any(n.startswith("loop/traffic/") for n in names)
    assert any("/round" in n and n.startswith("sharded/traffic@")
               for n in names)
    # every structural role the checker relies on is represented
    roles = {r for p in progs for r in p.roles}
    assert {"collect", "program", "round", "train_round", "donated",
            "train_body", "gs_body"} <= roles
    findings = contracts.run_rules(progs)
    assert findings == [], "\n".join(
        format_finding(f, github=False) for f in findings)


@pytest.mark.slow
def test_kernel_dtype_contracts_clean():
    """Regression for the two dtype-drift bugs the analyzer flagged:
    the GAE oracle used to crash tracing under bf16 (carry dtype
    desync) and the GAE kernel path silently returned f32."""
    from repro.analysis import programs
    findings = contracts.run_rules(programs.kernel_dtype_programs())
    assert findings == [], "\n".join(
        format_finding(f, github=False) for f in findings)


def test_reshard_collectives_token_classifier():
    find = contracts.ReshardCollectives._collectives_in_text
    hlo = ("%ag = f32[8,3] all-gather-start(f32[2,3] %p), dims={0}\n"
           "%cp = f32[2,3] collective-permute(f32[2,3] %x)")
    assert find(hlo) == ["all-gather", "collective-permute"]
    assert find("%r = f32[] all-reduce(f32[] %x)") == ["all-reduce"]
    # token boundaries: no spurious match inside identifiers
    assert find("my-all-reduce-like-name %all-gatherer") == []
    assert find("no collectives here") == []
    assert "ReshardCollectives" in {r.name for r in contracts.DEFAULT_RULES}


@pytest.mark.slow
def test_recovery_resume_programs_clean():
    """The PR-8 standing rule applied to the resume path: the restore /
    re-shard programs registered by ``recovery_programs`` must stay free
    of banned collectives (all-reduce, all-to-all, ...) — re-sharding a
    checkpoint onto a shrunken mesh is data movement (all-gather /
    collective-permute at most), never a reduction."""
    from repro.analysis import programs
    progs = programs.recovery_programs("traffic")
    names = {p.name for p in progs}
    assert any(n.endswith("/resume_round") for n in names)
    assert {"reshard_place", "reshard_fetch"} <= \
        {n.rsplit("/", 1)[-1] for n in names}
    roles = {r for p in progs for r in p.roles}
    assert "reshard" in roles and "round" in roles
    # and they ride along in the default registry next to the drivers
    all_names = {p.name for p in programs.all_programs(["traffic"])}
    assert any(n.startswith("recovery/traffic@") for n in all_names)
    findings = contracts.run_rules(progs)
    assert findings == [], "\n".join(
        format_finding(f, github=False) for f in findings)
