"""Sharded runtime substrate for the DIALS outer loop.

Three things the agent-sharded Algorithm-1 program needs, factored out so
tests and benchmarks can use them independently of the runner:

* **mesh construction** — :func:`shard_mesh` builds the 1-D ``("shards",)``
  device mesh; :func:`choose_shards` picks the largest shard count that
  divides the agent count (the agent axis must tile exactly — DIALS has no
  notion of a fractional region).
* **agent-axis placement** — :func:`agent_sharding` /
  :func:`shard_agent_tree`: every leaf of the IALS/AIP state has leading
  axis N, so one ``PartitionSpec("shards")`` shards the whole state.
* **jaxpr auditing** — :func:`jaxpr_primitives` /
  :func:`collectives_in_jaxpr` / :func:`assert_no_collectives`: the
  paper's runtime-stays-constant claim rests on the inner program having
  ZERO cross-shard communication between AIP refreshes.  Rather than
  trusting the partitioner, we walk the jaxpr of the per-shard body
  (including every nested scan/cond/pjit sub-jaxpr) and assert that no
  collective primitive appears — the claim as an executable check.
"""
from __future__ import annotations

from typing import Iterable, Optional, Set

import jax
import jax.extend
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shards"

# Cross-device communication primitives (jax.lax collectives as they appear
# in jaxprs). ``axis_index`` is deliberately absent: it reads the shard id
# without communicating.
COLLECTIVE_PRIMS: frozenset = frozenset({
    "psum", "psum2", "pmin", "pmax", "pmean", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "collective_permute", "pgather", "pdot",
})

# Neighbour-only communication — what a region-decomposed GS body is
# allowed (repro.core.gs_sharded exchanges halos with ring ppermutes).
# Deliberately NOT psum_scatter/reduce_scatter: those are full
# cross-shard reductions, i.e. exactly the quiet re-centralization this
# whitelist exists to reject. Anything outside this set in a GS body
# means the "decomposed" rollout re-centralized.
HALO_PRIMS: frozenset = frozenset({
    "ppermute", "collective_permute",
})


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------
def choose_shards(n_agents: int, n_devices: Optional[int] = None) -> int:
    """Largest divisor of ``n_agents`` that is ≤ the device count."""
    if n_devices is None:
        n_devices = len(jax.devices())
    for s in range(min(n_agents, n_devices), 0, -1):
        if n_agents % s == 0:
            return s
    return 1


def shard_mesh(n_shards: Optional[int] = None, *,
               devices: Optional[Iterable] = None) -> Mesh:
    """1-D ``("shards",)`` mesh over ``n_shards`` devices.

    Single process: the first ``n_shards`` of ``jax.devices()``, as
    before. Multi-process (``jax.distributed`` initialized): the mesh
    takes ``n_shards / process_count`` devices from EVERY process, in
    process order — each host owns a contiguous block of shards, which
    is both the layout the elastic reassignment reasons about
    (:func:`shards_on_hosts`) and the one that keeps every process
    addressable in every program (a process with no devices in a
    sharding cannot even call the jit that uses it)."""
    if devices is not None:
        devices = list(devices)
        if n_shards is None:
            n_shards = len(devices)
        if n_shards > len(devices):
            raise ValueError(
                f"asked for {n_shards} shards but only "
                f"{len(devices)} devices")
        return Mesh(np.array(devices[:n_shards]), (SHARD_AXIS,))

    all_devices = jax.devices()
    nproc = jax.process_count()
    if n_shards is None:
        n_shards = len(all_devices)
    if n_shards > len(all_devices):
        raise ValueError(
            f"asked for {n_shards} shards but only "
            f"{len(all_devices)} devices")
    if nproc <= 1:
        return Mesh(np.array(all_devices[:n_shards]), (SHARD_AXIS,))
    if n_shards % nproc:
        raise ValueError(
            f"{n_shards} shards cannot be balanced over {nproc} "
            f"processes (must divide evenly)")
    per = n_shards // nproc
    by_proc: dict = {}
    for d in all_devices:
        by_proc.setdefault(d.process_index, []).append(d)
    if any(len(ds) < per for ds in by_proc.values()):
        raise ValueError(
            f"{n_shards} shards need {per} devices per process; some "
            f"process has fewer")
    chosen = [d for pid in sorted(by_proc) for d in by_proc[pid][:per]]
    return Mesh(np.array(chosen), (SHARD_AXIS,))


def mesh_hosts(mesh: Mesh) -> tuple:
    """Sorted process ids whose devices participate in ``mesh``."""
    return tuple(sorted({d.process_index for d in mesh.devices.flat}))


def mesh_spans_processes(mesh: Mesh) -> bool:
    return len(mesh_hosts(mesh)) > 1


def shards_on_hosts(mesh: Mesh, hosts) -> tuple:
    """Shard indices (positions along the ``shards`` axis) whose device
    lives on one of ``hosts`` — the work units orphaned when those hosts
    die."""
    hosts = set(hosts)
    return tuple(i for i, d in enumerate(mesh.devices.flat)
                 if d.process_index in hosts)


def surviving_devices(mesh: Mesh, dead_hosts) -> list:
    """``mesh``'s devices minus the dead hosts', in shard order."""
    dead = set(dead_hosts)
    return [d for d in mesh.devices.flat if d.process_index not in dead]


def spare_device(n_in_use: int):
    """First local device beyond the first ``n_in_use``, or None.

    The sharded runtime puts the ``("shards",)`` mesh on the first
    ``n_shards`` devices; when the machine has more, the overlapped GS
    collect (repro.distributed.async_collect) runs on the next one so it
    never contends with the shard-train program's devices.

    Multi-process: always None. The collect is a *global* program there
    — its arrays span processes and cannot be device_put onto one spare
    — so the async collector falls back to in-stream dispatch."""
    if jax.process_count() > 1:
        return None
    devices = jax.devices()
    return devices[n_in_use] if len(devices) > n_in_use else None


def shard_map_nocheck(f, mesh: Mesh, *, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled (the DIALS
    per-shard body produces sharded-only outputs)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Agent-axis placement
# ---------------------------------------------------------------------------
def agent_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis (agent) sharding over the shard mesh."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_agent_tree(tree, mesh: Mesh):
    """Place a pytree whose every leaf has leading agent axis N onto the
    mesh, N/num_shards agents per device.

    On a single-process mesh this is a plain ``device_put``. On a mesh
    spanning processes, ``device_put`` of a host array is not legal —
    instead each process materializes ONLY the slices its local devices
    own (``jax.make_array_from_callback``), which is also the point:
    per-host data plumbing ships a host its own agents' block, never the
    global state."""
    sh = agent_sharding(mesh)
    if not mesh_spans_processes(mesh):
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def place(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            # already a global array (e.g. a replicated-GS collect
            # output): reshard in-stream instead of round-tripping
            # through the host
            return jax.jit(lambda a: a, out_shardings=sh)(x)
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, sh,
                                            lambda idx: x[idx])
    return jax.tree.map(place, tree)


def fetch_tree(tree):
    """Bring a (possibly cross-process-sharded) pytree to host numpy.

    Single-process arrays are just ``device_get``. Arrays with
    non-addressable shards are first made fully replicated via a jit'd
    identity (an all-gather under the hood — every process ends up
    holding every agent's block), after which each process can read them
    locally. This is the mirror the elastic driver keeps so that
    surviving hosts can re-materialize a dead host's agents."""
    def fetch(x):
        if not hasattr(x, "sharding"):
            return np.asarray(x)
        if getattr(x, "is_fully_addressable", True):
            return np.asarray(jax.device_get(x))
        mesh = x.sharding.mesh
        rep = jax.jit(lambda a: a,
                      out_shardings=NamedSharding(mesh, P()))(x)
        return np.asarray(jax.device_get(rep))
    return jax.tree.map(fetch, tree)


def local_slice_struct(tree, n_shards: int):
    """ShapeDtypeStructs of one shard's slice of an agent-stacked tree —
    what the per-shard body of a ``shard_map`` actually sees."""
    def one(x):
        n = x.shape[0]
        if n % n_shards:
            raise ValueError(
                f"agent axis {n} not divisible by {n_shards} shards")
        return jax.ShapeDtypeStruct((n // n_shards,) + tuple(x.shape[1:]),
                                    x.dtype)
    return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# Jaxpr auditing — thin compatibility surface over repro.analysis.walker
# (the path-aware traversal with source provenance; pallas_call kernel
# bodies are walked explicitly there, which the old generic param scan
# left to luck)
# ---------------------------------------------------------------------------
def _sub_jaxprs(eqn):
    from repro.analysis import walker
    for _label, sub in walker.sub_jaxprs(eqn):
        yield sub


def jaxpr_primitives(jaxpr) -> Set[str]:
    """All primitive names in a (Closed)Jaxpr, recursing into every
    nested sub-jaxpr — scan/while/cond/pjit/custom_* AND ``pallas_call``
    kernel bodies (``repro.analysis.walker`` owns the traversal)."""
    from repro.analysis import walker
    return walker.primitives(jaxpr)


def collectives_in_jaxpr(jaxpr) -> Set[str]:
    return jaxpr_primitives(jaxpr) & COLLECTIVE_PRIMS


def find_shard_map_jaxprs(jaxpr):
    """The body jaxprs of every ``shard_map`` eqn in a traced program
    (recursing through nested sub-jaxprs). Auditing these — extracted
    from the REAL program rather than traced separately — is what ties
    the no-collectives assertion to the code that actually runs."""
    from repro.analysis import walker
    jaxpr = walker.raw_jaxpr(jaxpr)
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            body = eqn.params.get("jaxpr")
            if body is not None:
                found.append(body)
        for _label, sub in walker.sub_jaxprs(eqn):
            found.extend(find_shard_map_jaxprs(sub))
    return found


def _collective_sites(jaxpr):
    from repro.analysis import walker
    return walker.sites(jaxpr, COLLECTIVE_PRIMS)


def _describe_sites(sites) -> str:
    return "; ".join(s.describe() for s in sites)


def assert_no_collectives(jaxpr, *, what: str = "program") -> None:
    """Raise if any cross-shard collective appears anywhere in
    ``jaxpr`` — naming each occurrence's source line and jaxpr path."""
    sites = _collective_sites(jaxpr)
    if sites:
        raise AssertionError(
            f"{what} must be collective-free between AIP refreshes but "
            f"contains {sorted({s.prim for s in sites})}: "
            f"{_describe_sites(sites)}")


def assert_only_halo_collectives(jaxpr, *, what: str = "GS body") -> None:
    """Raise unless every collective in ``jaxpr`` is a halo exchange
    (``HALO_PRIMS``) and at least one is present — a region-decomposed
    GS body must talk to its ring neighbours and to nobody else."""
    sites = _collective_sites(jaxpr)
    extra = [s for s in sites if s.prim not in HALO_PRIMS]
    if extra:
        raise AssertionError(
            f"{what} may contain only halo-exchange collectives "
            f"{sorted(HALO_PRIMS)} but also has "
            f"{sorted({s.prim for s in extra})}: "
            f"{_describe_sites(extra)}")
    if not sites:
        raise AssertionError(
            f"{what} contains no halo exchange at all — it is not the "
            f"region-decomposed GS program")


def live_collective_prims() -> Set[str]:
    """Collective primitive names registered by the *running* jax (from
    ``jax.lax``'s parallel-operator module), minus ``axis_index`` (reads
    the shard id without communicating). The frozen tables above must
    cover these — :func:`validate_collective_tables`."""
    from jax._src.lax import parallel
    live = {
        p.name for p in vars(parallel).values()
        if isinstance(p, jax.extend.core.Primitive)
    }
    return live - {"axis_index"}


def validate_collective_tables() -> None:
    """Raise if the frozen ``COLLECTIVE_PRIMS``/``HALO_PRIMS`` tables
    rotted against the running jax: every live collective primitive must
    be classified (else an upgrade could add a collective the audits
    silently wave through), and the halo whitelist must stay a strict
    subset of the collective set."""
    live = live_collective_prims()
    missing = live - COLLECTIVE_PRIMS
    if missing:
        raise AssertionError(
            f"COLLECTIVE_PRIMS is missing live jax collective "
            f"primitives {sorted(missing)} — the no-collectives audit "
            f"would not see them; add them to the table")
    if not HALO_PRIMS <= COLLECTIVE_PRIMS:
        raise AssertionError(
            f"HALO_PRIMS {sorted(HALO_PRIMS - COLLECTIVE_PRIMS)} not in "
            f"COLLECTIVE_PRIMS — the halo whitelist must be a subset of "
            f"the collective set")
