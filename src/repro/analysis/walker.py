"""Path-aware jaxpr traversal with source provenance.

``repro.distributed.runtime.jaxpr_primitives`` flattens a whole traced
program to a *set of primitive names* — enough to say "a psum exists",
useless for saying *where*. This walker replaces that flattening with a
structured traversal: every primitive occurrence becomes a
:class:`PrimSite` carrying

* the **structural path** from the program root — which ``pjit`` /
  ``shard_map`` / ``scan`` / ``cond`` / ``while`` / ``custom_vjp`` /
  ``custom_vmap`` / ``pallas_call`` bodies enclose it (e.g.
  ``pjit:train_fn / shard_map / scan``);
* the **named-scope labels** active at trace time
  (``jax.named_scope`` — the ``shard_train`` / ``gs_collect`` /
  ``halo_exchange`` annotations ``repro.obs.trace.annotate`` stamps);
* the **source location** (file, line, function) of the user code that
  emitted the primitive, via the eqn's ``source_info``.

Contract violations reported off these records name the offending
primitive AND the line of repro code that traced it — see
``repro.analysis.contracts``.

Sub-jaxpr discovery is belt-and-braces: an explicit table for the
primitives whose body parameters we know (including ``pallas_call``,
whose kernel body is a *raw* ``Jaxpr`` parameter — exactly the shape a
ClosedJaxpr-only param scan misses), plus a generic scan over every
equation parameter for stray (Closed)Jaxpr values so a new jax
primitive cannot silently hide a body from the audit.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Sequence, Set, Tuple

import jax
import jax.extend

__all__ = [
    "PrimSite", "walk", "primitives", "sites", "fingerprint",
    "raw_jaxpr", "sub_jaxprs",
]


def raw_jaxpr(jaxpr):
    """The underlying ``Jaxpr`` of a (Closed)Jaxpr."""
    if isinstance(jaxpr, jax.extend.core.ClosedJaxpr):
        return jaxpr.jaxpr
    return jaxpr


# primitives whose params are known to carry sub-jaxprs; the walker
# labels these bodies by primitive name. Everything else goes through
# the generic param scan below.
_KNOWN_BODY_PARAMS = {
    "scan": ("jaxpr",),
    "while": ("cond_jaxpr", "body_jaxpr"),
    "cond": ("branches",),
    "pjit": ("jaxpr",),
    "shard_map": ("jaxpr",),
    "pallas_call": ("jaxpr",),
    "custom_jvp_call": ("call_jaxpr", "jvp_jaxpr_fun"),
    "custom_vjp_call": ("call_jaxpr", "fun_jaxpr"),
    "custom_vjp_call_jaxpr": ("fun_jaxpr",),
    "custom_vmap_call": ("call",),
    "checkpoint": ("jaxpr",),
    "remat2": ("jaxpr",),
}


def sub_jaxprs(eqn) -> Iterator:
    """Every sub-jaxpr an equation carries, as ``(label, jaxpr)``.

    ``pallas_call`` is listed in the known-body table explicitly: its
    kernel body is a raw ``Jaxpr`` param (not a ClosedJaxpr), which is
    how name-set flatteners historically missed Pallas kernel interiors.
    The generic fallback scans all remaining params for (Closed)Jaxpr
    values — list- or tuple-nested included — so nothing is silently
    skipped when jax grows new body-carrying primitives.
    """
    jaxpr_types = (jax.extend.core.ClosedJaxpr, jax.extend.core.Jaxpr)
    known = _KNOWN_BODY_PARAMS.get(eqn.primitive.name, ())
    emitted = set()

    def emit(name, val, index=None):
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for i, v in enumerate(vals):
            if isinstance(v, jaxpr_types) and id(v) not in emitted:
                emitted.add(id(v))
                label = name if len(vals) == 1 else f"{name}[{i}]"
                yield label, raw_jaxpr(v)

    for name in known:
        if name in eqn.params:
            yield from emit(name, eqn.params[name])
    for name, val in eqn.params.items():
        if name in known:
            continue
        yield from emit(name, val)


@dataclasses.dataclass(frozen=True)
class PrimSite:
    """One primitive occurrence inside a traced program."""
    prim: str
    path: Tuple[str, ...]          # enclosing bodies, outermost first
    scopes: Tuple[str, ...]        # jax.named_scope labels, outermost first
    file: Optional[str] = None     # user source that emitted the primitive
    line: Optional[int] = None
    fn: Optional[str] = None

    @property
    def location(self) -> str:
        """``file:line (fn)`` — empty string when provenance is absent
        (e.g. a synthetic jaxpr)."""
        if self.file is None:
            return ""
        loc = f"{self.file}:{self.line}"
        return f"{loc} ({self.fn})" if self.fn else loc

    def describe(self) -> str:
        """Human-oriented one-liner: primitive, path, scopes, source."""
        parts = [self.prim]
        if self.path:
            parts.append("in " + "/".join(self.path))
        if self.scopes:
            parts.append("under scope " + "/".join(self.scopes))
        loc = self.location
        if loc:
            parts.append(f"at {loc}")
        return " ".join(parts)


def _provenance(source_info):
    """(file, line, fn, scopes) off an eqn's source_info; every field
    degrades to None/() on jax builds whose internals moved."""
    scopes: Tuple[str, ...] = ()
    try:
        stack = str(source_info.name_stack)
        if stack:
            scopes = tuple(s for s in stack.split("/") if s)
    except Exception:
        pass
    try:
        from jax._src import source_info_util as siu
        frame = siu.user_frame(source_info)
        if frame is not None:
            return frame.file_name, frame.start_line, \
                frame.function_name, scopes
    except Exception:
        pass
    return None, None, None, scopes


def _path_component(eqn) -> str:
    """Display name of one enclosing body: the primitive, plus the
    program name where the primitive carries one (``pjit:round_fn``)."""
    name = eqn.params.get("name")
    if not isinstance(name, str):
        info = eqn.params.get("name_and_src_info")     # pallas_call
        name = getattr(info, "name", None)
    if isinstance(name, str) and name:
        return f"{eqn.primitive.name}:{name}"
    return eqn.primitive.name


def walk(jaxpr, *, path: Tuple[str, ...] = ()) -> Iterator[PrimSite]:
    """Yield a :class:`PrimSite` for every primitive in ``jaxpr``,
    recursing into every sub-jaxpr (scan/while/cond/pjit/shard_map/
    custom_vjp/pallas_call bodies included)."""
    jaxpr = raw_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        file, line, fn, scopes = _provenance(eqn.source_info)
        yield PrimSite(eqn.primitive.name, path, scopes, file, line, fn)
        component = _path_component(eqn)
        subs = list(sub_jaxprs(eqn))
        for label, sub in subs:
            comp = component if len(subs) == 1 else f"{component}:{label}"
            yield from walk(sub, path=path + (comp,))


def primitives(jaxpr) -> Set[str]:
    """Name-set flattening, as a walker view (the compatibility surface
    ``repro.distributed.runtime.jaxpr_primitives`` keeps serving)."""
    return {site.prim for site in walk(jaxpr)}


def sites(jaxpr, prims: Optional[Sequence[str]] = None) -> list:
    """All :class:`PrimSite` records, optionally filtered to a
    primitive-name set — the usual rule-engine entry point."""
    if prims is None:
        return list(walk(jaxpr))
    wanted = set(prims)
    return [s for s in walk(jaxpr) if s.prim in wanted]


def fingerprint(jaxpr) -> Tuple:
    """Order-insensitive structural fingerprint: the sorted multiset of
    ``(primitive, path)`` pairs. Two programs with equal fingerprints
    execute the same primitives in the same body structure — the
    invariant the telemetry-cannot-change-the-program rule pins, without
    the brittleness of string-equality on jaxpr pretty-printing."""
    counts: dict = {}
    for site in walk(jaxpr):
        key = (site.prim, site.path)
        counts[key] = counts.get(key, 0) + 1
    return tuple(sorted((p, path, n) for (p, path), n in counts.items()))
