"""Reusable jaxpr visitor: one walker for every trace-level analysis.

`repro.dist.commstats` started this idiom (PR 2) with a private recursive
walk that tallied collectives and multiplied `scan` trip counts.  Every
jaxpr-level invariant check needs the same traversal — nested jaxprs in
eqn params (pjit / scan / while / shard_map / custom_* bodies), loop
multiplicity, and the execution context an equation sits in — so this
module extracts it as a visitor:

    closed = jax.make_jaxpr(plan.apply)(x_spec)
    def visit(eqn, ctx):
        if eqn.primitive.name == "ppermute":
            ...ctx.mult, ctx.in_while, ctx.axis_sizes...
    walk_jaxpr(closed, visit)

:class:`EqnContext` carries what the traversal knows at each equation:

  * ``mult`` — static trip multiplier: an eqn inside a ``scan`` of length
    L executes L times per application (nested scans multiply);
  * ``in_while`` — whether any enclosing jaxpr is a ``while`` body/cond,
    whose trip count is *unknown at trace time* (checks that need exact
    counts must treat anything here as uncountable — see
    `commstats.measure`, which now refuses to undercount collectives
    found there);
  * ``axis_sizes`` — mesh axis name -> size, collected from enclosing
    ``shard_map`` equations (what the ppermute-bijection check needs to
    decide whether a permutation covers the whole axis);
  * ``path`` — the enclosing primitive names, outermost first (for
    diagnostics).

`commstats.measure` is rebased on this walker; the invariant checks in
:mod:`repro.analysis.checks` are its other consumers.  Keep the walker
purely structural — rule logic lives with the rules.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, List, Mapping, Tuple

from jax.extend.core import ClosedJaxpr, Jaxpr

#: Collective primitives the communication analyses care about (moved here
#: from `dist.commstats`, which re-exports it for compatibility).
COLLECTIVE_PRIMITIVES = frozenset({
    "ppermute",
    "pgather",
    "all_gather",
    "all_to_all",
    "psum",
    "reduce_scatter",
})


@dataclasses.dataclass(frozen=True)
class EqnContext:
    """Traversal context for one visited equation (see module docstring)."""

    mult: int = 1
    in_while: bool = False
    path: Tuple[str, ...] = ()
    axis_sizes: Mapping[str, int] = dataclasses.field(default_factory=dict)

    def axis_size(self, axis_name) -> int:
        """Product size of a ppermute/all_gather ``axis_name`` param (a
        name or tuple of names); 0 when any axis is unknown here."""
        names = axis_name if isinstance(axis_name, (tuple, list)) \
            else (axis_name,)
        size = 1
        for a in names:
            if a not in self.axis_sizes:
                return 0
            size *= int(self.axis_sizes[a])
        return size


def subjaxprs(value: Any) -> Iterable[Any]:
    """Yield every Jaxpr reachable from one eqn param value."""
    if isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from subjaxprs(v)


def _child_context(eqn, ctx: EqnContext) -> EqnContext:
    name = eqn.primitive.name
    mult = ctx.mult
    if name == "scan":
        mult *= int(eqn.params.get("length", 1))
    axis_sizes = ctx.axis_sizes
    if name == "shard_map":
        shape = getattr(eqn.params.get("mesh"), "shape", None)
        if shape:
            axis_sizes = {**dict(axis_sizes), **dict(shape)}
    return EqnContext(
        mult=mult,
        in_while=ctx.in_while or name == "while",
        path=ctx.path + (name,),
        axis_sizes=axis_sizes,
    )


def walk_jaxpr(jaxpr, visit: Callable[[Any, EqnContext], None],
               ctx: EqnContext = None) -> None:
    """Depth-first walk calling ``visit(eqn, ctx)`` on every equation.

    `jaxpr` may be a `Jaxpr` or `ClosedJaxpr`.  Equations are visited in
    trace order at each nesting level, parents before their sub-jaxpr
    bodies — so a flat list of visited collectives *is* the static
    collective schedule (what the batch-invariance check compares).
    """
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    if ctx is None:
        ctx = EqnContext()
    for eqn in jaxpr.eqns:
        visit(eqn, ctx)
        sub_ctx = _child_context(eqn, ctx)
        for value in eqn.params.values():
            for sub in subjaxprs(value):
                walk_jaxpr(sub, visit, sub_ctx)


def collect_eqns(jaxpr, primitives=None) -> List[Tuple[Any, EqnContext]]:
    """All (eqn, ctx) pairs, optionally filtered to a primitive-name set."""
    out: List[Tuple[Any, EqnContext]] = []

    def visit(eqn, ctx):
        if primitives is None or eqn.primitive.name in primitives:
            out.append((eqn, ctx))

    walk_jaxpr(jaxpr, visit)
    return out


def eqn_payload(eqn) -> Tuple[int, int]:
    """(elems, bytes) moved by one execution of a collective eqn."""
    import numpy as np

    elems = 0
    nbytes = 0
    for var in eqn.invars:
        aval = getattr(var, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = int(np.prod(shape)) if len(shape) else 1
        elems += n
        nbytes += n * np.dtype(dtype).itemsize
    return elems, nbytes


def source_location(eqn) -> Tuple[str, int]:
    """(file, line) of the user code that traced `eqn`, best effort.

    Uses jax's source-info tracking (private API, so failures degrade to
    ``("", 0)`` rather than breaking a check)."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            return str(frame.file_name), int(frame.start_line)
    except Exception:
        pass
    return "", 0
