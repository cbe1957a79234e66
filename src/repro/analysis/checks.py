"""Jaxpr-level invariant checks over traced plan methods (analysis Layer 1).

Three families, each guarding an invariant PRs 1–5 established but until
now only re-verified where a test author remembered to assert it:

* **Comm-schedule safety** (:func:`check_comm_schedule`) —
  ``JX-PPERMUTE-BIJECTION``: every ``ppermute`` permutation is a complete
  bijection on its mesh axis.  A partial or colliding permutation is a
  latent deadlock / silent-zero: on real interconnects every device must
  both send and receive exactly once per exchange, and jax zero-fills
  devices nobody sends to — either way the 2K|E| accounting breaks.
  ``JX-COLLECTIVE-IN-WHILE``: no collective may sit under ``while_loop``,
  whose trip count is unknown at trace time — the static schedule (and
  `commstats.measure`, which now raises on this) cannot count it.
* **Batch invariance** (:func:`collective_schedule` compared across batch
  sizes; ``JX-BATCH-SCHEDULE``) — the (..., N) contract promises B signals
  share the K exchange rounds.  Statically: the *ordered* collective
  schedule (primitive, axis, permutation, trip multiplier) traced at B=1
  must equal the one traced at B=64.  Payload shapes legitimately scale
  with B and are excluded.
* **Fault-injection honesty** (:func:`check_fault_schedule`;
  ``JX-FAULT-NO-EXTRA-COLLECTIVES``) — a fault-injected plan
  (``fault_spec=`` on the sharded backends, :mod:`repro.dist.faults`)
  must trace the *identical* ordered collective schedule as its clean
  twin: faults are receiver-side value substitutions after the
  ``ppermute``, never extra rounds, retries, or control flow around the
  collective — so `commstats` keeps measuring exactly the paper's 2K|E|
  messages under every injected configuration.
* **VMEM budget** (:func:`check_vmem_budget`; ``JX-VMEM-BUDGET``) — every
  ``pallas_call`` in the trace has its block + scratch footprint
  recomputed from its BlockSpecs and asserted under the PR-5 sweep budget
  (`repro.kernels.ops.DEFAULT_SWEEP_VMEM_BUDGET` unless overridden), so
  no future kernel ships an unguarded launch.
* **Dtype discipline** (:func:`check_dtype_discipline`) —
  ``JX-DTYPE-F64``: no f64 values appear on hot paths (an accidental
  ``astype(float64)`` doubles every halo payload and falls off the fast
  unit paths); ``JX-DTYPE-PROMOTION``: no op silently mixes real floating
  widths (e.g. a bf16 constant meeting f32 state promotes the whole
  recurrence).  Complex dtypes are exempt — the ARMA solver mixes
  complex64 poles with f32 signals by design.  ``JX-DTYPE-MIXED-OK``:
  the sanctioned-site carve-out for PROMOTION — :data:`DTYPE_MIXED_OK`
  names the source paths where mixing widths is intentional (the
  mixed-precision sweep kernels), with the justification recorded as
  rule metadata instead of `tools/lint_allowlist.txt` entries.

:func:`check_plan` bundles all of the above for one `ExecutionPlan`;
`tools/lint_repro.py` runs it across every registered backend.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .findings import Finding
from .jaxpr_walk import (COLLECTIVE_PRIMITIVES, EqnContext, collect_eqns,
                         source_location, walk_jaxpr)

#: Rule IDs of the jaxpr layer (catalogued in ARCHITECTURE.md).
JAXPR_RULES = (
    "JX-PPERMUTE-BIJECTION",
    "JX-COLLECTIVE-IN-WHILE",
    "JX-BATCH-SCHEDULE",
    "JX-VMEM-BUDGET",
    "JX-DTYPE-F64",
    "JX-DTYPE-PROMOTION",
    "JX-DTYPE-MIXED-OK",
    "JX-FAULT-NO-EXTRA-COLLECTIVES",
)

#: Sanctioned mixed-float-width sites (rule ``JX-DTYPE-MIXED-OK``): source
#: paths where ``JX-DTYPE-PROMOTION`` findings are suppressed because the
#: width mix is the *point* of the code, with the justification recorded
#: here instead of as opaque `tools/lint_allowlist.txt` entries.  Each
#: entry is ``(path fragment, why)``; a PROMOTION finding whose source
#: location contains the fragment is dropped (when ``mixed_ok=True``).
#: Keep this list tight — every fragment is a hole in the lint.
DTYPE_MIXED_OK = (
    ("repro/kernels/cheb_sweep.py",
     "mixed-precision sweep kernels: bf16 blocks/iterate scratch feed an "
     "f32 coefficient table and f32 accumulator (scratch_dtype='bf16', "
     "preferred_element_type=f32) — the pallas_call operands legitimately "
     "span two widths"),
)


def _finding(rule: str, eqn, label: str, message: str) -> Finding:
    path, line = source_location(eqn)
    return Finding(rule=rule, path=path or label, line=line, symbol=label,
                   message=message)


# ---------------------------------------------------------------------------
# Comm-schedule safety
# ---------------------------------------------------------------------------
def perm_problems(perm: Sequence[Tuple[int, int]],
                  axis_size: int) -> List[str]:
    """Why `perm` is not a complete bijection on a size-`axis_size` axis.

    Returns [] for a deadlock-free permutation: every device sends exactly
    once, receives exactly once, and all indices are on-axis.  This is the
    pure core of ``JX-PPERMUTE-BIJECTION`` — unit-testable without a mesh.
    """
    problems: List[str] = []
    pairs = [(int(s), int(d)) for s, d in perm]
    srcs = [s for s, _ in pairs]
    dsts = [d for _, d in pairs]
    off = [i for i in srcs + dsts if not 0 <= i < axis_size]
    if off:
        problems.append(f"indices {sorted(set(off))} outside axis of size "
                        f"{axis_size}")
    if len(set(srcs)) != len(srcs):
        dup = sorted({s for s in srcs if srcs.count(s) > 1})
        problems.append(f"devices {dup} send more than once")
    if len(set(dsts)) != len(dsts):
        dup = sorted({d for d in dsts if dsts.count(d) > 1})
        problems.append(f"devices {dup} receive more than once")
    missing_src = sorted(set(range(axis_size)) - set(srcs))
    missing_dst = sorted(set(range(axis_size)) - set(dsts))
    if missing_src:
        problems.append(f"devices {missing_src} never send")
    if missing_dst:
        problems.append(f"devices {missing_dst} never receive "
                        "(jax zero-fills them; a real interconnect "
                        "deadlocks)")
    return problems


def check_comm_schedule(fn: Callable, *example_args,
                        label: str = "fn") -> List[Finding]:
    """JX-PPERMUTE-BIJECTION + JX-COLLECTIVE-IN-WHILE over a traced `fn`."""
    closed = jax.make_jaxpr(fn)(*example_args)
    findings: List[Finding] = []
    for eqn, ctx in collect_eqns(closed, COLLECTIVE_PRIMITIVES):
        name = eqn.primitive.name
        if ctx.in_while:
            findings.append(_finding(
                "JX-COLLECTIVE-IN-WHILE", eqn, label,
                f"`{name}` under a while_loop (path {'/'.join(ctx.path)}): "
                "trip count is unknown at trace time, so the collective "
                "schedule cannot be statically verified or counted"))
        if name != "ppermute":
            continue
        perm = eqn.params.get("perm")
        axis = eqn.params.get("axis_name")
        size = ctx.axis_size(axis)
        if perm is None or not size:
            # unknown mesh axis (traced outside shard_map) — nothing to
            # verify statically; the 1-shard guards make this legitimate
            continue
        problems = perm_problems(perm, size)
        if problems:
            findings.append(_finding(
                "JX-PPERMUTE-BIJECTION", eqn, label,
                f"ppermute perm={list(perm)} on axis {axis!r} (size {size}) "
                f"is not a complete bijection: " + "; ".join(problems)))
    return findings


# ---------------------------------------------------------------------------
# Batch invariance (static collective schedule)
# ---------------------------------------------------------------------------
def collective_schedule(fn: Callable, *example_args) -> Tuple[Tuple, ...]:
    """The ordered static collective schedule of a traced `fn`.

    Each entry is (primitive, axis_name, perm, trip-multiplier) — the
    structure of the communication, with payload shapes deliberately
    excluded (they scale with batch size; the *schedule* must not).
    """
    closed = jax.make_jaxpr(fn)(*example_args)
    sched: List[Tuple] = []
    for eqn, ctx in collect_eqns(closed, COLLECTIVE_PRIMITIVES):
        perm = eqn.params.get("perm")
        sched.append((
            eqn.primitive.name,
            repr(eqn.params.get("axis_name")),
            tuple((int(s), int(d)) for s, d in perm) if perm else None,
            ctx.mult,
        ))
    return tuple(sched)


def check_batch_schedule(fn_for_batch: Callable[[int], Tuple[Callable, tuple]],
                         batches: Sequence[int] = (1, 64),
                         label: str = "fn") -> List[Finding]:
    """JX-BATCH-SCHEDULE: schedules at every batch size must be identical.

    `fn_for_batch(B)` returns ``(fn, example_args)`` for batch size B.
    """
    ref_b = batches[0]
    fn, args = fn_for_batch(ref_b)
    ref = collective_schedule(fn, *args)
    findings: List[Finding] = []
    for b in batches[1:]:
        fn, args = fn_for_batch(b)
        sched = collective_schedule(fn, *args)
        if sched != ref:
            findings.append(Finding(
                rule="JX-BATCH-SCHEDULE", path=label, symbol=label,
                message=(
                    f"collective schedule at B={b} differs from B={ref_b} "
                    f"({len(sched)} vs {len(ref)} entries): the batched "
                    "path re-runs or re-orders the exchange rounds instead "
                    "of sharing them across the batch")))
    return findings


# ---------------------------------------------------------------------------
# Fault-injection honesty
# ---------------------------------------------------------------------------
def check_fault_schedule(clean_plan, faulted_plan,
                         n: Optional[int] = None,
                         solve_methods: Sequence[str] = ()) -> List[Finding]:
    """JX-FAULT-NO-EXTRA-COLLECTIVES: faulted == clean collective schedule.

    Traces apply / apply_adjoint / apply_gram (plus ``plan.solve`` for
    each of `solve_methods`) on both plans and requires the ordered
    static collective schedules (:func:`collective_schedule` — primitive,
    axis, permutation, trip multiplier; payload shapes excluded) to be
    identical.  Any difference means the fault injection touched the
    exchange *structure* instead of only the received values, which
    breaks the 2K|E| accounting contract of `repro.dist.faults`.
    """
    op = clean_plan.op
    if n is None:
        if callable(op.P):
            raise ValueError("check_fault_schedule needs n= for a closure P")
        n = int(np.asarray(op.P).shape[0])
    fkey = faulted_plan.info.get("fault_key", "none")
    findings: List[Finding] = []

    def spec(*shape) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, np.float32)

    targets: List[Tuple[str, Callable, Callable, tuple]] = [
        ("apply", clean_plan.apply, faulted_plan.apply, (spec(n),)),
        ("apply_adjoint", clean_plan.apply_adjoint,
         faulted_plan.apply_adjoint, (spec(op.eta, n),)),
        ("apply_gram", clean_plan.apply_gram, faulted_plan.apply_gram,
         (spec(n),)),
    ]
    for method in solve_methods:
        def _solve(plan, _m=method):
            return lambda y: plan.solve(y, _m, tau=0.5).x

        targets.append((f"solve[{method}]", _solve(clean_plan),
                        _solve(faulted_plan), (spec(n),)))

    for name, clean_fn, faulted_fn, args in targets:
        label = f"{faulted_plan.backend}.{name}"
        ref = collective_schedule(clean_fn, *args)
        sched = collective_schedule(faulted_fn, *args)
        if sched != ref:
            findings.append(Finding(
                rule="JX-FAULT-NO-EXTRA-COLLECTIVES", path=label,
                symbol=label,
                message=(
                    f"fault-injected plan ({fkey}) traces a different "
                    f"collective schedule than the clean plan "
                    f"({len(sched)} vs {len(ref)} entries): faults must "
                    "be receiver-side value substitutions after the "
                    "ppermute, never extra rounds or reordered exchanges "
                    "— the 2K|E| accounting depends on it")))
    return findings


# ---------------------------------------------------------------------------
# VMEM budget
# ---------------------------------------------------------------------------
def _vmem_aval_bytes(aval) -> int:
    """Tiled VMEM bytes of one kernel-side ref aval; 0 for refs outside
    VMEM (SMEM tables, operands left in HBM, DMA semaphores)."""
    from ..kernels.layout import tile_bytes

    inner = getattr(aval, "inner_aval", aval)
    shape = getattr(inner, "shape", None)
    dtype = getattr(inner, "dtype", None)
    if shape is None or dtype is None:
        return 0
    space = str(getattr(aval, "memory_space", "")).lower()
    if any(k in space for k in ("smem", "any", "hbm", "semaphore")):
        return 0
    return tile_bytes(shape, dtype)


def pallas_footprint(eqn) -> Dict[str, int]:
    """Recomputed VMEM footprint of one ``pallas_call`` equation.

    Sums the per-grid-step block bytes of every VMEM operand/output
    BlockSpec plus all VMEM scratch, under the TPU (8, 128) tiling
    (`kernels.layout.tile_bytes`) — the same model as
    `repro.kernels.ops.cheb_sweep_vmem_bytes`, but recovered from the
    *traced* GridMapping rather than the launch parameters, so it audits
    what was actually staged.  SMEM operands (scalar tables, indices) do
    not count.
    """
    gm = eqn.params["grid_mapping"]
    block = sum(_vmem_aval_bytes(bm.block_aval) for bm in gm.block_mappings)
    scratch = 0
    kernel_jaxpr = eqn.params.get("jaxpr")
    n_scratch = int(getattr(gm, "num_scratch_operands", 0) or 0)
    if kernel_jaxpr is not None and n_scratch:
        for var in kernel_jaxpr.invars[-n_scratch:]:
            scratch += _vmem_aval_bytes(var.aval)
    return {"block_bytes": block, "scratch_bytes": scratch,
            "total_bytes": block + scratch}


def check_vmem_budget(fn: Callable, *example_args,
                      budget: Optional[int] = None,
                      label: str = "fn") -> List[Finding]:
    """JX-VMEM-BUDGET: every traced pallas_call fits the sweep budget."""
    if budget is None:
        from ..kernels import ops as _ops
        budget = _ops.DEFAULT_SWEEP_VMEM_BUDGET
    closed = jax.make_jaxpr(fn)(*example_args)
    findings: List[Finding] = []
    for eqn, _ctx in collect_eqns(closed, {"pallas_call"}):
        fp = pallas_footprint(eqn)
        if fp["total_bytes"] > budget:
            findings.append(_finding(
                "JX-VMEM-BUDGET", eqn, label,
                f"pallas_call footprint {fp['total_bytes']} B "
                f"(blocks {fp['block_bytes']} + scratch "
                f"{fp['scratch_bytes']}) exceeds the sweep VMEM budget "
                f"{budget} B — the launch must shrink its tile or fall "
                "back (see ops.fused_cheb_sweep's budget guard)"))
    return findings


# ---------------------------------------------------------------------------
# Dtype discipline
# ---------------------------------------------------------------------------
def _float_dtypes(vars_) -> List[np.dtype]:
    import jax.numpy as jnp

    out = []
    for v in vars_:
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is None or jnp.issubdtype(dt, jax.dtypes.extended):
            continue  # no dtype, or a semaphore / PRNG key
        dt = np.dtype(dt)
        # jnp.issubdtype, not np.: the ml_dtypes floats (bfloat16, fp8)
        # are exactly the ones implicit promotion bites
        if jnp.issubdtype(dt, jnp.floating):
            out.append(dt)
    return out


def _mixed_ok_site(eqn) -> bool:
    """True when `eqn`'s source location is a :data:`DTYPE_MIXED_OK` site."""
    path, _line = source_location(eqn)
    if not path:
        return False
    return any(frag in path for frag, _why in DTYPE_MIXED_OK)


def check_dtype_discipline(fn: Callable, *example_args,
                           label: str = "fn",
                           mixed_ok: bool = True) -> List[Finding]:
    """JX-DTYPE-F64 + JX-DTYPE-PROMOTION over a traced `fn` (see module
    docstring for rule semantics; complex dtypes are exempt by design).

    ``mixed_ok=True`` (default) silently drops PROMOTION findings whose
    source location is a sanctioned :data:`DTYPE_MIXED_OK` site — the
    carve-out is metadata here, not an allowlist entry, so the
    justification travels with the rule.  Pass ``mixed_ok=False`` to see
    the raw findings (used by the carve-out's own tests).
    """
    closed = jax.make_jaxpr(fn)(*example_args)
    findings: List[Finding] = []

    def visit(eqn, ctx: EqnContext):
        in_f = _float_dtypes(eqn.invars)
        out_f = _float_dtypes(eqn.outvars)
        if any(d == np.float64 for d in out_f) \
                and not all(d == np.float64 for d in in_f):
            findings.append(_finding(
                "JX-DTYPE-F64", eqn, label,
                f"`{eqn.primitive.name}` upcasts to float64 on a hot path "
                f"(inputs {[str(d) for d in in_f]}): doubles every halo "
                "payload and leaves the f32 unit paths"))
        if eqn.primitive.name != "convert_element_type" \
                and len({d.itemsize for d in in_f}) > 1:
            if mixed_ok and _mixed_ok_site(eqn):
                return
            findings.append(_finding(
                "JX-DTYPE-PROMOTION", eqn, label,
                f"`{eqn.primitive.name}` mixes real floating widths "
                f"{sorted({str(d) for d in in_f})}: implicit promotion — "
                "cast explicitly so the recurrence dtype is intentional"))

    walk_jaxpr(closed, visit)
    return findings


# ---------------------------------------------------------------------------
# Plan-level bundle
# ---------------------------------------------------------------------------
def check_plan(plan, n: Optional[int] = None,
               batches: Sequence[int] = (1, 64),
               budget: Optional[int] = None,
               solve_methods: Sequence[str] = ()) -> List[Finding]:
    """Run every jaxpr check over one `ExecutionPlan`.

    Traces apply / apply_adjoint / apply_gram (unbatched (N,) signatures
    for the safety/VMEM/dtype checks; (B, N) for each B in `batches` for
    the schedule-equality check) and optionally ``plan.solve`` for each of
    `solve_methods`.  Findings carry ``symbol = "<backend>.<method>"`` so
    allowlist entries can pin to a traced target.
    """
    op = plan.op
    if n is None:
        if callable(op.P):
            raise ValueError("check_plan needs n= for a closure P")
        n = int(np.asarray(op.P).shape[0])
    findings: List[Finding] = []

    def spec(lead: tuple, *trailing) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(lead + trailing, np.float32)

    targets: List[Tuple[str, Callable, Callable[[tuple], tuple]]] = [
        ("apply", plan.apply, lambda lead: (spec(lead, n),)),
        ("apply_adjoint", plan.apply_adjoint,
         lambda lead: (spec(lead, op.eta, n),)),
        ("apply_gram", plan.apply_gram, lambda lead: (spec(lead, n),)),
    ]
    for method in solve_methods:
        def _solve(y, _m=method):
            return plan.solve(y, _m, tau=0.5).x

        targets.append((f"solve[{method}]", _solve,
                        lambda lead: (spec(lead, n),)))

    for name, fn, args_for in targets:
        label = f"{plan.backend}.{name}"
        args = args_for(())
        findings += check_comm_schedule(fn, *args, label=label)
        findings += check_vmem_budget(fn, *args, budget=budget, label=label)
        findings += check_dtype_discipline(fn, *args, label=label)
        findings += check_batch_schedule(
            lambda b, _fn=fn, _af=args_for: (_fn, _af((b,))),
            batches=batches, label=label)
    return findings
