"""The unified execution API: `GraphOperator` + `ExecutionPlan`.

One object owns the paper's math (coefficients of Eq. (14), error bound of
Prop. 4, message accounting of Section IV) and an explicit *plan* step picks
the execution strategy:

    op = GraphOperator(P, multipliers, lmax=lmax, K=20)
    plan = op.plan(backend="halo", mesh=mesh)     # or dense | pallas | allgather
    out  = plan.apply(f)            # Phi~ f          (..., N) -> (..., eta, N)
    sig  = plan.apply_adjoint(out)  # Phi~* a         (..., eta, N) -> (..., N)
    gr   = plan.apply_gram(f)       # Phi~* Phi~ f    (..., N) -> (..., N)
    res  = plan.solve_lasso(y, mu)  # Algorithm 3     (..., N) signals
    sol  = plan.solve(y, method="jacobi", tau=0.5)  # Section V solvers

Signals are ``(..., N)``: leading axes are batch signals, and because the
Chebyshev recurrence is linear every batch signal rides the *same* K
communication rounds (Section III-D's shared-rounds trick as a first-class
contract — B signals cost one sweep, not B).  Every backend honours the
same signatures and the same logical sizes — padding (Block-ELL tiles,
shard grids) is a backend detail, applied on the way in and stripped on
the way out.  New strategies register through :mod:`repro.dist.backends`
without touching any caller.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from .. import obs
from ..core.chebyshev import LocalMatvec
from ..core.multiplier import UnionMultiplier

Array = jax.Array

logger = logging.getLogger(__name__)

#: The matvec a runner's body is shape-probed with: P = I.
_PROBE = LocalMatvec(lambda v: v)


def canonical_kwarg(v) -> Any:
    """Hashable, collision-free canonical form of one solver kwarg value.

    The memo keys of :meth:`ExecutionPlan.compiled_solve` — and the
    serving engine's compatibility keys, which must agree with them —
    key array-valued kwargs by (shape, dtype, bytes) so two solves of
    different systems never share a compiled entry.  ``bool`` is tagged
    before the numeric paths because ``True == 1`` (and hashes equal):
    without the tag, ``use_pallas=True`` and ``use_pallas=1`` would
    collide into one entry keyed by whichever was compiled first.
    """
    if isinstance(v, bool):
        return ("bool", v)
    if isinstance(v, (list, tuple)):
        return tuple(canonical_kwarg(x) for x in v)
    if hasattr(v, "shape") or type(v).__module__ == "numpy":
        import numpy as np

        a = np.asarray(v)
        return (a.shape, str(a.dtype), a.tobytes())
    return v


def canonical_solve_items(solve_kwargs: Dict[str, Any]):
    """Sorted ``(name, canonical_kwarg(value))`` tuple for a kwargs dict.

    This IS the kwargs part of the `compiled_solve` memo key;
    `repro.serve` builds its request-compatibility keys from the same
    function so "same compat key" and "same compiled entry" can never
    drift apart.
    """
    return tuple((k, canonical_kwarg(v))
                 for k, v in sorted(solve_kwargs.items()))


class _Entry:
    """A jitted ``fn(structure, *args)`` called as ``entry(*args)``, with
    the plan's structure passed as arguments: ``lower(*args)`` lowers with
    it too."""

    def __init__(self, fn: Callable, structure: Tuple[Any, ...]):
        self.fn = jax.jit(fn)
        self.structure = structure

    def __call__(self, *args):
        return self.fn(self.structure, *args)

    def lower(self, *args):
        return self.fn.lower(self.structure, *args)


def _scoped(name: str, fn: Callable) -> Callable:
    """`fn` with every op it traces under the ``repro.<name>`` scope."""
    if getattr(fn, "obs_scope", None) == name:
        return fn

    @functools.wraps(fn)
    def run(*args, **kwargs):
        with obs.scope(name):
            return fn(*args, **kwargs)

    run.obs_scope = name
    return run


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """A compiled-strategy view of one GraphOperator.

    `apply` / `apply_adjoint` / `apply_gram` are jit-compatible closures with
    the uniform signatures documented on :class:`GraphOperator`.  `info`
    carries backend-specific cost metadata (halo bytes, Block-ELL occupancy,
    ...) for benchmarks and dashboards.  Serving loops should call the
    memoized :meth:`compiled` / :meth:`compiled_solve` wrappers instead of
    re-wrapping the closures in `jax.jit` per request.
    """

    op: UnionMultiplier
    backend: str
    apply: Callable[[Array], Array]
    apply_adjoint: Callable[[Array], Array]
    apply_gram: Callable[[Array], Array]
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)
    solve_lasso_fn: Optional[Callable] = None
    #: Backend-generic distributed-iteration primitive (the Section-V solver
    #: substrate): ``matvec_runner(fn, signals, consts=()) -> outputs`` runs
    #: the jit-compatible body ``fn(mv, *signals, *consts)`` against this
    #: backend's distributed matvec ``mv``, a `core.chebyshev.LocalMatvec`
    #: (applies P along the last axis on the backend's padded/sharded
    #: domain).  `signals` are (..., N) arrays
    #: with the vertex axis LAST — the runner pads them on the way in,
    #: shards them on the vertex axis, and crops every output back to the
    #: logical N; `consts` are small replicated arrays (coefficients).
    #: Backends that leave it None fall back to the single-device reference
    #: matvec in `plan.solve` (logged at INFO).
    matvec_runner: Optional[Callable] = None
    #: Arrays the entries read that a compiled program takes as arguments
    #: (a multi-shard general plan's structure, laid out on its mesh):
    #: closed over, they would be compiled in as constants, and a program
    #: over several devices keeps each constant whole on every one.  Empty
    #: where the entries close over their graph.
    structure: Tuple[Any, ...] = ()
    #: ``over(structure) -> ExecutionPlan``: this plan with every entry
    #: reading `structure` instead (traced arrays, under `jax.jit`); None
    #: when `structure` is empty.
    over: Optional[Callable] = None

    def __post_init__(self):
        # the plan entries name their device phase once, for every backend
        for kind in ("apply", "apply_adjoint", "apply_gram"):
            object.__setattr__(self, kind, _scoped(kind, getattr(self, kind)))

    # compiled-callable memoization ----------------------------------------
    def _jit_cache(self) -> Dict[Any, Any]:
        """Per-plan memo for jitted callables (frozen-dataclass __dict__
        idiom, like the operator's coefficient cache)."""
        return self.__dict__.setdefault("_compiled", {})

    def compiled(self, kind: str = "apply") -> Callable[[Array], Array]:
        """Memoized `jax.jit`-wrapped plan method for serving loops.

        ``plan.compiled("apply")`` returns THE SAME jit wrapper on every
        call, so repeated serving requests hit jax's per-(shape, dtype)
        trace cache instead of retracing — the failure mode of writing
        ``jax.jit(plan.apply)`` afresh per request, which builds a new
        wrapper (and a new empty cache) every time.  kind: ``"apply"`` |
        ``"apply_adjoint"`` | ``"apply_gram"``.  The program takes the
        plan's `structure` as arguments.
        """
        fns = {"apply": self.apply, "apply_adjoint": self.apply_adjoint,
               "apply_gram": self.apply_gram}
        if kind not in fns:
            raise KeyError(f"unknown kind {kind!r}; available: "
                           f"{sorted(fns)}")
        # The memo is per-plan, but the exchange precision and partition
        # identity still join the key: plans rebuilt at a different
        # ``exchange_dtype`` or ``partition=`` that share a cache (e.g.
        # via copy/replace) must never serve each other's compiled
        # entries.  GeneralPartition plans carry a content fingerprint;
        # banded plans key on the literal "banded".
        key = (kind, self.info.get("exchange_dtype", "f32"),
               self.info.get("partition_fingerprint",
                             self.info.get("partition", "banded")),
               self.info.get("fault_key", "none"))
        cache = self._jit_cache()
        if key not in cache:
            def entry(structure, x):
                return getattr(self._over(structure), kind)(x)

            entry.__name__ = kind  # the program is named after its kind
            cache[key] = _Entry(entry, self.structure)
        return cache[key]

    def _over(self, structure) -> "ExecutionPlan":
        """This plan reading `structure` (itself when it has none)."""
        return self if self.over is None else self.over(structure)

    def compiled_solve(self, method: str = "chebyshev", **solve_kwargs):
        """Memoized jitted Section-V solver: ``y -> x`` (or ``(x, history)``
        with ``history=True``).

        Keyed per (method, solver kwargs); shapes/dtypes are handled by
        jax's own jit cache, so a serving loop calling
        ``plan.compiled_solve("jacobi", tau=0.5)(y)`` pays the numpy solve
        setup and the trace once per signature.  Array-valued kwargs
        (``den_diag=``, explicit ``poles=``) key by value (bytes), so two
        plans solving different systems never share a cache entry — which
        also means every `compiled_solve` *lookup* re-hashes those arrays:
        hold the returned callable in the request loop rather than calling
        ``compiled_solve(...)`` per request when passing large arrays.  The
        program takes the plan's `structure` as arguments, as
        :meth:`compiled`'s do.
        """
        key = (("solve", method, self.info.get("exchange_dtype", "f32"),
                self.info.get("partition_fingerprint",
                              self.info.get("partition", "banded")),
                self.info.get("fault_key", "none"))
               + canonical_solve_items(solve_kwargs))
        cache = self._jit_cache()
        if key not in cache:
            history = bool(solve_kwargs.get("history", False))

            def run(structure, y):
                res = self._over(structure).solve(y, method, **solve_kwargs)
                return (res.x, res.history) if history else res.x

            cache[key] = _Entry(run, self.structure)
        return cache[key]

    def bucketed_callables(self, buckets, kinds=("apply",),
                           solve_specs=(), n: Optional[int] = None,
                           dtype=None, warm: bool = False):
        """Enumerate the compiled entries a serving loop dispatches onto.

        Continuous-batching serving (``repro.serve``) pads every dynamic
        batch to a fixed set of bucket sizes so the engine only ever
        presents ``len(buckets)`` signatures per callable — this method
        is the inventory of that contract.  Returns an ordered dict

            {(label, B): callable}

        where `label` is a plan kind (``"apply"`` | ``"apply_adjoint"``
        | ``"apply_gram"``) or ``("solve", method, canonical-kwargs)``
        for each ``(method, kwargs)`` pair in `solve_specs`, and the
        callable takes one ``(B, N)`` stack (``(B, eta, N)`` for the
        adjoint).  Entries for the same label share ONE memoized jit
        wrapper (:meth:`compiled` / :meth:`compiled_solve`): bucket
        specialization lives in jax's per-shape trace cache under it, so
        distinct buckets get distinct compiled executables while repeat
        calls at any enumerated bucket never retrace.

        ``warm=True`` runs each entry once on zeros of its bucket shape,
        paying every trace + compile up front so the first real request
        of each bucket is served at steady-state latency.  `n` defaults
        to the operator's dense-P dimension (pass it for closure-P
        operators).
        """
        import collections

        import jax.numpy as jnp
        import numpy as np

        if n is None:
            if callable(self.op.P):
                raise ValueError(
                    "bucketed_callables needs n= for a closure P")
            n = int(np.asarray(self.op.P).shape[0])
        dtype = dtype or jnp.float32
        buckets = tuple(sorted({int(b) for b in buckets}))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        entries = collections.OrderedDict()
        for kind in kinds:
            fn = self.compiled(kind)
            lead = (self.op.eta,) if kind == "apply_adjoint" else ()
            for B in buckets:
                entries[(kind, B)] = (fn, (B,) + lead + (int(n),))
        for method, kw in solve_specs:
            kw = dict(kw or {})
            label = ("solve", method) + canonical_solve_items(kw)
            fn = self.compiled_solve(method, **kw)
            for B in buckets:
                entries[(label, B)] = (fn, (B, int(n)))
        out = collections.OrderedDict()
        for (label, B), (fn, shape) in entries.items():
            if warm:
                fn(jnp.zeros(shape, dtype))
            out[(label, B)] = fn
        return out

    # mirrored operator metadata -------------------------------------------
    @property
    def eta(self) -> int:
        return self.op.eta

    @property
    def K(self) -> int:
        return self.op.K

    @property
    def lmax(self) -> float:
        return self.op.lmax

    @property
    def coeffs(self):
        return self.op.coeffs

    def error_bound(self) -> float:
        return self.op.error_bound()

    def message_counts(self, n_edges: int) -> dict:
        return self.op.message_counts(n_edges)

    # Section V solvers -----------------------------------------------------
    def solve(self, y: Array, method: str = "chebyshev", **kwargs):
        """Apply x = g(P) y by a Section-V iterative method, distributed.

        The solver problem is the rational filter g = num/den (monomial
        coefficients, low-degree-first) — equivalently: solve
        ``den(P) x = num(P) y`` (Eq. (23), Q = g(P)^{-1}).  Sugar: pass
        ``tau=`` (+ ``r=``, ``h_scale=``) for the Tikhonov/SSL family
        g = tau / (tau + h_scale * lambda^r); named specs live in
        `repro.core.filters` (`tikhonov_rational`,
        `inverse_filter_rational`, `random_walk_rational`).

        method: ``"chebyshev"`` (Section IV truncated approximation, order
        n_iters), ``"jacobi"`` (Eq. (24)), ``"cheb_jacobi"`` (Eq. (25);
        needs rho < 1, estimated if omitted), ``"arma"`` (Eqs. (29)-(30);
        pole/residue recursion, |p_k| > lmax/2 required for convergence).

        y: (..., N) batched signals — every signal shares the exchange
        rounds; each round costs exactly the backend's matvec communication
        (boundary-only halos under halo/pallas_halo), with Jacobi rounds
        costing deg(den) matvecs.  Runs inside this plan's
        ``matvec_runner``; backends without one fall back to the reference
        matvec (logged).  Returns a :class:`repro.dist.solvers.SolveResult`
        (``history=True`` records the per-round iterates).

        Keyword reference: see API.md ("Section V solvers — plan.solve")
        and :func:`repro.dist.solvers.solve_plan`.
        """
        from .solvers import solve_plan

        with obs.scope("solve"):
            return solve_plan(self, y, method, **kwargs)

    # Algorithm 3 -----------------------------------------------------------
    def solve_lasso(self, y: Array, mu, gamma: Optional[float] = None,
                    n_iters: int = 300, **kwargs):
        """Distributed wavelet lasso (Section VI) under this plan's backend.

        y: (..., N) — batched signals share every exchange round; mu:
        scalar, (eta,) per-scale, or (..., eta) per-signal weights.

        Backends that can fuse the whole ISTA loop (halo / pallas_halo: one
        shard_map) override the generic path.  The fused path takes no
        extra loop knobs, so kwargs that *change* the loop (a0,
        record_objective, soft_threshold_fn, ...) route to the generic ISTA
        over this plan's apply/apply_adjoint instead of being dropped —
        kwargs explicitly passed at their default values are benign and do
        NOT forfeit fusion.  Every forfeit is logged (INFO) with the
        offending kwargs, and `LassoResult.fused` records which path ran,
        so benchmarks can't silently misattribute the slow path.
        """
        import jax.numpy as jnp

        from ..core import lasso as _lasso

        if gamma is None:
            gamma = _lasso.ista_step_size(self.op)
        if self.solve_lasso_fn is not None:
            # drop benign kwargs (== the generic-ISTA defaults); only
            # genuinely loop-changing kwargs forfeit the fused path
            benign = {"a0": None, "record_objective": False,
                      "soft_threshold_fn": _lasso.soft_threshold}
            blocking = {k: v for k, v in kwargs.items()
                        if not (k in benign and v is benign[k])}
            # per-vertex mu ((..., eta, N): trailing axis is N, not eta)
            # also runs the generic loop — the fused backends thresh on the
            # padded shard domain and take scalar/(eta,)/(..., eta) only
            mu_arr = jnp.asarray(mu)
            if mu_arr.ndim >= 2 and mu_arr.shape[-1] != self.op.eta:
                blocking["mu"] = f"per-vertex, shape {mu_arr.shape}"
            if not blocking:
                return self.solve_lasso_fn(y, mu, gamma, n_iters)
            obs.count("lasso.unfused")
            logger.info(
                "solve_lasso[%s]: %s forfeit the fused in-shard_map "
                "ISTA; running the generic (unfused) loop",
                self.backend, sorted(blocking))
        return _lasso.distributed_lasso(self, y, mu=mu, gamma=gamma,
                                        n_iters=n_iters, **kwargs)


def _vspec(ndim: int, axis: str):
    """PartitionSpec sharding only the last of `ndim` axes on `axis` —
    batch / eta axes replicate, the vertex axis splits across shards."""
    from jax.sharding import PartitionSpec as P

    return P(*((None,) * (ndim - 1) + (axis,)))


def _same(x, *_):
    return x


@dataclasses.dataclass(frozen=True)
class LocalForm:
    """What a backend hands the plan body (:func:`plan_from_form`): its
    local operator and the way signals reach it.  The body writes apply,
    adjoint, gram, the fused lasso and the solver runner once over it.

    local: ``local(mine) -> LocalMatvec``, the matvec over one shard's
        arrays (`arrays` with their shard axis indexed away).  Without a
        mesh it is called once, at plan build, on shard 0's arrays.
    recurrence: ``(mv, x, coeffs, lmax) -> (..., eta, n)`` for (eta, K+1)
        coeffs, the forward recurrence of this form.
    arrays: per-shard arrays, leading axis the shard, split on `axis`.
    structure: the arrays are the plan's `structure`, arguments of every
        compiled program; otherwise they are closed over as constants.
    glob_in / glob_out: a whole signal into / out of the body (outside
        any shard_map): padding, or the gather into partition order.
    enter / leave: ``(x, mine) -> x`` on a shard (on the device, without
        a mesh): into the local domain and back out.
    mesh / axis: the body runs in a shard_map over `axis` of `mesh`; no
        mesh runs it directly.
    count: an `obs` counter every entry adds once per trace.
    lasso: the plan runs `solve_lasso` in the body; otherwise the generic
        ISTA over apply / apply_adjoint runs.
    host_coeffs: apply and gram hand `recurrence` their coefficients as
        host arrays, for it to convert (the single-device sweep does);
        otherwise they are converted before the signals enter.
    """

    local: Callable
    recurrence: Callable
    arrays: Tuple[Any, ...] = ()
    structure: bool = False
    glob_in: Callable = _same
    glob_out: Callable = _same
    enter: Callable = _same
    leave: Callable = _same
    mesh: Any = None
    axis: Optional[str] = None
    count: Optional[str] = None
    lasso: bool = True
    host_coeffs: bool = False


def plan_from_form(op, backend: str, form: LocalForm,
                   info: Dict[str, Any]) -> ExecutionPlan:
    """The ExecutionPlan of `form`: every entry written once, here."""
    import jax.numpy as jnp
    import numpy as np

    from ..core import chebyshev as cheb
    from ..core.lasso import LassoResult, _mu_threshold, soft_threshold

    coeffs, lmax = op.coeffs, op.lmax
    if form.mesh is None:
        mine0 = tuple(a[0] for a in form.arrays)
        mv0 = form.local(mine0)

    def run(arrays, inner, signals, consts, out_ndim):
        """``inner(mv, mine, *signals, *consts)`` on every shard."""
        if form.count:
            obs.count(form.count)
        sigs = tuple(form.glob_in(jnp.asarray(s)) for s in signals)
        if form.mesh is None:
            outs = inner(mv0, mine0, *sigs, *consts)
        else:
            from jax.sharding import PartitionSpec as P

            na, axis = len(arrays), form.axis

            def shard(*args):
                mine = tuple(a[0] for a in args[:na])
                return inner(form.local(mine), mine, *args[na:])

            if callable(out_ndim):
                out_ndim = out_ndim(sigs)
            outs = jax.shard_map(
                shard, mesh=form.mesh,
                in_specs=((P(axis),) * na
                          + tuple(_vspec(s.ndim, axis) for s in sigs)
                          + (P(),) * len(consts)),
                out_specs=jax.tree.map(lambda d: _vspec(d, axis), out_ndim),
                check_vma=False)(*arrays, *sigs, *consts)
        return jax.tree.map(form.glob_out, outs)

    def apply(arrays, f):
        def inner(mv, mine, x, c):
            return form.leave(form.recurrence(mv, form.enter(x, mine), c,
                                              lmax), mine)

        c = (np.atleast_2d(coeffs) if form.host_coeffs
             else jnp.atleast_2d(jnp.asarray(coeffs, f.dtype)))
        return run(arrays, inner, (f,), (c,), f.ndim + 1)

    def apply_adjoint(arrays, a):
        def inner(mv, mine, x, c):
            return form.leave(cheb.cheb_apply_adjoint(
                mv, form.enter(x, mine), c, lmax), mine)

        c = jnp.asarray(coeffs, a.dtype)
        return run(arrays, inner, (a,), (c,), a.ndim - 1)

    def apply_gram(arrays, f):
        # Phi~* Phi~ by the product coefficients (Section IV-C)
        def inner(mv, mine, x, d):
            out = form.recurrence(mv, form.enter(x, mine), d, lmax)
            return form.leave(out[..., 0, :], mine)

        d = (cheb.gram_coeffs(coeffs)[None] if form.host_coeffs
             else jnp.asarray(cheb.gram_coeffs(coeffs), f.dtype)[None])
        return run(arrays, inner, (f,), (d,), f.ndim)

    def solve_lasso(arrays, y, mu, gamma, n_iters):
        # Algorithm 3 in the body: the whole ISTA loop on the local domain
        # (padded rows stay zero), left once on the way out
        def inner(mv, mine, yl, c, thresh):
            phi_y = form.recurrence(mv, form.enter(yl, mine), c, lmax)

            def body(a, _):
                back = cheb.cheb_apply_adjoint(mv, a, c, lmax)
                gram_a = form.recurrence(mv, back, c, lmax)
                return soft_threshold(a + gamma * (phi_y - gram_a),
                                      thresh), None

            a_star, _ = jax.lax.scan(body, jnp.zeros_like(phi_y), None,
                                     length=n_iters)
            y_star = cheb.cheb_apply_adjoint(mv, a_star, c, lmax)
            return form.leave(a_star, mine), form.leave(y_star, mine)

        c = jnp.asarray(coeffs, y.dtype)
        thresh = _mu_threshold(mu, op.eta, y.dtype, gamma)
        a_star, y_star = run(arrays, inner, (y,), (c, thresh),
                             (y.ndim + 1, y.ndim))
        return LassoResult(coeffs=a_star, signal=y_star, objective=jnp.nan,
                           n_iters=n_iters, fused=True)

    def matvec_runner(arrays, fn, signals, consts=()):
        ns = len(signals)

        def inner(mv, mine, *args):
            outs = fn(mv, *(form.enter(s, mine) for s in args[:ns]),
                      *args[ns:])
            return jax.tree.map(lambda o: form.leave(o, mine), outs)

        def out_ndim(sigs):
            probe = jax.eval_shape(lambda *a: fn(_PROBE, *a), *sigs, *consts)
            return jax.tree.map(lambda sd: sd.ndim, probe)

        return run(arrays, inner, tuple(signals), tuple(consts), out_ndim)

    def entries(arrays):
        def bind(fn):
            return functools.partial(fn, arrays)

        return dict(apply=bind(apply), apply_adjoint=bind(apply_adjoint),
                    apply_gram=bind(apply_gram),
                    solve_lasso_fn=bind(solve_lasso) if form.lasso else None,
                    matvec_runner=bind(matvec_runner))

    if not form.structure:
        return ExecutionPlan(op=op, backend=backend, info=info,
                             **entries(form.arrays))

    def over(structure):
        return ExecutionPlan(op=op, backend=backend, info=info,
                             structure=structure, over=over,
                             **entries(structure))

    return over(form.arrays)


@dataclasses.dataclass(frozen=True)
class GraphOperator(UnionMultiplier):
    """Union of graph multiplier operators with pluggable execution.

    Construction computes the truncated shifted-Chebyshev coefficients once
    (Eq. (14)); `.plan(backend=...)` binds an execution strategy.  Uniform
    plan signatures across all backends (leading `...` = batch signals
    sharing the K communication rounds):

        plan.apply(f)          f: (..., N)      ->  (..., eta, N)
        plan.apply_adjoint(a)  a: (..., eta, N) ->  (..., N)
        plan.apply_gram(f)     f: (..., N)      ->  (..., N)
        plan.solve_lasso(y, mu, ...)            ->  LassoResult (batched)

    GraphOperator also keeps every UnionMultiplier method (`apply`,
    `exact_apply`, `error_bound`, ...), so it is a drop-in replacement —
    `op.apply(f)` is simply shorthand for `op.plan("dense").apply(f)`.
    """

    # `plan` is inherited from UnionMultiplier (defined there so legacy
    # UnionMultiplier instances route through the same registry); the
    # subclass exists to give the unified API its own name + docs and to
    # host future plan-level caching without touching the math core.


def as_graph_operator(op: UnionMultiplier) -> GraphOperator:
    """Re-wrap any UnionMultiplier as a GraphOperator (shares P, no copy)."""
    if isinstance(op, GraphOperator):
        return op
    return GraphOperator(P=op.P, multipliers=op.multipliers, lmax=op.lmax,
                         K=op.K, coeff_points=op.coeff_points)
