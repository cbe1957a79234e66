"""Distributed Section-V solvers behind one entry point: `plan.solve()`.

The paper's Section V frames *exact* inverse filtering as solving

    Q x = y,   Q = g(P)^{-1}                                     (Eq. (23))

by iterations that cost one-or-a-few matvecs per round — Jacobi (Eq. (24)),
Chebyshev-accelerated Jacobi (Eq. (25)) and the parallel ARMA recursion
(Eqs. (29)-(30)) — which makes them exactly as distributable as the
Section-IV Chebyshev recurrence.  This module runs all of them (plus the
Section-IV truncated-Chebyshev approximation itself, for like-for-like
error-vs-communication comparisons) under every registered execution
backend:

    plan = op.plan("pallas_halo", mesh=mesh)
    res  = plan.solve(y, method="jacobi", tau=0.5, r=2, n_iters=20)
    res.x           # (..., N) solutions, batched signals share the rounds
    res.history     # optional (n_iters, ..., N) iterate history
    res.info        # matvecs/round, rho, ARMA stability, ...

The solver problem is a *rational* filter g(lambda) = num(lambda)/den(lambda)
given by monomial coefficients (low-degree-first; see
`repro.core.filters.power_rational` & friends), from which every method is
derived:

  * ``chebyshev``  — truncated shifted-Chebyshev approximation of g
    (Section IV; n_iters = order K, one matvec per round);
  * ``jacobi``     — Jacobi on den(P) x = num(P) y (Eq. (24);
    deg(den) matvecs per round — Fig. 2(b)'s "2 matvecs per iteration");
  * ``cheb_jacobi``— Chebyshev-accelerated Jacobi (Eq. (25); needs a
    spectral-radius bound rho < 1, estimated by power iteration if omitted);
  * ``arma``       — pole/residue parallel recursion (Eqs. (29)-(30);
    converges iff |p_k| > (lmax - lmin)/2, checked and recorded).

Backends participate through one extracted primitive: the plan's
``matvec_runner`` executes an arbitrary jit-compatible iteration body
against the backend's distributed matvec (padding, sharding specs and halo
exchange handled by the backend), so a solver round costs exactly the
boundary-only exchanges of one matvec — measured, not assumed, by
:func:`repro.dist.commstats.solve_comm_stats`.  Backends without a runner
(out-of-tree registrations) fall back to the single-device reference
matvec, logged at INFO.

Single-launch fast path: a runner matvec record with its ``block_ell``
set (a purely local Block-ELL product — `pallas`; `pallas_halo` on one
shard) collapses a whole Jacobi / accelerated-Jacobi solve into ONE
`kernels.cheb_sweep.jacobi_sweep` kernel launch (the Chebyshev method
rides the same upgrade inside `ops.fused_cheb_recurrence`), VMEM-guarded
with a logged per-round fallback — see docs/ARCHITECTURE.md "Perf
accounting".
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import arma as _arma
from ..core import chebyshev as cheb
from ..core import jacobi as _jacobi

Array = jax.Array

logger = logging.getLogger(__name__)

#: The `plan.solve` method vocabulary (tools/check_docs.py asserts every
#: entry is documented in API.md).
METHODS = ("chebyshev", "jacobi", "cheb_jacobi", "arma")


@dataclasses.dataclass
class SolveResult:
    """Result of one `plan.solve` call.

    x: (..., N) solutions (same leading batch dims as the input y).
    history: (n_iters, ..., N) iterate stack when `history=True` — the
    error-vs-communication-budget hook Fig. 2 plots; `history_errors`
    converts it to per-round errors against a reference.
    info: method/backend diagnostics — `matvecs_per_round` (Jacobi rounds
    that cost deg(den) matvecs show it), `exchange_rounds` (the closed-form
    matvec count; `commstats.solve_comm_stats` measures the same number
    from the jaxpr), `rho` / `arma_stable` convergence data.
    """

    x: Array
    method: str
    backend: str
    n_iters: int
    history: Optional[Array] = None
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def history_errors(self, target: Array) -> np.ndarray:
        """Per-iterate l2 errors ||x^{(t)} - target|| (summed over batch).

        Pairs with `info["matvecs_per_round"]` to plot error against
        communication budget in matvec-equivalents (Fig. 2's axes)."""
        if self.history is None:
            raise ValueError("solve(..., history=True) to record iterates")
        h = np.asarray(self.history)
        t = np.asarray(target)
        diff = h - t[None]
        return np.sqrt((diff * diff).reshape(h.shape[0], -1).sum(axis=1))


# ---------------------------------------------------------------------------
# Rational-spec plumbing
# ---------------------------------------------------------------------------
def _resolve_rational(num, den, tau, r, h_scale):
    """(num, den) monomial coefficients (low-first) or (None, None)."""
    if den is not None:
        num = (1.0,) if num is None else num
        return (tuple(float(c) for c in num), tuple(float(c) for c in den))
    if num is not None:
        raise ValueError("num= given without den=")
    if tau is not None:
        from ..core.filters import power_rational

        return power_rational(tau, r, h_scale)
    return None, None


def _rational_callable(num, den):
    nh = np.asarray(num, dtype=np.float64)[::-1]
    dh = np.asarray(den, dtype=np.float64)[::-1]

    def g(lam):
        lam = np.asarray(lam, dtype=np.float64)
        return np.polyval(nh, lam) / np.polyval(dh, lam)

    return g


def poly_matvec(mv, coeffs: Tuple[float, ...], x: Array) -> Array:
    """p(P) x by Horner — exactly deg(p) matvecs (= exchange rounds)."""
    acc = coeffs[-1] * x
    for c in reversed(coeffs[:-1]):
        acc = mv(acc) + c * x
    return acc


def _poly_matvec_protocol(mv, coeffs: Tuple[float, ...]):
    """:func:`poly_matvec` as a matvec record of the same protocol.

    When the record `mv` carries the dual-signature error-feedback
    protocol (its ``init_state``; see
    `repro.core.chebyshev._stateful_matvec`), the returned
    ``p(P)``-matvec forwards it so the iteration loops can thread the
    quantizer residual through every Horner step.
    """
    def pmv(x, state=None):
        if state is None:
            return poly_matvec(mv, coeffs, x)
        acc = coeffs[-1] * x
        for c in reversed(coeffs[:-1]):
            h, state = mv(acc, state)
            acc = h + c * x
        return acc, state

    return cheb.LocalMatvec(pmv, init_state=mv.init_state)


def _poly_diag(P_dense: np.ndarray, coeffs: Sequence[float]) -> np.ndarray:
    """diag(p(P)) for the Jacobi split, computed once at solve setup.

    diag(P^0) = 1 and diag(P^1) = diag(P) are free; diag(P^2) is one
    O(N^2) einsum; higher powers accumulate dense matrix powers (setup-time
    numpy, acceptable at validation scale — pass `den_diag=` to skip)."""
    P_dense = np.asarray(P_dense)
    n = P_dense.shape[0]
    d = np.full(n, float(coeffs[0]))
    if len(coeffs) > 1 and coeffs[1] != 0.0:
        d = d + coeffs[1] * np.diag(P_dense)
    if len(coeffs) > 2 and coeffs[2] != 0.0:
        d = d + coeffs[2] * np.einsum("ij,ji->i", P_dense, P_dense)
    for m in range(3, len(coeffs)):
        if coeffs[m] == 0.0:
            continue
        d = d + coeffs[m] * np.diag(np.linalg.matrix_power(P_dense, m))
    return d


def _estimate_rho(op, den: Tuple[float, ...], inv_d: np.ndarray,
                  n_iters: int = 100) -> float:
    """Spectral radius of M = I - D^{-1} den(P) by power iteration.

    Pure-numpy setup-time estimate (a scalar, not part of the distributed
    hot loop — and deliberately outside any jax trace so
    `solve_comm_stats` can trace `plan.solve` without concretization
    errors).  D^{-1} den(P) is similar to a symmetric matrix for symmetric
    P, so the dominant eigenvalue is real and plain power iteration
    converges.  The returned value carries a 2% safety factor — pass
    `rho=` for the exact bound.  Needs a dense P (like the Jacobi diagonal
    itself); closure-P operators must pass `rho=` explicitly.
    """
    if callable(op.P):
        raise ValueError(
            "cheb_jacobi needs a spectral-radius bound; P is a matvec "
            "closure — pass rho= explicitly")
    Pm = np.asarray(op.P, dtype=np.float64)

    def mv(v):
        return Pm @ v

    rng = np.random.default_rng(0)
    v = rng.standard_normal(Pm.shape[0])
    v = v / np.linalg.norm(v)
    nrm = 0.0
    for _ in range(n_iters):
        w = v - inv_d * np.asarray(poly_matvec(mv, den, v))
        nrm = float(np.linalg.norm(w))
        v = w / nrm
    return nrm * 1.02


def _fallback_runner(plan):
    mv = cheb.LocalMatvec(plan.op.matvec)

    def runner(fn, signals, consts=()):
        return fn(mv, *signals, *consts)

    return runner


def _op_solver_cache(op) -> Dict[Any, Any]:
    """Per-operator memo for the dense solve setup (diag(den(P)), rho).

    Stored in the instance __dict__ exactly like the frozen dataclass's
    `cached_property` coefficients, keyed by the den tuple — repeat solves
    (budget sweeps, solve_comm_stats re-traces) pay only the distributed
    iteration, not the O(N^2)-O(N^3) numpy setup."""
    return op.__dict__.setdefault("_solver_cache", {})


def _resolve_den_diag(op, den, den_diag):
    if den_diag is not None:
        return np.asarray(den_diag)
    if callable(op.P):
        raise ValueError(
            "the Jacobi split needs diag(den(P)); P is a matvec closure — "
            "pass den_diag= explicitly")
    cache = _op_solver_cache(op)
    key = ("den_diag", den)
    if key not in cache:
        cache[key] = _poly_diag(np.asarray(op.P), den)
    return cache[key]


# ---------------------------------------------------------------------------
# The entry point behind ExecutionPlan.solve
# ---------------------------------------------------------------------------
def solve_plan(
    plan,
    y: Array,
    method: str = "chebyshev",
    *,
    num: Optional[Sequence[float]] = None,
    den: Optional[Sequence[float]] = None,
    tau: Optional[float] = None,
    r: int = 1,
    h_scale: float = 1.0,
    n_iters: Optional[int] = None,
    rho: Optional[float] = None,
    den_diag: Optional[Array] = None,
    poles: Optional[Sequence[complex]] = None,
    residues: Optional[Sequence[complex]] = None,
    const: Optional[float] = None,
    x0: Optional[Array] = None,
    history: bool = False,
    use_pallas: Optional[bool] = None,
    vmem_budget: Optional[int] = None,
    check_every: int = 0,
) -> SolveResult:
    """Apply x = g(P) y by the Section-V method of choice, distributed.

    See :meth:`repro.dist.operator.ExecutionPlan.solve` for the user-facing
    reference; this is the implementation shared by every backend.

    ``vmem_budget=`` overrides the single-launch sweep's VMEM guard for
    this call only (bytes; default `kernels.ops.DEFAULT_SWEEP_VMEM_BUDGET`)
    — tightening it forces the logged per-order fallback, the knob
    `tools/lint_repro.py`'s JX-VMEM-BUDGET check and the budget-sweep
    benchmarks share.  It changes the traced program, so it is part of the
    `compiled_solve` cache key like every other solver kwarg.

    ``check_every=r`` (default 0 = off, exactly today's behavior) arms the
    **divergence guard**: the solve evaluates the relative residual
    ``||num(P) y - den(P) x|| / ||num(P) y|`` under the plan's own
    (possibly fault-injected) matvec and reports it honestly in
    ``info["residual"]`` / ``info["diverged"]``, with
    ``info["exchange_rounds"]`` counting the residual evaluations' extra
    matvecs.  For plain ``method="jacobi"`` (a stationary iteration, so
    restarting from the current iterate is trajectory-exact) the solve
    runs in chunks of r rounds with a residual/NaN check between chunks
    and exits early once the iteration has demonstrably diverged
    (non-finite, or growing past ``2 x max(best, 1)``); the other methods
    run to completion and take a single post-solve residual/NaN check.
    Guarded runs are eager (one runner launch per chunk), so serving
    loops should keep ``check_every=0`` on known-convergent systems."""
    if method not in METHODS:
        raise ValueError(
            f"unknown solve method {method!r}; available: {METHODS}")
    op = plan.op
    num, den = _resolve_rational(num, den, tau, r, h_scale)
    K = int(n_iters) if n_iters is not None else op.K
    if K < 1:
        raise ValueError("n_iters must be >= 1")

    runner = plan.matvec_runner
    if runner is None:
        obs.count("solve.reference_matvec")
        logger.info(
            "solve[%s]: backend provides no matvec_runner; falling back to "
            "the single-device reference matvec (results are exact, but the "
            "iteration does not run under the backend's execution strategy)",
            plan.backend)
        runner = _fallback_runner(plan)

    if vmem_budget is not None:
        # the per-call sweep budget rides on the runner's matvec record
        plain = runner

        def runner(fn, signals, consts=()):
            def budgeted(mv, *args):
                return fn(dataclasses.replace(mv, vmem_budget=int(
                    vmem_budget)), *args)

            return plain(budgeted, signals, consts)

    y = jnp.asarray(y)
    info: Dict[str, Any] = {"num": num, "den": den}
    check_every = int(check_every)
    if check_every < 0:
        raise ValueError("check_every must be >= 0")

    if method == "chebyshev":
        res = _solve_chebyshev(plan, runner, y, num, den, K, history,
                               use_pallas, info)
        if check_every > 0:
            _post_solve_check(res, runner, y, num, den, check_every)
        return res
    if den is None and not (method == "arma" and poles is not None):
        raise ValueError(
            f"method {method!r} needs the rational filter spec: pass "
            "tau= (+ r=, h_scale=) or num=/den= monomial coefficients "
            "(see repro.core.filters.power_rational / tikhonov_rational / "
            "inverse_filter_rational)" + (
                "; arma also accepts an explicit poles=/residues= form"
                if method == "arma" else ""))
    if method == "jacobi" and check_every > 0 and not history:
        return _solve_jacobi_guarded(plan, runner, y, num, den, K, rho,
                                     den_diag, x0, use_pallas, check_every,
                                     info)
    if method in ("jacobi", "cheb_jacobi"):
        res = _solve_jacobi(plan, runner, y, num, den, K, method, rho,
                            den_diag, x0, history, use_pallas, info)
    else:
        res = _solve_arma(plan, runner, y, num, den, K, poles, residues,
                          const, x0, history, info)
    if check_every > 0:
        _post_solve_check(res, runner, y, num, den, check_every)
    return res


# ---------------------------------------------------------------------------
# Divergence guard (check_every=r)
# ---------------------------------------------------------------------------
#: A checked residual counts as divergence once it exceeds this factor
#: times max(best residual so far, 1.0) — 1.0 being the zero iterate's
#: relative residual, so a solve that never beats "do nothing" and is
#: growing is flagged while honest slow convergence is not.
_DIVERGENCE_FACTOR = 2.0


def _solve_residual(runner, y, x, num, den):
    """Relative residual ||num(P) y - den(P) x|| / ||num(P) y|| evaluated
    through the plan's own matvec (the fault-injected one, if any) — the
    number a real deployment could actually measure.  Costs
    deg(num) + deg(den) exchange rounds; callers account for them."""

    def fn(mv, yl, xl):
        return poly_matvec(mv, num, yl), poly_matvec(mv, den, xl)

    b, ax = runner(fn, (y, x))
    bn = float(jnp.linalg.norm(b))
    rn = float(jnp.linalg.norm(b - ax))
    return rn / max(bn, 1e-30)


def _post_solve_check(res, runner, y, num, den, check_every):
    """Single residual/NaN check after a completed solve (methods whose
    trajectory cannot restart mid-run: chebyshev, cheb_jacobi, arma, and
    any history-recording run).  Mutates ``res.info`` in place."""
    finite = bool(jnp.all(jnp.isfinite(res.x)))
    residual = None
    if den is not None:
        residual = _solve_residual(runner, y, res.x, num, den)
        res.info["exchange_rounds"] = (
            res.info.get("exchange_rounds", 0)
            + (len(num) - 1) + (len(den) - 1))
    diverged = (not finite) or (residual is not None
                                and not np.isfinite(residual))
    if residual is not None and np.isfinite(residual):
        diverged = diverged or residual > _DIVERGENCE_FACTOR
    res.info.update(check_every=check_every, residual=residual,
                    diverged=bool(diverged))


def _solve_jacobi_guarded(plan, runner, y, num, den, K, rho, den_diag, x0,
                          use_pallas, check_every, info):
    """Plain Jacobi in chunks of `check_every` rounds with a residual/NaN
    check between chunks and early exit on divergence.

    Jacobi (Eq. (24)) is a stationary iteration — restarting from the
    current iterate reproduces the unchunked trajectory exactly (the one
    caveat is per-runner-launch state like the fault injector's round
    counter and the int8 error-feedback residuals, which reset per chunk;
    determinism per configuration is preserved).  ``exchange_rounds``
    reports what actually ran: per chunk, deg(num) for the right-hand
    side + iters x deg(den) for the sweep + deg(num) + deg(den) for the
    residual evaluation.
    """
    deg_den = len(den) - 1
    deg_num = len(num) - 1
    x = x0
    rounds = 0
    done = 0
    residuals = []
    best = 1.0  # the zero iterate's relative residual
    diverged = False
    while done < K:
        iters = min(check_every, K - done)
        sub = _solve_jacobi(plan, runner, y, num, den, iters, "jacobi",
                            rho, den_diag, x, False, use_pallas, dict(info))
        x = sub.x
        done += iters
        rounds += iters * deg_den + deg_num
        res = _solve_residual(runner, y, x, num, den)
        rounds += deg_den + deg_num
        residuals.append(res)
        if not np.isfinite(res) or res > _DIVERGENCE_FACTOR * max(best, 1.0):
            diverged = True
            logger.warning(
                "solve[jacobi]: diverged at round %d/%d "
                "(residual %.3e, best %.3e) — stopping early", done, K, res,
                best)
            break
        best = min(best, res)
    info.update(matvecs_per_round=deg_den, exchange_rounds=rounds,
                check_every=check_every, residual=residuals[-1],
                residual_history=tuple(residuals), diverged=diverged,
                rounds_run=done)
    return SolveResult(x=x, method="jacobi", backend=plan.backend,
                       n_iters=done, info=info)


# ---------------------------------------------------------------------------
# Method implementations (each runs inside the backend's matvec_runner)
# ---------------------------------------------------------------------------
def _cheb_partial_sums(mv, x, c, alpha):
    """Chebyshev recurrence recording the order-k partial sums (history)."""
    t0 = x
    acc = 0.5 * c[0] * t0
    t1 = mv(x) / alpha - x
    acc1 = acc + c[1] * t1

    def body(carry, ck):
        t_km1, t_km2, acc = carry
        t_k = (2.0 / alpha) * mv(t_km1) - 2.0 * t_km1 - t_km2
        acc = acc + ck * t_k
        return (t_k, t_km1, acc), acc

    (_, _, acc_f), hist = jax.lax.scan(body, (t1, t0, acc1), c[2:])
    hist = jnp.concatenate([acc1[None], hist], axis=0)
    return acc_f, hist


def _solve_chebyshev(plan, runner, y, num, den, K, history, use_pallas,
                     info):
    """Section-IV truncated Chebyshev approximation of g at order K."""
    from ..kernels import ops as kops

    op = plan.op
    lmax = op.lmax
    if den is not None:
        coeffs = cheb.cheb_coeffs(_rational_callable(num, den), K, lmax)
    else:
        # no rational spec: approximate the plan's own (scalar) multiplier
        if op.eta != 1:
            raise ValueError(
                "solve(method='chebyshev') without a rational spec needs a "
                f"scalar operator (eta == 1); this one has eta={op.eta}. "
                "Pass tau=/num=/den= or use plan.apply for the union.")
        coeffs = (np.asarray(op.coeffs)[0] if K == op.K
                  else cheb.cheb_coeffs(op.multipliers[0], K, lmax,
                                        op.coeff_points))
    alpha = lmax / 2.0

    def fn(mv, yl, c):
        if history:
            x, hist = _cheb_partial_sums(mv, yl, c, alpha)
            return x, hist
        return kops.fused_cheb_recurrence(mv, yl, c, lmax,
                                          use_pallas=use_pallas)[..., 0, :]

    c = jnp.asarray(coeffs, y.dtype)
    info.update(matvecs_per_round=1, exchange_rounds=K, order=K)
    if history:
        x, hist = runner(fn, (y,), (c,))
        return SolveResult(x=x, method="chebyshev", backend=plan.backend,
                           n_iters=K, history=hist, info=info)
    x = runner(fn, (y,), (c,))
    return SolveResult(x=x, method="chebyshev", backend=plan.backend,
                       n_iters=K, info=info)


def _solve_jacobi(plan, runner, y, num, den, K, method, rho, den_diag, x0,
                  history, use_pallas, info):
    """Jacobi (Eq. (24)) / Chebyshev-accelerated Jacobi (Eq. (25)) on
    den(P) x = num(P) y; deg(den) matvecs per round, deg(num) once for the
    right-hand side."""
    op = plan.op
    dd = _resolve_den_diag(op, den, den_diag)
    inv_d = jnp.asarray(1.0 / dd, y.dtype)
    deg_den = len(den) - 1
    deg_num = len(num) - 1
    if method == "cheb_jacobi":
        if rho is None:
            cache = _op_solver_cache(op)
            key = ("rho", den)
            if key not in cache:
                cache[key] = _estimate_rho(op, den, 1.0 / dd)
            rho = cache[key]
            info["rho_estimated"] = True
        rho = float(rho)
        if not 0.0 < rho < 1.0:
            raise ValueError(
                f"cheb_jacobi needs a spectral-radius bound 0 < rho < 1 "
                f"(got {rho:.4f}): the Jacobi split of den(P) diverges — "
                "use method='arma' (Fig. 2(c)'s regime) or a different "
                "splitting")
        info["rho"] = rho
    else:
        # record the estimate for diagnostics but run regardless (plain
        # Jacobi simply diverges when rho >= 1, as Fig. 2(c) shows)
        info["rho"] = float(rho) if rho is not None else None

    info.update(matvecs_per_round=deg_den,
                exchange_rounds=K * deg_den + deg_num)

    signals = [y, inv_d] + ([x0] if x0 is not None else [])

    def fn(mv, yl, inv_dl, *rest):
        from ..kernels import ops as kops

        x0l = rest[0] if rest else None
        b = poly_matvec(mv, num, yl)
        # Single-launch upgrade: a matvec record with its local Block-ELL
        # structure (pallas backend; pallas_halo on a 1-shard mesh) runs
        # the whole Eq. (24)/(25) iteration — deg(den) in-kernel SpMVs +
        # the fused update per round — in ONE jacobi_sweep launch, the
        # weight schedule computed host-side.  History recording needs the
        # per-round iterates in HBM, so it stays on the per-round path.
        if mv.block_ell is not None and not history:
            if K * deg_den > 256:
                # the in-kernel round loop unrolls the Horner chain; past
                # this many SpMVs the trace/compile cost outweighs the
                # launch savings — logged like every other fallback
                obs.count("solve.unroll_fallback")
                logger.info(
                    "solve[%s]: %d rounds x %d matvecs exceeds the "
                    "single-launch unroll budget (256) — running the "
                    "per-round jacobi_step path", method, K, deg_den)
            else:
                ws = (_jacobi.cheb_jacobi_weights(rho, K)
                      if method == "cheb_jacobi"
                      else _jacobi.jacobi_weights(K))
                return kops.fused_jacobi_sweep(
                    mv.block_ell, b, inv_dl, den, ws, x0=x0l,
                    use_pallas=use_pallas, vmem_budget=mv.vmem_budget,
                    scratch_dtype=mv.sweep_dtype)

        a_mv = _poly_matvec_protocol(mv, den)

        if method == "jacobi":
            return _jacobi.jacobi_solve(
                a_mv, None, b, K, x0=x0l, return_history=history,
                inv_diag=inv_dl, use_pallas=use_pallas)
        return _jacobi.jacobi_chebyshev_solve(
            a_mv, None, b, rho, K, x0=x0l, return_history=history,
            inv_diag=inv_dl, use_pallas=use_pallas)

    out = runner(fn, tuple(signals))
    if history:
        x, hist = out
        return SolveResult(x=x, method=method, backend=plan.backend,
                           n_iters=K, history=hist, info=info)
    return SolveResult(x=out, method=method, backend=plan.backend,
                       n_iters=K, info=info)


def _solve_arma(plan, runner, y, num, den, K, poles, residues, const, x0,
                history, info):
    """Parallel ARMA recursion (Eqs. (29)-(30)): poles stacked on a leading
    axis, complex iterate carried as a real [Re, Im] stack — one matvec
    (one neighbour exchange of length-K_p messages) per round."""
    op = plan.op
    lmax = op.lmax
    if x0 is not None:
        raise ValueError(
            "method='arma' carries per-pole internal state; a warm-start "
            "x0 in signal space has no (29)-(30) analog")
    if poles is not None:
        if residues is None:
            raise ValueError("poles= given without residues=")
        p_arr = np.asarray(poles, dtype=np.complex128)
        r_arr = np.asarray(residues, dtype=np.complex128)
        c0 = float(const) if const is not None else 0.0
    else:
        r_arr, p_arr, c0 = _arma.arma_from_rational(num, den, lmax)
        if const is not None:
            c0 = float(const)
    stable = _arma.arma_stable(p_arr, lmax)
    if not stable:
        logger.warning(
            "solve[arma]: |p_k| > lmax/2 fails for some pole "
            "(min |p_k| = %.4f vs lmax/2 = %.4f) — the recursion (30) "
            "will diverge (Section V-D)", float(np.abs(p_arr).min()),
            lmax / 2.0)
    info.update(matvecs_per_round=1, exchange_rounds=K,
                n_poles=int(p_arr.shape[0]), arma_stable=stable,
                arma_const=c0)

    rj = jnp.asarray(r_arr, jnp.complex64)
    pj = jnp.asarray(p_arr, jnp.complex64)

    def fn(mv, yl, rjl, pjl):
        return _arma.arma_apply(mv, yl, rjl, pjl, lmax, n_iters=K,
                                const=c0, return_history=history)

    out = runner(fn, (y,), (rj, pj))
    if history:
        x, hist = out
        return SolveResult(x=x, method="arma", backend=plan.backend,
                           n_iters=K, history=hist, info=info)
    return SolveResult(x=out, method="arma", backend=plan.backend,
                       n_iters=K, info=info)
