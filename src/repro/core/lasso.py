"""Distributed lasso / wavelet denoising — Section VI, Algorithm 3.

Iterative soft thresholding (ISTA, Eq. (32)) over the Chebyshev-approximated
spectral graph wavelet frame Phi_tilde:

    argmin_a  (1/2) || y - Phi~* a ||_2^2 + || a ||_{1, mu}        (33)

Each iteration needs Phi~ y (computed once, Algorithm 1) and
Phi~ Phi~* a^{(beta-1)} (Algorithm 2 then Algorithm 1). The step size must
satisfy gamma < 2 / ||Phi~||_2^2 for convergence [58].
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .multiplier import UnionMultiplier

Array = jax.Array


def soft_threshold(z: Array, thresh: Array) -> Array:
    """S_t(z) = 0 if |z| <= t else z - sgn(z) t   (shrinkage operator)."""
    return jnp.sign(z) * jnp.maximum(jnp.abs(z) - thresh, 0.0)


def lasso_objective(op: UnionMultiplier, y: Array, a: Array, mu: Array) -> Array:
    """Eq. (33) objective; for batched y/a the objectives are summed over
    the batch (each signal's problem is separable, so the sum is what the
    batched ISTA minimizes)."""
    resid = y - op.apply_adjoint(a)
    return 0.5 * jnp.sum(resid * resid) + jnp.sum(mu * jnp.abs(a))


@dataclasses.dataclass
class LassoResult:
    coeffs: Array       # a_*, shape (..., eta, N) — leading batch dims of y
    signal: Array       # Phi~* a_*, shape (..., N)
    objective: Array    # objective value per recorded iteration
    n_iters: int
    fused: bool = False  # True iff a backend's in-shard_map ISTA ran


def _mu_threshold(mu: Union[float, Array], eta: int, dtype, gamma: float,
                  n: Optional[int] = None) -> Array:
    """Shrinkage threshold mu*gamma broadcastable against a (..., eta, N).

    mu: scalar (shared), (eta,) per-scale (the paper's 0.01 / 0.75 split),
    (..., eta) per-signal-per-scale for batched solves, or — when the
    vertex count `n` is given — (..., eta, N) per-vertex weights.  When
    ``n == eta`` an (eta, n)-shaped mu is read as per-vertex (the
    pre-batch meaning of a 2-D mu).
    """
    mu_arr = jnp.asarray(mu, dtype=dtype)
    if mu_arr.ndim == 0:
        mu_arr = jnp.full((eta,), mu_arr)
    if (n is not None and mu_arr.ndim >= 2
            and mu_arr.shape[-1] == n and mu_arr.shape[-2] == eta):
        return mu_arr * gamma  # per-vertex: already (..., eta, N)
    if mu_arr.shape[-1] != eta:
        per_vertex_hint = (
            f", or (..., eta, N) with N={n} for per-vertex weights"
            if n is not None else
            "; per-vertex (..., eta, N) weights are not supported on this "
            "(fused/padded) path — use the generic ISTA loop")
        raise ValueError(
            f"mu trailing axis must be eta={eta}{per_vertex_hint}; "
            f"got shape {mu_arr.shape}")
    return mu_arr[..., None] * gamma


def distributed_lasso(
    op: UnionMultiplier,
    y: Array,
    mu: Union[float, Array],
    gamma: float = 0.2,
    n_iters: int = 300,
    a0: Optional[Array] = None,
    record_objective: bool = False,
    soft_threshold_fn: Callable = soft_threshold,
    backend: Optional[str] = None,
    mesh=None,
) -> LassoResult:
    """Algorithm 3. `y` may be a single (N,) signal or a batched (..., N)
    stack — every signal rides the same Chebyshev exchange rounds (the
    recurrence is linear).  `mu` may be a scalar, an (eta,)-vector
    (per-scale weights, as in the paper: 0.01 for scaling coefficients,
    0.75 for wavelets), a per-signal (..., eta) array for batched y, or a
    per-vertex (..., eta, N) array (the fused backend paths support the
    first three; per-vertex weights run the generic loop here).

    `op` may be a UnionMultiplier/GraphOperator or an already-built
    ExecutionPlan; passing `backend=` (plus `mesh=` for sharded backends)
    plans the operator here — `backend="halo"` runs the whole ISTA loop
    inside one shard_map (`repro.dist.operator.plan_from_form`).

    The whole ISTA loop is a single lax.scan whose body applies
    Phi~ Phi~* (2*K matvecs via Algorithms 2+1) plus local shrinkage — the
    same structure a real sensor network would execute.
    """
    if backend is not None:
        plan = op.plan(backend, mesh=mesh)
        # the fused (in-shard_map) path supports none of the loop knobs —
        # fall through to the generic ISTA over the plan if any is set
        if (plan.solve_lasso_fn is not None and a0 is None
                and not record_objective
                and soft_threshold_fn is soft_threshold):
            return plan.solve_lasso(y, mu, gamma=gamma, n_iters=n_iters)
        op = plan
    thresh = _mu_threshold(mu, op.eta, y.dtype, gamma, n=y.shape[-1])

    phi_y = op.apply(y)  # Algorithm 3 line 3 (stored); (..., eta, N)
    a = jnp.zeros_like(phi_y) if a0 is None else a0

    def body(a, _):
        # line 5: Phi~ Phi~* a    (Algorithm 2 then Algorithm 1)
        gram_a = op.apply(op.apply_adjoint(a))
        a_new = soft_threshold_fn(a + gamma * (phi_y - gram_a), thresh)
        obj = (lasso_objective(op, y, a_new, thresh / gamma)
               if record_objective else jnp.nan)
        return a_new, obj

    a_final, objs = jax.lax.scan(body, a, None, length=n_iters)
    signal = op.apply_adjoint(a_final)  # line 14
    return LassoResult(coeffs=a_final, signal=signal, objective=objs,
                       n_iters=n_iters)


def distributed_lasso_masked(
    op: UnionMultiplier,
    y: Array,
    mask: Array,
    mu: Union[float, Array],
    gamma: float = 0.2,
    n_iters: int = 150,
) -> LassoResult:
    """Algorithm 3 with a vertex observation mask M (data term
    ||M(y - Phi~* a)||^2/2): the ISTA gradient picks up M elementwise —
    still fully local, used by the cross-validation below."""
    thresh = _mu_threshold(mu, op.eta, y.dtype, gamma, n=y.shape[-1])
    m = mask.astype(y.dtype)
    phi_my = op.apply(m * y)

    def body(a, _):
        resid = m * op.apply_adjoint(a)
        a_new = soft_threshold(a + gamma * (phi_my - op.apply(resid)), thresh)
        return a_new, None

    a0 = jnp.zeros_like(phi_my)
    a_star, _ = jax.lax.scan(body, a0, None, length=n_iters)
    return LassoResult(coeffs=a_star, signal=op.apply_adjoint(a_star),
                       objective=jnp.nan, n_iters=n_iters)


def lasso_cross_validate(
    op: UnionMultiplier,
    y: Array,
    mu_grid,
    key: Array,
    holdout_frac: float = 0.2,
    n_folds: int = 3,
    gamma: float = 0.2,
    n_iters: int = 120,
):
    """Distributed cross-validation of the lasso weights mu (the optional
    extension the paper points to in Section VI / refs [29,30]).

    Random vertex subsets are held out; each candidate mu is fit on the
    observed vertices (masked ISTA) and scored by MSE on the held-out ones
    (both computable with the same local message passing). Returns
    (best_mu, scores).
    """
    n = y.shape[0]
    scores = []
    for mu in mu_grid:
        fold_mse = []
        for fold in range(n_folds):
            key, sub = jax.random.split(key)
            held = jax.random.uniform(sub, (n,)) < holdout_frac
            res = distributed_lasso_masked(op, y, ~held, mu, gamma=gamma,
                                           n_iters=n_iters)
            err = (res.signal - y) * held.astype(y.dtype)
            fold_mse.append(float(jnp.sum(err * err)
                                  / jnp.maximum(jnp.sum(held), 1)))
        scores.append(sum(fold_mse) / n_folds)
    best = int(np.argmin(scores))
    return mu_grid[best], scores


def ista_step_size(op: UnionMultiplier, safety: float = 0.9) -> float:
    """gamma < 2/||Phi~||^2; we bound ||Phi~||^2 <= max_lambda sum_j p_j(lambda)^2
    on a dense grid (B(K)-style estimate)."""
    from .chebyshev import cheb_eval

    lam = np.linspace(0.0, op.lmax, 4000)
    vals = np.asarray(cheb_eval(np.asarray(op.coeffs), jnp.asarray(lam), op.lmax))
    frame = np.max(np.sum(vals**2, axis=0))
    return float(safety * 2.0 / max(frame, 1e-12))
