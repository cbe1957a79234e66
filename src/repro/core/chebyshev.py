"""Shifted Chebyshev polynomial machinery (Section IV of the paper).

Implements:
  * truncated shifted-Chebyshev coefficients c_{j,k} of Eq. (14), computed by
    Chebyshev-Gauss quadrature (exact for integrands of matching degree);
  * the three-term recurrence Eq. (15) as a single `lax.scan` whose body does
    exactly one application of P — the distributed hot loop of Algorithm 1;
  * union application  f -> Phi_tilde f           (Algorithm 1, Eq. (17));
  * adjoint application a -> Phi_tilde^* a         (Algorithm 2, Eq. (19));
  * Gram application    f -> Phi_tilde^* Phi_tilde f  via the Chebyshev
    product-coefficient identity T_k T_k' = (T_{k+k'} + T_{|k-k'|})/2
    (Section IV-C), costing 2K matvecs instead of 2·(K + K·eta);
  * scalar polynomial evaluation for the B(K) bound of Prop. 4.

Conventions follow the paper: a series is represented by coefficients
(c_0, ..., c_K) with   g(x) ~= c_0/2 + sum_{k>=1} c_k Tbar_k(x),
Tbar_k(x) = T_k((x - alpha)/alpha), alpha = lmax/2, on x in [0, lmax].
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .graph import BlockELL

Array = jax.Array
MatVec = Callable[[Array], Array]


@dataclasses.dataclass(frozen=True, eq=False)
class LocalMatvec:
    """A plan's local matvec and what the recurrences may know about it.

    fn: ``x -> y`` applying P along the last axis, or, with `init_state`,
        the dual-signature ``fn(x) -> y`` / ``fn(x, state) -> (y, state)``
        of a matvec that carries state across orders (the int8
        error-feedback exchange, the fault injector's round counter).
    block_ell: the `core.graph.BlockELL` P is, set only where the product
        is purely local (no collectives): the single-launch sweep kernels
        may then run the whole recurrence or solve
        (`kernels.ops.fused_cheb_recurrence`, `dist.solvers`).
    vmem_budget: the sweep kernels' VMEM guard (None: their default).
    sweep_dtype: the sweep kernels' scratch dtype (None: float32).
    init_state: ``x -> state`` for a stateful `fn`, else None.

    Calling the record calls `fn`.
    """

    fn: Callable
    block_ell: Optional[BlockELL] = None
    vmem_budget: Optional[int] = None
    sweep_dtype: Optional[str] = None
    init_state: Optional[Callable] = None

    def __call__(self, x, *state):
        return self.fn(x, *state)


def _stateful_matvec(matvec: MatVec, x: Array):
    """Adapt `matvec` to the dual-signature stateful protocol.

    A :class:`LocalMatvec` with an `init_state` accepts ``matvec(x,
    state) -> (y, state)``; every other matvec keeps its stateless
    signature and gets an empty-state shim, so every recurrence below
    threads state uniformly through its scan carry at zero cost for the
    common case.

    Returns ``(mv2, state0)`` with ``mv2(v, s) -> (y, s')``.
    """
    if isinstance(matvec, LocalMatvec) and matvec.init_state is not None:
        return matvec, matvec.init_state(x)
    return (lambda v, s: (matvec(v), s)), ()


# ---------------------------------------------------------------------------
# Coefficients — Eq. (14)
# ---------------------------------------------------------------------------
def cheb_coeffs(
    g: Callable[[np.ndarray], np.ndarray],
    K: int,
    lmax: float,
    n_points: int = 1000,
    dtype=np.float64,
) -> np.ndarray:
    """Truncated shifted-Chebyshev coefficients of `g` on [0, lmax].

    c_k = (2/pi) * integral_0^pi cos(k phi) g(alpha (cos phi + 1)) dphi,
    evaluated with the midpoint rule at Chebyshev angles (equivalently,
    Chebyshev-Gauss quadrature), which converges spectrally for smooth g.

    Returns shape (K+1,) float array in the paper's half-c0 convention.
    """
    alpha = lmax / 2.0
    m = np.arange(n_points, dtype=dtype)
    phi = np.pi * (m + 0.5) / n_points
    vals = np.asarray(g(alpha * (np.cos(phi) + 1.0)), dtype=dtype)
    ks = np.arange(K + 1, dtype=dtype)[:, None]
    c = (2.0 / n_points) * np.sum(np.cos(ks * phi[None, :]) * vals[None, :], axis=1)
    return c.astype(dtype)


def cheb_coeffs_stack(
    gs: Sequence[Callable[[np.ndarray], np.ndarray]],
    K: int,
    lmax: float,
    n_points: int = 1000,
) -> np.ndarray:
    """Coefficients for a union of multipliers; shape (eta, K+1)."""
    return np.stack([cheb_coeffs(g, K, lmax, n_points) for g in gs], axis=0)


# ---------------------------------------------------------------------------
# Scalar polynomial evaluation (for bounds / tests)
# ---------------------------------------------------------------------------
def cheb_eval(coeffs: Union[Array, np.ndarray], x, lmax: float):
    """Evaluate the truncated series at scalar/array abscissae x in [0,lmax].

    coeffs: (K+1,) or (eta, K+1). Returns x.shape or (eta,) + x.shape.
    """
    c = jnp.atleast_2d(jnp.asarray(coeffs))
    x = jnp.asarray(x)
    alpha = lmax / 2.0
    y = (x - alpha) / alpha  # in [-1, 1]
    K = c.shape[1] - 1
    t_km2 = jnp.ones_like(y)
    acc = 0.5 * c[:, 0][(...,) + (None,) * y.ndim] * t_km2
    if K >= 1:
        t_km1 = y
        acc = acc + c[:, 1][(...,) + (None,) * y.ndim] * t_km1
        for k in range(2, K + 1):
            t_k = 2.0 * y * t_km1 - t_km2
            acc = acc + c[:, k][(...,) + (None,) * y.ndim] * t_k
            t_km2, t_km1 = t_km1, t_k
    if jnp.asarray(coeffs).ndim == 1:
        return acc[0]
    return acc


def approx_error_bound(
    gs: Sequence[Callable],
    coeffs: np.ndarray,
    lmax: float,
    n_grid: int = 4000,
) -> float:
    """B(K) of Prop. 4 Eq. (20): max_j sup_{lambda in [0,lmax]} |g_j - p_j^K|.

    Estimated on a dense grid (the paper's bound is a sup over the continuous
    interval; a 4000-point grid is what the reference MATLAB code uses).
    """
    lam = np.linspace(0.0, lmax, n_grid)
    worst = 0.0
    approx = np.asarray(cheb_eval(np.asarray(coeffs), jnp.asarray(lam), lmax))
    approx = np.atleast_2d(approx)
    for j, g in enumerate(gs):
        exact = np.asarray(g(lam))
        worst = max(worst, float(np.max(np.abs(exact - approx[j]))))
    return worst


# ---------------------------------------------------------------------------
# Operator application — Algorithm 1 / Eq. (17)
# ---------------------------------------------------------------------------
def _outer(c: Array, t: Array) -> Array:
    """(eta,) x (..., N) -> (..., eta, N): per-multiplier scaled copies,
    the eta axis inserted before the vertex axis."""
    return c[:, None] * t[..., None, :]


def cheb_apply(
    matvec: MatVec,
    x: Array,
    coeffs: Union[Array, np.ndarray],
    lmax: float,
) -> Array:
    """Compute Phi_tilde x for a union of multipliers given by `coeffs`.

    matvec: linear map applying P along the *last* axis of its argument,
            broadcasting over any leading batch dims ((N,) and (..., N)
            inputs both work).
    x: (..., N) — leading axes are batch signals riding the same recurrence.
    coeffs: (K+1,) single multiplier or (eta, K+1) union.
    Returns (..., N) (single) or (..., eta, N) (union).

    The body performs exactly one matvec per Chebyshev order — the same
    communication/computation structure as Algorithm 1 lines 6-10 — and the
    recurrence is linear, so every batch signal shares the K rounds
    (Section III-D's shared-rounds trick generalized to arbitrary batches).
    """
    single = jnp.asarray(coeffs).ndim == 1
    c = jnp.atleast_2d(jnp.asarray(coeffs, dtype=x.dtype))
    K = c.shape[1] - 1
    alpha = lmax / 2.0

    t0 = x
    acc = _outer(0.5 * c[:, 0], t0)
    if K == 0:
        return acc[..., 0, :] if single else acc

    mv2, st = _stateful_matvec(matvec, x)
    # Tbar_1(P) x = (P x)/alpha - x     (Algorithm 1 line 5)
    px, st = mv2(x, st)
    t1 = px / alpha - x
    acc = acc + _outer(c[:, 1], t1)

    if K >= 2:
        def body(carry, ck):
            t_km1, t_km2, acc, st = carry
            # Tbar_k = (2/alpha) P t_{k-1} - 2 t_{k-1} - t_{k-2}   (line 9)
            pt, st = mv2(t_km1, st)
            t_k = (2.0 / alpha) * pt - 2.0 * t_km1 - t_km2
            acc = acc + _outer(ck, t_k)
            return (t_k, t_km1, acc, st), None

        (_, _, acc, _), _ = jax.lax.scan(body, (t1, t0, acc, st),
                                         c[:, 2:].T)
    return acc[..., 0, :] if single else acc


def cheb_apply_adjoint(
    matvec: MatVec,
    a: Array,
    coeffs: Union[Array, np.ndarray],
    lmax: float,
    matvec_batched: MatVec = None,
) -> Array:
    """Compute Phi_tilde^* a per Eq. (19) / Algorithm 2.

    a: (..., eta, N) stacked coefficient signals a_j (eta on axis -2,
    leading axes are batch).
    coeffs: (eta, K+1).
    Returns (..., N). Each Chebyshev order applies P to all eta streams (and
    all batch signals) at once — the paper's length-eta messages.

    matvec_batched: deprecated alias kept for old callers; `matvec` itself
    must broadcast over leading dims under the (..., N) contract, so the
    eta axis needs no special handling.  If given, it is used instead of
    `matvec`.
    """
    c = jnp.asarray(coeffs, dtype=a.dtype)
    assert c.ndim == 2 and a.shape[-2] == c.shape[0], "eta mismatch"
    K = c.shape[1] - 1
    alpha = lmax / 2.0
    mv = matvec_batched if matvec_batched is not None else matvec

    def combine(ck: Array, t: Array) -> Array:
        # sum_j ck[j] * t[..., j, :]
        return jnp.einsum("j,...jn->...n", ck, t)

    t0 = a
    acc = combine(0.5 * c[:, 0], t0)
    if K == 0:
        return acc
    mv2, st = _stateful_matvec(mv, a)
    pa, st = mv2(a, st)
    t1 = pa / alpha - a
    acc = acc + combine(c[:, 1], t1)
    if K >= 2:
        def body(carry, ck):
            t_km1, t_km2, acc, st = carry
            pt, st = mv2(t_km1, st)
            t_k = (2.0 / alpha) * pt - 2.0 * t_km1 - t_km2
            return (t_k, t_km1, acc + combine(ck, t_k), st), None

        (_, _, acc, _), _ = jax.lax.scan(body, (t1, t0, acc, st),
                                         c[:, 2:].T)
    return acc


# ---------------------------------------------------------------------------
# Product / Gram coefficients — Section IV-C
# ---------------------------------------------------------------------------
def cheb_product_coeffs(c1: np.ndarray, c2: np.ndarray) -> np.ndarray:
    """Coefficients of the product of two truncated series (paper convention).

    Uses T_j T_k = (T_{j+k} + T_{|j-k|}) / 2. If c1 has degree K1 and c2 has
    degree K2, the product has degree K1+K2 and shape (K1+K2+1,).
    """
    a = np.array(c1, dtype=np.float64).copy()
    b = np.array(c2, dtype=np.float64).copy()
    a[0] *= 0.5  # convert half-c0 convention -> plain coefficients
    b[0] *= 0.5
    K1, K2 = len(a) - 1, len(b) - 1
    out = np.zeros(K1 + K2 + 1, dtype=np.float64)
    for j in range(K1 + 1):
        if a[j] == 0.0:
            continue
        for k in range(K2 + 1):
            v = 0.5 * a[j] * b[k]
            if v == 0.0:
                continue
            out[j + k] += v
            out[abs(j - k)] += v
    out[0] *= 2.0  # back to half-c0 convention
    return out


def gram_coeffs(coeffs: np.ndarray) -> np.ndarray:
    """d_k such that Phi_tilde^* Phi_tilde = d0/2 + sum_k d_k Tbar_k(P).

    coeffs: (eta, K+1). Returns (2K+1,). See Section IV-C: this lets
    Phi*Phi f be computed with 2K matvecs (4K|E| messages) instead of
    sequential adjoint-after-forward.
    """
    coeffs = np.atleast_2d(np.asarray(coeffs, dtype=np.float64))
    K = coeffs.shape[1] - 1
    d = np.zeros(2 * K + 1, dtype=np.float64)
    for j in range(coeffs.shape[0]):
        d += cheb_product_coeffs(coeffs[j], coeffs[j])
    return d


def cheb_apply_gram(
    matvec: MatVec,
    x: Array,
    coeffs: np.ndarray,
    lmax: float,
) -> Array:
    """Phi_tilde^* Phi_tilde x via the product coefficients (Section IV-C).

    x: (..., N) -> (..., N); batch signals share the 2K exchange rounds."""
    d = gram_coeffs(coeffs)
    return cheb_apply(matvec, x, jnp.asarray(d, dtype=x.dtype), lmax)
