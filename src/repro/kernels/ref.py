"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

Array = jax.Array


def block_ell_spmv_ref(blocks: Array, indices: Array, x: Array) -> Array:
    """y = A @ x; blocks (nrb, slots, br, bc), indices (nrb, slots),
    x (..., ncb*bc) with arbitrary leading batch dims. Padded slots must
    hold zero blocks."""
    nrb, slots, br, bc = blocks.shape
    xb = x.reshape(x.shape[:-1] + (-1, bc))
    gathered = jnp.take(xb, indices, axis=-2)  # (..., nrb, slots, bc)
    y = jnp.einsum("rsij,...rsj->...ri", blocks, gathered)
    return y.reshape(x.shape[:-1] + (nrb * br,))


def cheb_fold_ref(terms: Sequence[Array], coef: Array,
                  acc: Optional[Array] = None) -> Array:
    """acc[j] + sum_i coef[j, i] * terms[i]: terms (..., n) each, coef
    (eta, len(terms)), acc (eta, ..., n) or None (a fold with nothing to
    add to); the terms are added in order, as `cheb_step` adds them."""
    for i, t in enumerate(terms):
        term = coef[:, i].reshape((-1,) + (1,) * t.ndim) * t[None]
        acc = term if acc is None else acc + term
    return acc


def cheb_step_ref(pt: Array, t_km1: Array, t_km2: Array,
                  acc: Optional[Array] = None, coef: Optional[Array] = None,
                  *, alpha: float, held: Sequence[Array] = ()):
    """`cheb_step`'s oracle: t_k, or (t_k, fold) when `coef` ((eta,) or
    (eta, len(held) + 1)) is given; pt/t_km1/t_km2/held: (..., n); acc:
    (eta, ..., n) or None."""
    tk = (2.0 / alpha) * pt - 2.0 * t_km1 - t_km2
    if coef is None:
        return tk
    coef = coef.reshape(coef.shape[0], -1)
    return tk, cheb_fold_ref(tuple(held) + (tk,), coef, acc)


#: Above this padded size the sweep oracles keep the gather-based Block-ELL
#: matvec instead of densifying (n^2 memory).
_DENSE_SWEEP_MAX_N = 4096


def block_ell_to_dense(blocks, indices) -> Array:
    """Reassemble the dense (padded_n, padded_n) matrix from Block-ELL.

    Padded slots must hold zero blocks (they scatter zeros).  Used by the
    sweep oracles below: the structure arrays are plan-time constants, so
    for concrete inputs the scatter runs eagerly in numpy at trace time
    and the sweep's matvecs become plain dense products against a literal
    matrix — on CPU several times faster than the per-order gather+einsum,
    which is tuned for the TPU kernel's streaming layout, not for host
    execution."""
    import numpy as np

    nrb, slots, br, bc = blocks.shape
    n = nrb * br
    if not isinstance(blocks, jax.core.Tracer) and \
            not isinstance(indices, jax.core.Tracer):
        bl = np.asarray(blocks)
        ix = np.asarray(indices)
        dense = np.zeros((n, n), bl.dtype)
        for rb in range(nrb):
            for s in range(slots):
                cb = int(ix[rb, s])
                dense[rb * br:(rb + 1) * br, cb * bc:(cb + 1) * bc] += \
                    bl[rb, s]
        return jnp.asarray(dense)
    one_hot = jax.nn.one_hot(indices, n // bc, dtype=blocks.dtype)
    return jnp.einsum("rsij,rsc->ricj", blocks, one_hot).reshape(n, n)


def _sweep_matvec(blocks, indices):
    """The sweep oracles' matvec: dense when small enough, gather otherwise."""
    n = blocks.shape[0] * blocks.shape[2]
    if n <= _DENSE_SWEEP_MAX_N:
        dense = block_ell_to_dense(blocks, indices)
        return lambda v: jnp.einsum("ij,...j->...i", dense, v)
    return lambda v: block_ell_spmv_ref(blocks, indices, v)


def cheb_sweep_ref(blocks: Array, indices: Array, x: Array, coeffs: Array,
                   *, alpha: float) -> Array:
    """Whole K-order recurrence as one fused jnp computation (the
    `cheb_sweep` oracle): the order loop is unrolled host-side (K is
    static), so XLA sees a single straight-line trace with no per-order
    scan machinery, and the matvec densifies at small n
    (:func:`block_ell_to_dense`) — the CPU analog of the single-launch
    kernel.

    x: (..., n) at the Block-ELL padded size; coeffs: (eta, K+1).
    Returns (..., eta, n)."""
    c = jnp.asarray(coeffs, x.dtype)
    mv = _sweep_matvec(blocks, indices)
    K = c.shape[1] - 1
    t0 = x
    acc = 0.5 * c[:, 0:1] * x[..., None, :]
    if K == 0:
        return acc
    t1 = mv(x) / alpha - x
    acc = acc + c[:, 1:2] * t1[..., None, :]
    for k in range(2, K + 1):
        pt = mv(t1)
        tk = (2.0 / alpha) * pt - 2.0 * t1 - t0
        acc = acc + c[:, k:k + 1] * tk[..., None, :]
        t0, t1 = t1, tk
    return acc


def jacobi_sweep_ref(blocks: Array, indices: Array, b: Array, inv_d: Array,
                     weights, x0: Array, *, den) -> Array:
    """Whole (accelerated-)Jacobi solve of den(P) x = b, rounds unrolled
    (the `jacobi_sweep` oracle).  weights: (n_iters, 2) host-side (w_t,
    s_t) schedule; den: monomial coefficients, low-first.  Returns x after
    n_iters rounds, shape broadcast(b, x0)."""
    import numpy as np

    ws = np.asarray(weights, dtype=np.float64)
    mv = _sweep_matvec(blocks, indices)
    x, x_prev = x0, x0
    for t in range(ws.shape[0]):
        h = den[-1] * x
        for c in den[-2::-1]:
            h = mv(h) + c * x
        x_next = jacobi_step_ref(h, x, x_prev, b, inv_d,
                                 w=float(ws[t, 0]), s=float(ws[t, 1]))
        x, x_prev = x_next, x
    return x


def jacobi_step_ref(qx: Array, x: Array, x_prev: Array, y: Array,
                    inv_d: Array, *, w, s) -> Array:
    """One (accelerated-)Jacobi update x_next = w (x + D^{-1}(y - Qx)) - s x_prev.

    qx = Q @ x; all of qx/x/x_prev: (..., n); y/inv_d broadcastable against
    them.  w = 1, s = 0 is the plain Jacobi sweep (Eq. (24)); the
    Chebyshev-accelerated weights of Eq. (25) vary per iteration."""
    return w * (x + inv_d * (y - qx)) - s * x_prev


def ista_shrink_ref(a: Array, phi_y: Array, gram_a: Array, thresh: Array,
                    *, gamma: float) -> Array:
    z = a + gamma * (phi_y - gram_a)
    return jnp.sign(z) * jnp.maximum(jnp.abs(z) - thresh, 0.0)


def attention_ref(q: Array, k: Array, v: Array, *, causal: bool = True,
                  scale: float | None = None) -> Array:
    """Naive softmax attention with GQA; q (B,Hq,Sq,D), k/v (B,Hkv,Sk,D)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    if causal:
        rows = jnp.arange(sq)[:, None]
        cols = jnp.arange(sk)[None, :]
        s = jnp.where(cols <= rows, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return out.astype(q.dtype)
