"""Chebyshev gossip consensus on the device ring (the paper's Algorithm 1
with P = the ring-graph Laplacian and the devices as vertices).

The n-device ring Laplacian L_ring has eigenvalues
``lambda_k = 2 - 2 cos(2 pi k / n)`` with the constant vector spanning the
nullspace.  A polynomial p with ``p(0) = 1`` and ``p(lambda_k) = 0`` on
every distinct non-zero eigenvalue therefore satisfies
``p(L_ring) = (1/n) 11^T`` — *finite-time* average consensus after
``K = ceil(n/2)`` neighbour exchange rounds, each round being exactly the
per-order message exchange of Algorithm 1.  For smaller budgets
``K < ceil(n/2)`` the coefficients solve the constrained least-squares
problem (minimise the residual on the non-zero spectrum subject to
p(0) = 1), giving graceful approximate consensus.

Degradation paths (refs [31]-style robustness):
  * ``quantize=True`` — messages ship as REAL int8 wire buffers
    (``repro.dist.quantize`` codec: per-row scale bitcast-packed into the
    payload, ``h + 4`` bytes per h-element row vs ``4h`` for f32 — a
    ``4h/(h+4)`` ~= 4x traffic reduction for large rows; consensus error
    grows to ~the quantization noise floor);
  * ``fault_spec=`` — the SAME seeded link-fault model the sharded halo
    backends use (:mod:`repro.dist.faults`): per-(round, link) Bernoulli
    drop/stale plus bit-noise on quantized wires, with a
    ``degradation=`` policy (``"zero_fill"`` | ``"hold_last"``) for
    dropped deliveries.  Ring link ids match the banded halo convention
    (0 = from-left, 1 = from-right), so one ``FaultSpec`` replays the
    identical fault trace on a filter plan and on the gossip ring.
  * ``drop_left`` / ``drop_right`` — compat shim for the original
    deterministic lost-link model: a device ignores its incoming link
    and substitutes its own state (the ring degrades to a path graph,
    consensus stays bounded).  Kept for callers that want a *static*
    per-device link disable; probabilistic faults should use
    ``fault_spec``.

Usage — gradient averaging without a fabric all-reduce (what
``repro.launch.train --dp-mode gossip`` does)::

    coeffs = consensus_coeffs(mesh.shape["data"])     # host-side, once

    @partial(jax.shard_map, mesh=mesh, ...)
    def step(batch, params):
        grads = ...                                    # per-device grads
        return gossip_mean_tree(grads, "data", coeffs) # ~= all-reduce mean

Communication per call: ``K = ceil(n/2)`` neighbour-exchange rounds of the
full payload per direction (measure with :mod:`repro.dist.commstats`);
``consensus_error(n, coeffs)`` bounds the distance from the true mean.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import chebyshev as cheb
from . import faults
from . import quantize as q

Array = jax.Array

#: The ring Laplacian spectrum lives in [0, 4] for every n.
RING_LMAX = 4.0


# ---------------------------------------------------------------------------
# Coefficients
# ---------------------------------------------------------------------------
def ring_eigenvalues(n: int) -> np.ndarray:
    """Distinct eigenvalues of the n-ring Laplacian, ascending (0 first)."""
    ks = np.arange(n // 2 + 1)
    return 2.0 - 2.0 * np.cos(2.0 * np.pi * ks / n)


def _cheb_rows(lam: np.ndarray, K: int) -> np.ndarray:
    """Rows of shifted-Chebyshev basis values (half-c0 convention) at lam."""
    alpha = RING_LMAX / 2.0
    y = (np.asarray(lam, np.float64) - alpha) / alpha
    rows = np.zeros((len(y), K + 1))
    t_km2 = np.ones_like(y)
    rows[:, 0] = 0.5 * t_km2
    if K >= 1:
        t_km1 = y.copy()
        rows[:, 1] = t_km1
        for k in range(2, K + 1):
            t_k = 2.0 * y * t_km1 - t_km2
            rows[:, k] = t_k
            t_km2, t_km1 = t_km1, t_k
    return rows


def consensus_coeffs(n: int, K: Optional[int] = None) -> np.ndarray:
    """Chebyshev coefficients of the degree-K ring-consensus polynomial.

    Default ``K = ceil(n/2)`` hits every distinct non-zero ring eigenvalue
    -> exact (finite-time) consensus.  Smaller K returns the constrained
    least-squares polynomial: p(0) = 1 exactly, residual minimised on the
    non-zero spectrum.  Shape (K+1,), float64, half-c0 convention (as
    consumed by :func:`repro.core.chebyshev.cheb_apply`).
    """
    if K is None:
        K = int(np.ceil(n / 2))
    lam = ring_eigenvalues(n)
    rows = _cheb_rows(lam, K)
    t0, t_nz = rows[0], rows[1:]
    # constrained LS via the nullspace of the p(0)=1 constraint row
    c_part = t0 / float(t0 @ t0)
    _, _, vt = np.linalg.svd(t0[None, :])
    null = vt[1:].T  # (K+1, K)
    z, *_ = np.linalg.lstsq(t_nz @ null, -t_nz @ c_part, rcond=None)
    return c_part + null @ z


def consensus_error(n: int, coeffs: Union[np.ndarray, Sequence[float]]) -> float:
    """Worst-case consensus defect of p on the n-ring spectrum.

    ``max(|p(0) - 1|, max_{k != 0} |p(lambda_k)|)`` — the operator-norm
    distance between p(L_ring) and the averaging projector.
    """
    coeffs = np.asarray(coeffs, np.float64)
    lam = ring_eigenvalues(n)
    vals = _cheb_rows(lam, len(coeffs) - 1) @ coeffs
    err0 = abs(vals[0] - 1.0)
    err_nz = float(np.max(np.abs(vals[1:]))) if len(lam) > 1 else 0.0
    return float(max(err0, err_nz))


# ---------------------------------------------------------------------------
# On-device gossip (runs inside shard_map)
# ---------------------------------------------------------------------------
def quantize_message(x: Array, bits: int = 8) -> Array:
    """Encode a gossip message as a REAL int8 wire buffer.

    Delegates to the shared halo codec (:func:`repro.dist.quantize.encode`):
    per-last-axis-row max-abs scale, 127 signed levels, the f32 scale
    bitcast-packed into the trailing 4 bytes of the int8 payload — so the
    ppermute'd array really is ``h + 4`` bytes per h-element row (vs ``4h``
    for the f32 payload), and :mod:`repro.dist.commstats` counts the
    shrunken traffic automatically.  Decode with :func:`dequantize_message`.
    All-zero rows pass through unchanged (scale clamps to 1).  Only the
    int8 wire format is implemented; other widths raise.
    """
    if bits != 8:
        raise ValueError(f"only bits=8 (int8 wire) is supported, got {bits}")
    return q.encode(x, "int8")


def dequantize_message(wire: Array, out_dtype=jnp.float32) -> Array:
    """Decode an int8 wire buffer from :func:`quantize_message`."""
    return q.decode(wire, "int8", out_dtype)


def _ring_matvec(axis: str, *, quantize: bool = False,
                 drop_left=False, drop_right=False,
                 fault_spec=None, degradation: str = "zero_fill"):
    """L_ring matvec: one left + one right neighbour exchange per call.

    With an active `fault_spec` the matvec is stateful (the shared
    :mod:`repro.dist.faults` protocol: round counter + carried tiles
    threaded by `cheb_apply`); otherwise the original stateless closure
    is returned, bitwise-identical to the pre-faults trace.
    """
    size = jax.lax.axis_size(axis)
    inj = faults.make_injector(fault_spec, degradation, axis,
                               exchanging=size > 1)
    wire_dtype = "int8" if quantize else "f32"

    def _exchange(x: Array):
        msg = quantize_message(x) if quantize else x
        if size > 1:
            from_left = jax.lax.ppermute(
                msg, axis, perm=[(i, (i + 1) % size) for i in range(size)])
            from_right = jax.lax.ppermute(
                msg, axis, perm=[(i, (i - 1) % size) for i in range(size)])
        else:
            from_left = from_right = msg
        return from_left, from_right

    def _finish(x, from_left, from_right):
        # straggler mitigation: a dropped link substitutes local state,
        # degrading the ring to a path graph (still PSD, still consensus-
        # preserving on the constant component).
        from_left = jnp.where(drop_left, x, from_left)
        from_right = jnp.where(drop_right, x, from_right)
        return 2.0 * x - from_left - from_right

    if inj is None:
        def mv(x: Array) -> Array:
            from_left, from_right = _exchange(x)
            if quantize:
                from_left = dequantize_message(from_left, x.dtype)
                from_right = dequantize_message(from_right, x.dtype)
            return _finish(x, from_left, from_right)

        return mv

    def mv(x: Array, state):  # type: ignore[misc]
        k, (c_l, c_r) = state
        from_left, from_right = _exchange(x)
        from_left = inj.wire(from_left, k, 0, wire_dtype)
        from_right = inj.wire(from_right, k, 1, wire_dtype)
        if quantize:
            from_left = dequantize_message(from_left, x.dtype)
            from_right = dequantize_message(from_right, x.dtype)
        from_left, c_l = inj.recv(from_left, c_l, k, 0)
        from_right, c_r = inj.recv(from_right, c_r, k, 1)
        return _finish(x, from_left, from_right), (k + 1, (c_l, c_r))

    def init_state(x):
        return (inj.init_round(), inj.init_carried((x, x)))

    return cheb.LocalMatvec(mv, init_state=init_state)


def gossip_mean(x: Array, axis: str, coeffs, *, quantize: bool = False,
                drop_left=False, drop_right=False,
                fault_spec=None, degradation: str = "zero_fill") -> Array:
    """Approximate per-component mean over the `axis` device ring.

    Must be called inside a shard_map over `axis`; `x` is the local block
    (any shape) and the return value has the same shape, each entry
    replaced by (approximately) the across-devices mean.  With the default
    full-order coefficients the consensus is exact to float32.

    `fault_spec` / `degradation` inject the shared
    :mod:`repro.dist.faults` link-fault model into the ring exchange
    (None or an all-zero spec = the untouched clean path).
    """
    mv = _ring_matvec(axis, quantize=quantize,
                      drop_left=drop_left, drop_right=drop_right,
                      fault_spec=fault_spec, degradation=degradation)
    c = jnp.asarray(np.asarray(coeffs), x.dtype)
    x = jnp.asarray(x)
    if x.ndim == 0:
        # cheb_apply's (..., N) contract needs a trailing axis; the ring
        # "graph" lives on the device axis, so a scalar leaf is a 1-vector
        return cheb.cheb_apply(mv, x[None], c, RING_LMAX)[0]
    return cheb.cheb_apply(mv, x, c, RING_LMAX)


def gossip_mean_tree(tree, axis: str, coeffs, *, quantize: bool = False,
                     fault_spec=None, degradation: str = "zero_fill"):
    """:func:`gossip_mean` mapped over a pytree of same-sharded leaves.

    The gradient-consensus entry point used by ``repro.launch.train
    --dp-mode gossip``: every leaf is averaged over the `axis` device ring
    independently (one Chebyshev recurrence per leaf).  Must be called
    inside a shard_map over `axis`, like :func:`gossip_mean`.
    """
    return jax.tree_util.tree_map(
        lambda leaf: gossip_mean(leaf, axis, coeffs, quantize=quantize,
                                 fault_spec=fault_spec,
                                 degradation=degradation), tree)
