"""Public jit'd wrappers around the Pallas kernels.

This module is the single dispatch point between the Pallas TPU kernels
(`bcsr_spmv.block_ell_spmv_batched`, `cheb_step.cheb_step`, ...) and
their pure-jnp oracles in :mod:`repro.kernels.ref`.  Everything above it — the `pallas`
and `pallas_halo` execution backends, the benchmarks, the tests — calls
these wrappers and never touches `pallas_call` directly.

Dispatch policy: on TPU the Pallas kernels run natively (the default);
on CPU `use_pallas=True` runs them under interpret=True (the kernel body
executed in Python — used by the kernel test sweeps), and the default
takes the pure-jnp reference path so tests and CPU count runs stay fast.

SpMV dispatch: :func:`spmv` takes the band-windowed kernel
(`bcsr_spmv.block_ell_spmv_window`) when the structure's band is known
(`BlockELL.band`, set at plan build) and the window fits
:data:`DEFAULT_SPMV_WINDOW_VMEM_BUDGET` at the batch's lane width, else
the gather kernel (`bcsr_spmv.block_ell_spmv_batched`); each choice is
counted (``spmv.window`` / ``spmv.gather``) at trace time.

Sharded use: :func:`fused_cheb_recurrence` is the matvec-generic form of the
fused recurrence.  The `pallas_halo` backend calls it *inside* a shard_map
with a halo-exchanging matvec over the per-shard Block-ELL tiles, so the
same fused Chebyshev-step kernel serves both the single-device and the
sharded hot path (per-shard sizes need not be 128-multiples — `cheb_step`
tiles a ``cdiv`` grid and masks the ragged edge).

Single-launch sweep dispatch: when the matvec is a *local* Block-ELL
product (no collectives — the `pallas` backend always, `pallas_halo` on a
1-shard mesh), the backend's `core.chebyshev.LocalMatvec` record carries
its `block_ell` and :func:`fused_cheb_recurrence` upgrades the whole K-order loop to the
persistent `cheb_sweep` kernel: one launch, iterates pinned in VMEM
across all orders.  The upgrade is guarded by the VMEM footprint model
:func:`cheb_sweep_vmem_bytes` — oversized problems fall back to the
per-order path, logged at INFO (see docs/ARCHITECTURE.md "Perf
accounting").
"""
from __future__ import annotations

import logging
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.chebyshev import LocalMatvec
from ..core.graph import BlockELL
from . import ref
from .bcsr_spmv import (SMEM_INDEX_WORDS, block_ell_spmv_batched,
                        block_ell_spmv_window, window_buffers, window_starts)
from .cheb_step import cheb_step
from .cheb_sweep import (SCRATCH_DTYPES, cheb_sweep, cheb_sweep_buffers,
                         jacobi_sweep, jacobi_sweep_buffers)
from .jacobi_step import jacobi_step
from .flash_attention import flash_attention as _flash
from .layout import lane_pad, vmem_bytes
from .soft_threshold import ista_shrink

Array = jax.Array

logger = logging.getLogger(__name__)

#: Default VMEM budget for the single-launch sweep kernels: the compiler's
#: default scoped-VMEM limit (16 MiB on v5e) minus headroom for its own
#: buffers, against the tiled footprint of `cheb_sweep_vmem_bytes`.
DEFAULT_SWEEP_VMEM_BUDGET = 12 * 1024 * 1024

#: VMEM budget of the band-windowed SpMV (`bcsr_spmv.block_ell_spmv_window`):
#: the same headroom under the default scoped-VMEM limit, against the
#: tiled footprint of `bcsr_spmv.window_buffers`.
DEFAULT_SPMV_WINDOW_VMEM_BUDGET = 12 * 1024 * 1024

#: Row blocks per group the windowed SpMV tries, largest first: the
#: largest whose buffers fit the budget.  The window's overlap with its
#: neighbours (2 * band column blocks) is read again by each group, so
#: larger groups read less of x twice.
SPMV_WINDOW_ROWS = (128, 64, 32, 16)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(use_pallas: Optional[bool]):
    """Returns (use_pallas, interpret)."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    return use_pallas, (use_pallas and not _on_tpu())


def spmv(A: BlockELL, x: Array, use_pallas: Optional[bool] = None) -> Array:
    """Block-ELL y = A @ x on padded signals (..., padded_n).

    The Algorithm-1 hot loop: one call per Chebyshev order, cost
    proportional to the number of non-zero blocks (the paper's O(|E|)
    per-order cost).  Leading batch dims ride one sweep of the sparsity
    structure (`block_ell_spmv_batched`: each Block-ELL block is loaded
    once for the whole batch, not once per signal).  `x`'s last axis must
    already be at `A.padded_n`; use `fused_cheb_apply` / the `pallas`
    backend if you want padding handled for you.
    """
    use, interp = _resolve(use_pallas)
    with obs.scope("spmv"):
        if use:
            rows = spmv_window_rows(A, x)
            if rows is not None:
                obs.count("spmv.window")
                return block_ell_spmv_window(A.panels, A.indices, x,
                                             band=A.band, rows=rows,
                                             interpret=interp)
            obs.count("spmv.gather")
            return block_ell_spmv_batched(A.panels, A.indices, x,
                                          interpret=interp)
        return ref.block_ell_spmv_ref(A.blocks, A.indices, x)


def spmv_window_rows(A: BlockELL, x) -> Optional[int]:
    """Row blocks per group for the band-windowed SpMV of A over the
    signals `x` (an array or shape-dtype), or None for the gather path.

    The window needs a known band (`BlockELL.band`); then the largest
    group of :data:`SPMV_WINDOW_ROWS` (at most the structure's row
    blocks) whose two x windows, panels and output blocks fit
    :data:`DEFAULT_SPMV_WINDOW_VMEM_BUDGET` at the batch's lane width and
    dtype, and whose window starts and two index blocks fit the SMEM
    words of `bcsr_spmv.SMEM_INDEX_WORDS`.
    """
    if A.band is None:
        return None
    nrb, br, width = A.panels.shape
    slots = A.indices.shape[-1]
    bc = width // slots
    lanes = lane_pad(max(1, int(np.prod(x.shape[:-1]))))
    for rows in SPMV_WINDOW_ROWS:
        rows = min(rows, nrb)
        starts, span = window_starts(nrb, br, bc, A.band, rows)
        vmem = vmem_bytes(window_buffers(rows, span, (br, bc), slots, lanes,
                                         x.dtype, A.panels.dtype))
        smem = starts.size + 2 * rows * lane_pad(slots)
        if (vmem <= DEFAULT_SPMV_WINDOW_VMEM_BUDGET
                and smem <= SMEM_INDEX_WORDS):
            return rows
    return None


def _scratch_dtype(scratch_dtype: Optional[str], itemsize: int):
    """The sweep scratch/operand dtype: bfloat16 under the mixed-precision
    mode, the wide float of `itemsize` bytes otherwise."""
    if scratch_dtype is not None and scratch_dtype not in SCRATCH_DTYPES:
        raise ValueError(f"scratch_dtype must be 'f32' or 'bf16', "
                         f"got {scratch_dtype!r}")
    return (jnp.bfloat16 if scratch_dtype == "bf16"
            else np.dtype(f"float{8 * itemsize}"))


def cheb_sweep_vmem_bytes(blocks_shape, n: int, eta: int,
                          batch: int = 1, itemsize: int = 4,
                          scratch_dtype: Optional[str] = None) -> int:
    """VMEM footprint model for one `cheb_sweep` launch over Block-ELL
    blocks of shape `blocks_shape` (nrb, slots, br, bc).

    Every VMEM buffer the persistent sweep holds at once
    (`cheb_sweep.cheb_sweep_buffers`), at its actual dtype and under the
    TPU tiling (`layout.tile_bytes`): the x operand and the t_k
    ping-pong pair at the scratch width (2 B under
    ``scratch_dtype="bf16"``), the eta accumulator planes at `itemsize`,
    and the Block-ELL blocks at the scratch width.  The batch rides the
    128 lanes, so B = 1 and B = 128 cost the same.  The budget sits under
    the compiler's default scoped-VMEM limit, so every launch the guard
    admits compiles (``tests/test_tpu_compile.py`` compiles one at the
    limit).  The degree does not enter: the coefficient table and column
    indices live in SMEM.
    """
    wide = np.dtype(f"float{8 * itemsize}")
    return vmem_bytes(cheb_sweep_buffers(
        n, batch, eta, blocks_shape, _scratch_dtype(scratch_dtype, itemsize),
        wide))


def _per_order_cheb(A: BlockELL, x: Array, coeffs: Array, lmax: float,
                    use_pallas: Optional[bool]) -> Array:
    """Per-order fallback: one SpMV + one `cheb_step` launch per order."""

    def mv(t):
        return spmv(A, t, use_pallas=use_pallas)

    return _cheb_recurrence_loop(mv, x, coeffs, lmax, use_pallas)


def fused_cheb_sweep(
    A: BlockELL,
    x: Array,
    coeffs: Union[Array, np.ndarray],
    lmax: float,
    use_pallas: Optional[bool] = None,
    vmem_budget: Optional[int] = None,
    scratch_dtype: Optional[str] = None,
) -> Array:
    """Phi_tilde x with the single-launch persistent sweep.

    x: (..., padded_n) at A's Block-ELL padded size; coeffs: (eta, K+1)
    (or (K+1,)).  Returns (..., eta, padded_n).  On the kernel path the
    whole K-order recurrence is ONE `pallas_call` (`kernels.cheb_sweep`)
    with iterates pinned in VMEM, guarded by
    :func:`cheb_sweep_vmem_bytes` against `vmem_budget` (default
    :data:`DEFAULT_SWEEP_VMEM_BUDGET`) — oversized problems fall back to
    the per-order `cheb_step` path (logged at INFO).  The reference path
    runs `ref.cheb_sweep_ref`, the same recurrence as one unrolled trace.

    scratch_dtype: None/"f32" or "bf16" — the mixed-precision kernel mode
    (`cheb_sweep.SCRATCH_DTYPES`); the footprint guard recomputes from
    the actual scratch width.
    """
    use, interp = _resolve(use_pallas)
    sdt = scratch_dtype or "f32"
    c = jnp.atleast_2d(jnp.asarray(coeffs, dtype=x.dtype))
    eta, K1 = c.shape
    K = K1 - 1
    alpha = float(lmax) / 2.0
    if use:
        budget = DEFAULT_SWEEP_VMEM_BUDGET if vmem_budget is None \
            else int(vmem_budget)
        n = x.shape[-1]
        batch = max(1, x.size // n)
        need = cheb_sweep_vmem_bytes(A.blocks.shape, n, eta, batch,
                                     scratch_dtype=sdt)
        if K < 2:
            return _per_order_cheb(A, x, c, lmax, use_pallas)
        if need > budget or A.indices.size > SMEM_INDEX_WORDS:
            obs.count("cheb_sweep.fallback")
            logger.info(
                "cheb_sweep: VMEM footprint %d B exceeds budget %d B or "
                "%d column indices exceed SMEM (n=%d, eta=%d, K=%d, B=%d) "
                "— falling back to the per-order cheb_step path", need,
                budget, A.indices.size, n, eta, K, batch)
            return _per_order_cheb(A, x, c, lmax, use_pallas)
        obs.count("cheb_sweep.launch")
        with obs.scope("sweep"):
            return cheb_sweep(A.blocks, A.indices, x, c, alpha=alpha,
                              interpret=interp, scratch_dtype=sdt)
    with obs.scope("sweep"):
        return ref.cheb_sweep_ref(A.blocks, A.indices, x, c, alpha=alpha)


def fused_cheb_recurrence(
    matvec,
    x: Array,
    coeffs: Union[Array, np.ndarray],
    lmax: float,
    use_pallas: Optional[bool] = None,
) -> Array:
    """Fused shifted-Chebyshev recurrence over an arbitrary matvec.

    The three-term recurrence of Algorithm 1 with the per-order AXPYs fused
    into the `cheb_step` Pallas kernel (one HBM round-trip per order instead
    of four).  `matvec` applies P along the last axis of the iterate,
    broadcasting over leading batch dims; it may contain collectives — the
    `pallas_halo` backend passes a halo-exchanging matvec and runs this
    whole function inside a shard_map, where `x` is the per-shard block.

    Single-launch upgrade: a `core.chebyshev.LocalMatvec` whose
    `block_ell` is set (a purely local Block-ELL product, no collectives)
    routes the whole loop to :func:`fused_cheb_sweep` — one kernel launch
    for all K orders, VMEM-guarded with a per-order fallback.  The
    `pallas` backend sets it always; `pallas_halo` only on a 1-shard
    mesh, where the halo exchange is a no-op.  The record's `vmem_budget`
    overrides the sweep budget, and its `sweep_dtype` ("bf16") selects
    the mixed-precision scratch mode of `cheb_sweep`.

    x: (..., n) — any n (`cheb_step` masks its ragged edge tile), and
    leading batch dims take the batched tile paths (one
    structure sweep / kernel launch per order for the whole batch).
    coeffs: (eta, K+1) (or (K+1,), treated as eta=1).
    Returns (..., eta, n).
    """
    if isinstance(matvec, LocalMatvec) and matvec.block_ell is not None:
        A_local = matvec.block_ell
        n_logical = x.shape[-1]
        out = fused_cheb_sweep(
            A_local, pad_trailing(x, A_local.padded_n), coeffs, lmax,
            use_pallas=use_pallas, vmem_budget=matvec.vmem_budget,
            scratch_dtype=matvec.sweep_dtype)
        return crop(out, n_logical)
    return _cheb_recurrence_loop(matvec, x, coeffs, lmax, use_pallas)


def _cheb_recurrence_loop(
    matvec,
    x: Array,
    coeffs: Union[Array, np.ndarray],
    lmax: float,
    use_pallas: Optional[bool] = None,
) -> Array:
    """The per-order recurrence loop (one matvec + one fused step/order),
    with the coefficient sum deferred: see :func:`_recurrence`.

    Supports the dual-signature stateful-matvec protocol of
    `core.chebyshev._stateful_matvec` (the int8 error-feedback halo
    exchange): a `core.chebyshev.LocalMatvec` with an ``init_state``
    threads its state through the scan carry; plain matvecs get an
    empty-state shim.
    """
    with obs.scope("recurrence"):
        return _recurrence(matvec, x, coeffs, lmax, use_pallas)


def _recurrence(matvec, x, coeffs, lmax, use_pallas):
    """sum_k c_{j,k} T_k x as (..., eta, n), folded into the accumulator
    once a chunk of s = eta + 1 orders instead of once an order.

    The iterates of a chunk are held, each its own buffer, and the order
    that closes the chunk folds them all in one `cheb_step` launch; the
    other orders compute t_k alone.  With s = eta + 1 the s - 1 held
    iterates take at most the accumulator's own memory.  The first chunk
    (at least T_0..T_2) writes the accumulator without reading it; full
    chunks run in a `lax.scan`, their orders unrolled, and a partial
    chunk closes the sum.  Inside the loop the accumulator is
    multiplier-major, (eta, ..., n), as `cheb_step` folds into it.
    """
    use, interp = _resolve(use_pallas)
    from ..core.chebyshev import _stateful_matvec

    c = jnp.atleast_2d(jnp.asarray(coeffs, dtype=x.dtype))
    eta, K1 = c.shape
    K = K1 - 1
    alpha = float(lmax) / 2.0

    w = c.at[:, 0].multiply(0.5)         # the weight of each T_k
    mv2, st = _stateful_matvec(matvec, x)
    if K < 2:                            # no order to fold: c_0 T_0 + c_1 T_1
        acc = w[:, 0:1] * x[..., None, :]
        if K == 1:
            px, _ = mv2(x, st)
            acc = acc + w[:, 1:2] * (px / alpha - x)[..., None, :]
        return acc
    px, st = mv2(x, st)
    t1 = px / alpha - x

    s = eta + 1
    obs.count("step.fold")
    obs.add("step.fold_width", s)

    def step(pt, t_km1, t_km2, acc=None, coef=None, held=()):
        with obs.scope("step"):
            if use:
                return cheb_step(pt, t_km1, t_km2, acc, coef, alpha=alpha,
                                 held=held, interpret=interp)
            return ref.cheb_step_ref(pt, t_km1, t_km2, acc, coef,
                                     alpha=alpha, held=held)

    def chunk(t_km1, t_km2, held, acc, st, coef):
        """The orders that complete a chunk of coef.shape[1] terms whose
        first iterates are `held`; the last order folds them all."""
        held = list(held)
        for _ in range(coef.shape[1] - len(held) - 1):
            pt, st = mv2(t_km1, st)
            tk = step(pt, t_km1, t_km2)
            held.append(tk)
            t_km2, t_km1 = t_km1, tk
        pt, st = mv2(t_km1, st)
        tk, acc = step(pt, t_km1, t_km2, acc, coef, tuple(held))
        return tk, t_km1, acc, st

    first = min(K, max(s - 1, 2))        # the order that closes chunk 0
    t_km1, t_km2, acc, st = chunk(t1, x, (x, t1), None, st,
                                  w[:, :first + 1])
    n_full, rest = divmod(K - first, s)
    if n_full:
        with obs.scope("layout"):
            chunks = w[:, first + 1:first + 1 + n_full * s].reshape(
                eta, n_full, s).transpose(1, 0, 2)

        def body(carry, coef):
            t_km1, t_km2, acc, st = carry
            return chunk(t_km1, t_km2, (), acc, st, coef), None

        (t_km1, t_km2, acc, st), _ = jax.lax.scan(
            body, (t_km1, t_km2, acc, st), chunks)
    if rest:
        _, _, acc, _ = chunk(t_km1, t_km2, (), acc, st, w[:, K - rest + 1:])
    return _multiplier_minor(acc)


def _multiplier_minor(acc: Array) -> Array:
    """(eta, ..., n) -> (..., eta, n), the layout callers take."""
    with obs.scope("layout"):
        return jnp.moveaxis(acc, 0, -2)


def fused_cheb_apply(
    A: BlockELL,
    x: Array,
    coeffs: Union[Array, np.ndarray],
    lmax: float,
    use_pallas: Optional[bool] = None,
    *,
    sweep: Optional[bool] = None,
    vmem_budget: Optional[int] = None,
    scratch_dtype: Optional[str] = None,
) -> Array:
    """Phi_tilde x with the SpMV + fused-step kernels (Algorithm 1 on TPU).

    x: (..., padded_n), last axis matching A's Block-ELL padding; any
    padded_n works (the fused step kernel masks its ragged edge tile)
    and leading batch dims share the K structure sweeps.
    Returns (..., eta, padded_n).

    sweep: None (default) routes through the single-launch
    :func:`fused_cheb_sweep` (which itself guards on the VMEM budget and
    falls back to the per-order path); False forces the per-order
    SpMV + `cheb_step` loop — the benchmark baseline.
    scratch_dtype: the sweep path's mixed-precision mode ("bf16" halves
    the iterate/operand/structure VMEM, f32 accumulator) — ignored on
    the per-order path.
    """
    if sweep is None or sweep:
        return fused_cheb_sweep(A, x, coeffs, lmax, use_pallas=use_pallas,
                                vmem_budget=vmem_budget,
                                scratch_dtype=scratch_dtype)
    return _per_order_cheb(
        A, x, jnp.atleast_2d(jnp.asarray(coeffs, dtype=x.dtype)), lmax,
        use_pallas)


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas: Optional[bool] = None,
) -> Array:
    """Flash attention (LM substrate): Pallas kernel on TPU, jnp oracle on
    CPU.  q: (B, Hq, S, D); k/v: (B, Hkv, S, D) with Hkv | Hq (GQA)."""
    use, interp = _resolve(use_pallas)
    if use:
        return _flash(q, k, v, causal=causal, scale=scale,
                      block_q=block_q, block_k=block_k, interpret=interp)
    return ref.attention_ref(q, k, v, causal=causal, scale=scale)


def jacobi_update(
    qx: Array,
    x: Array,
    x_prev: Array,
    y: Array,
    inv_d: Array,
    *,
    w,
    s,
    use_pallas: Optional[bool] = None,
) -> Array:
    """One fused (accelerated-)Jacobi round after the matvec ``qx = Q @ x``:

        x_next = w * (x + inv_d * (y - qx)) - s * x_prev

    (w = 1, s = 0 is the plain Jacobi sweep of Eq. (24); the Eq. (25)
    acceleration weights vary per iteration and may be traced scalars).
    The Section-V analog of `cheb_step`: five elementwise operands fused
    into one HBM round-trip per solver round.  Shapes as in
    :func:`repro.kernels.jacobi_step.jacobi_step`; complex iterates (none
    in the Jacobi solvers — ARMA carries its own real [Re, Im] stack) fall
    back to the jnp oracle.
    """
    use, interp = _resolve(use_pallas)
    with obs.scope("step"):
        if use and not jnp.iscomplexobj(x):
            return jacobi_step(qx, x, x_prev, y, inv_d, w=w, s=s,
                               interpret=interp)
        return ref.jacobi_step_ref(qx, x, x_prev, y, inv_d, w=w, s=s)


def jacobi_sweep_vmem_bytes(blocks_shape, n: int, n_den: int,
                            batch: int = 1, itemsize: int = 4,
                            scratch_dtype: Optional[str] = None) -> int:
    """VMEM footprint model for one `jacobi_sweep` launch over Block-ELL
    blocks of shape `blocks_shape` (`cheb_sweep.jacobi_sweep_buffers`,
    tiled): b, D^{-1}, x0 and the x
    ping-pong pair at `itemsize`, the Horner pair at the scratch width
    when deg(den) >= 2 (``n_den`` = len(den)), and the Block-ELL blocks
    at the scratch width."""
    wide = np.dtype(f"float{8 * itemsize}")
    return vmem_bytes(jacobi_sweep_buffers(
        n, batch, n_den, blocks_shape,
        _scratch_dtype(scratch_dtype, itemsize), wide))


def fused_jacobi_sweep(
    A: BlockELL,
    b: Array,
    inv_d: Array,
    den: Sequence[float],
    weights,
    *,
    x0: Optional[Array] = None,
    use_pallas: Optional[bool] = None,
    vmem_budget: Optional[int] = None,
    scratch_dtype: Optional[str] = None,
) -> Array:
    """Whole (accelerated-)Jacobi solve of den(P) x = b, one launch.

    The Section-V counterpart of :func:`fused_cheb_sweep`: all n_iters
    rounds of Eq. (24)/(25) — deg(den) Block-ELL SpMVs per round (Horner)
    plus the fused five-operand update — run inside one `jacobi_sweep`
    kernel with the iterates pinned in VMEM.  b / x0: (..., n) at any n
    (padded to A's Block-ELL size internally, cropped on return); inv_d
    broadcastable, zeros on padded/virtual rows.  weights: (n_iters, 2)
    host-side (w_t, s_t) schedule (`core.jacobi.jacobi_weights` /
    `cheb_jacobi_weights`).  The same VMEM-budget guard and per-order
    fallback (one `jacobi_step` launch per round, logged at INFO) as the
    Chebyshev sweep apply.  ``scratch_dtype="bf16"`` selects the
    mixed-precision kernel mode (the guard recomputes from the actual
    scratch width).
    """
    use, interp = _resolve(use_pallas)
    sdt = scratch_dtype or "f32"
    n_logical = b.shape[-1]
    total = A.padded_n
    bp = pad_trailing(jnp.asarray(b), total)
    invdp = pad_trailing(jnp.asarray(inv_d), total)
    x0p = (jnp.zeros_like(bp) if x0 is None
           else pad_trailing(jnp.asarray(x0), total))
    den = tuple(float(c) for c in den)
    ws = np.asarray(weights, dtype=np.float64)

    if use:
        budget = DEFAULT_SWEEP_VMEM_BUDGET if vmem_budget is None \
            else int(vmem_budget)
        batch = max(1, bp.size // total)
        need = jacobi_sweep_vmem_bytes(A.blocks.shape, total, len(den), batch,
                                       scratch_dtype=sdt)
        if need > budget or A.indices.size > SMEM_INDEX_WORDS:
            obs.count("jacobi_sweep.fallback")
            logger.info(
                "jacobi_sweep: VMEM footprint %d B exceeds budget %d B or "
                "%d column indices exceed SMEM (n=%d, B=%d) — falling back "
                "to the per-round jacobi_step path", need, budget,
                A.indices.size, total, batch)
        else:
            with obs.scope("sweep"):
                out = jacobi_sweep(A.blocks, A.indices, bp, invdp, ws, x0p,
                                   den=den, interpret=interp,
                                   scratch_dtype=sdt)
            return crop(out, n_logical)
        # per-round fallback: one SpMV chain + one fused update per round

        def body(carry, ws_row):
            x, x_prev = carry
            h = den[-1] * x
            for c in den[-2::-1]:
                h = spmv(A, h, use_pallas=use_pallas) + c * x
            x_next = jacobi_update(h, x, x_prev, bp, invdp,
                                   w=ws_row[0], s=ws_row[1],
                                   use_pallas=use_pallas)
            return (x_next, x), None

        with obs.scope("recurrence"):
            (x_final, _), _ = jax.lax.scan(
                body, (x0p, x0p), jnp.asarray(ws, bp.dtype))
        return crop(x_final, n_logical)
    with obs.scope("sweep"):
        out = ref.jacobi_sweep_ref(A.blocks, A.indices, bp, invdp, ws, x0p,
                                   den=den)
    return crop(out, n_logical)


def ista_update(
    a: Array,
    phi_y: Array,
    gram_a: Array,
    thresh: Array,
    gamma: float,
    use_pallas: Optional[bool] = None,
) -> Array:
    """One fused ISTA update (Algorithm 3 line 5 + Eq. (32) shrinkage):
    ``soft_threshold(a + gamma * (phi_y - gram_a), thresh)`` in a single
    kernel pass.  a/phi_y/gram_a: (..., eta, N); thresh: (eta,) or (eta, 1)
    or any shape broadcastable against a.  Batched inputs (ndim > 2) use
    the elementwise jnp path — shrinkage is memory-bound either way."""
    use, interp = _resolve(use_pallas)
    if thresh.ndim == 1:
        thresh = thresh[:, None]
    if use and a.ndim == 2 and thresh.shape == (a.shape[0], 1):
        return ista_shrink(a, phi_y, gram_a, thresh, gamma=gamma,
                           interpret=interp)
    return ref.ista_shrink_ref(a, phi_y, gram_a, thresh, gamma=gamma)


def pad_trailing(x: Array, total: int) -> Array:
    """Zero-pad the last (vertex) axis up to the absolute size `total`;
    leading batch / eta axes pass through untouched.  The one padding
    primitive every execution backend shares under the (..., N) contract.
    """
    pad = total - x.shape[-1]
    if pad == 0:
        return x
    with obs.scope("layout"):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def crop(x: Array, n: int) -> Array:
    """The first `n` entries of the last (vertex) axis: the inverse of
    :func:`pad_trailing`."""
    if x.shape[-1] == n:
        return x
    with obs.scope("layout"):
        return x[..., :n]


def pad_for_kernels(x: Array, multiple: int = 1024) -> Array:
    """Zero-pad the last axis up to `multiple` (kernel tile alignment).

    Callers that hold the logical size are responsible for stripping the
    padding from outputs; the execution backends do this internally.
    """
    n = x.shape[-1]
    return pad_trailing(x, n + (-n) % multiple)
