"""'pallas' execution backend: Block-ELL SpMV + fused Chebyshev-step kernels.

Converts the dense P once into the Block-ELL layout at plan time, then every
application runs the fused recurrence (`kernels.ops.fused_cheb_apply`) — the
hot path on TPU, interpret mode on CPU.  By default the whole K-order
recurrence dispatches to the single-launch persistent sweep
(`kernels.cheb_sweep` via `ops.fused_cheb_sweep`): iterates pinned in VMEM
across all orders, one kernel launch instead of 2K, guarded by the VMEM
footprint model with a per-order fallback (pass ``sweep=False`` /
``vmem_budget=`` at plan time to control it).  The plan's matvec is tagged
with its Block-ELL structure, so `plan.solve`'s Jacobi/Chebyshev solvers
ride the same one-launch sweep kernels.  Signals are padded to the
Block-ELL padded size internally and the padding is stripped from every
output, so callers see the logical N everywhere.  Batched (..., N) signals
hit the batched SpMV tile path: every Block-ELL block load is amortized
across the batch, so B signals cost one structure sweep per order, not B.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core import chebyshev as cheb
from ...core import graph as graphmod
from ...kernels import ops
from . import register_backend

Array = jax.Array


@register_backend("pallas")
def build(op, *, mesh=None, partition=None, block: Tuple[int, int] = (8, 128),
          use_pallas: Optional[bool] = True, sweep: Optional[bool] = None,
          vmem_budget: Optional[int] = None,
          sweep_dtype: Optional[str] = None, **options):
    from ..operator import ExecutionPlan

    del mesh, partition  # single-device backend
    if callable(op.P):
        raise ValueError("pallas backend needs a dense P to build Block-ELL")
    L = np.asarray(op.P, dtype=np.float32)
    A = graphmod.to_block_ell(L, block)
    n = L.shape[0]
    total = A.padded_n
    coeffs = op.coeffs
    lmax = op.lmax

    def _pad(x: Array) -> Array:
        return ops.pad_trailing(x, total)

    def _mv(t: Array) -> Array:
        # batched Block-ELL SpMV: leading dims (batch, eta streams, ...)
        # ride one sweep of the sparsity structure
        return ops.spmv(A, t, use_pallas=use_pallas)

    if sweep is None or sweep:
        # tag the matvec so ops.fused_cheb_recurrence / plan.solve collapse
        # whole iterations into the single-launch sweep kernels
        _mv.block_ell = A
        _mv.vmem_budget = vmem_budget
        _mv.sweep_dtype = sweep_dtype

    def apply(f: Array) -> Array:
        c2 = np.atleast_2d(np.asarray(coeffs))
        out = ops.fused_cheb_apply(A, _pad(f), c2, lmax,
                                   use_pallas=use_pallas, sweep=sweep,
                                   vmem_budget=vmem_budget,
                                   scratch_dtype=sweep_dtype)
        return ops.crop(out, n)

    def apply_adjoint(a: Array) -> Array:
        out = cheb.cheb_apply_adjoint(_mv, _pad(a),
                                      jnp.asarray(coeffs, a.dtype), lmax)
        return ops.crop(out, n)

    def apply_gram(f: Array) -> Array:
        d = cheb.gram_coeffs(coeffs)
        out = ops.fused_cheb_apply(A, _pad(f), d[None], lmax,
                                   use_pallas=use_pallas, sweep=sweep,
                                   vmem_budget=vmem_budget,
                                   scratch_dtype=sweep_dtype)
        return ops.crop(out[..., 0, :], n)

    def matvec_runner(fn, signals, consts=()):
        # run the iteration body against the Block-ELL SpMV on the padded
        # domain; every output's trailing vertex axis is cropped back to n
        padded = tuple(ops.pad_trailing(jnp.asarray(s), total)
                       for s in signals)
        outs = fn(_mv, *padded, *consts)
        return jax.tree.map(lambda o: ops.crop(o, n), outs)

    nnz_blocks = int(np.asarray(A.mask).sum()) if hasattr(A, "mask") else None
    return ExecutionPlan(
        op=op, backend="pallas",
        apply=apply, apply_adjoint=apply_adjoint, apply_gram=apply_gram,
        matvec_runner=matvec_runner,
        info={
            "block": block,
            "padded_n": total,
            "nnz_blocks": nnz_blocks,
            "flops_per_matvec": (
                None if nnz_blocks is None
                else nnz_blocks * 2 * block[0] * block[1]),
            "blockell_fill": graphmod.block_ell_fill(A.blocks),
            "spmv_band": A.band,
            "sweep_dtype": sweep_dtype or "f32",
            "sweep_vmem_bytes": ops.cheb_sweep_vmem_bytes(
                A.blocks.shape, total, op.eta, scratch_dtype=sweep_dtype),
            "sweep_vmem_budget": (ops.DEFAULT_SWEEP_VMEM_BUDGET
                                  if vmem_budget is None else vmem_budget),
        },
    )
