"""'pallas' execution backend: Block-ELL SpMV + fused Chebyshev-step kernels.

Converts the dense P once into the Block-ELL layout at plan time, then every
application runs the fused recurrence (`kernels.ops.fused_cheb_recurrence`)
— the hot path on TPU, interpret mode on CPU.  By default the plan's
matvec record carries its Block-ELL structure, so the whole K-order
recurrence dispatches to the single-launch persistent sweep
(`kernels.cheb_sweep` via `ops.fused_cheb_sweep`): iterates pinned in VMEM
across all orders, one kernel launch instead of 2K, guarded by the VMEM
footprint model with a per-order fallback (pass ``sweep=False`` /
``vmem_budget=`` at plan time to control it), and `plan.solve`'s
Jacobi/Chebyshev solvers ride the same one-launch sweep kernels.  Signals
are padded to the Block-ELL padded size internally and the padding is
stripped from every output, so callers see the logical N everywhere.
Batched (..., N) signals hit the batched SpMV tile path: every Block-ELL
block load is amortized across the batch, so B signals cost one structure
sweep per order, not B.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from ...core import chebyshev as cheb
from ...core import graph as graphmod
from ...kernels import ops
from . import register_backend


@register_backend("pallas")
def build(op, *, mesh=None, partition=None, block: Tuple[int, int] = (8, 128),
          use_pallas: Optional[bool] = True, sweep: Optional[bool] = None,
          vmem_budget: Optional[int] = None,
          sweep_dtype: Optional[str] = None, **options):
    from ..operator import LocalForm, plan_from_form

    del mesh, partition  # single-device backend
    if callable(op.P):
        raise ValueError("pallas backend needs a dense P to build Block-ELL")
    L = np.asarray(op.P, dtype=np.float32)
    A = graphmod.to_block_ell(L, block)
    n = L.shape[0]
    total = A.padded_n

    def spmv(t):
        # batched Block-ELL SpMV: leading dims (batch, eta streams, ...)
        # ride one sweep of the sparsity structure
        return ops.spmv(A, t, use_pallas=use_pallas)

    # sweep=False leaves the structure off the record: per-order kernels
    mv = cheb.LocalMatvec(spmv, block_ell=A if sweep is None or sweep
                          else None,
                          vmem_budget=vmem_budget, sweep_dtype=sweep_dtype)
    form = LocalForm(
        local=lambda mine: mv,
        recurrence=functools.partial(ops.fused_cheb_recurrence,
                                     use_pallas=use_pallas),
        enter=lambda x, mine: ops.pad_trailing(x, total),
        leave=lambda y, mine: ops.crop(y, n), lasso=False,
        host_coeffs=True)
    nnz_blocks = int(np.asarray(A.mask).sum()) if hasattr(A, "mask") else None
    return plan_from_form(op, "pallas", form, info={
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
    })
