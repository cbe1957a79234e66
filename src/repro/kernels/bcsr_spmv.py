"""Block-ELL sparse matvec Pallas kernel — the Algorithm 1 hot loop on TPU.

The paper's per-Chebyshev-order cost is one sparse matvec with P (cost
proportional to |E|, Section IV-A). On TPU we store P in Block-ELL
(`core.graph.BlockELL`): every row block keeps a fixed number of
column-block slots, so the kernel is fully static.  The kernel streams
the structure's row panels (`BlockELL.panels`, built once with the
structure by `core.graph.block_panels`): a row block's slots side by
side as one (br, slots * bc) panel.

Grid: (n_row_blocks,); each step loads one panel and gathers the slots'
column tiles through `slots` x BlockSpecs, whose index maps read the
scalar-prefetched column-block indices (in row-block chunks that fit
SMEM).

Batched layout (`block_ell_spmv_batched`, the only entry point — a 1-D
signal is a batch of one): the (..., N) signal contract makes B signals
ride one sweep of the sparsity structure — the iterate is laid out
(ncb, bc, B) so each row block is a single (br, slots * bc) x
(slots * bc, B) MXU product, amortizing every panel load (and every
index gather) across the whole batch.

This kernel is one *launch per matvec*: an order-K recurrence pays K
launches plus the `cheb_step` AXPYs in between.  `cheb_sweep` streams the
same Block-ELL structure through its in-kernel SpMV
(`cheb_sweep._row_product` gathers the same column tiles, (bc, B) with
the batch on lanes, by scalar-prefetched column index) so the whole recurrence runs in one
launch; this module stays the per-matvec primitive for sharded matvecs
whose orders are separated by halo exchanges.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .layout import lane_pad, mxu_precision, pad_lanes

Array = jax.Array


#: Column-index words one launch scalar-prefetches into SMEM (1 MiB on
#: v5e, shared with the compiler's own scalars).  A larger structure is
#: swept in row-block chunks, each launch writing its rows of one shared
#: output buffer.
SMEM_INDEX_WORDS = 128 * 1024


def _spmv_kernel(slots, idx_ref, panel_ref, *refs):
    """One row block: the (br, slots * bc) panel times the stacked
    (slots * bc, B) column tiles, a single MXU product."""
    x_refs, y_ref = refs[:slots], refs[-1]  # between: aliased earlier rows
    xs = jnp.concatenate([x[0] for x in x_refs], axis=0)
    panel = panel_ref[0]
    y_ref[0] = jnp.dot(panel, xs.astype(panel.dtype),
                       preferred_element_type=jnp.float32,
                       precision=mxu_precision(panel.dtype)
                       ).astype(y_ref.dtype)


def _sweep_rows(panels, idx, xt, y, r0: int, r1: int, slots: int,
                interpret: bool):
    """One launch over row blocks [r0, r1): idx is their flattened
    (rows * slots,) column-index slice; y (or None for the first chunk)
    is the output so far, aliased in place."""
    nrb, br, _ = panels.shape
    _, bc, B = xt.shape

    def tile(s):
        return pl.BlockSpec((1, bc, B),
                            lambda i, idx: (idx[i * slots + s], 0, 0))

    in_specs = ([pl.BlockSpec((1, br, slots * bc),
                              lambda i, idx: (i + r0, 0, 0))]
                + [tile(s) for s in range(slots)])
    args = [idx, panels] + [xt] * slots
    aliases = {}
    if y is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        aliases = {len(args): 0}
        args.append(y)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r1 - r0,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, br, B), lambda i, idx: (i + r0, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_spmv_kernel, slots),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrb, br, B), xt.dtype),
        input_output_aliases=aliases,
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_ell_spmv_batched(
    panels: Array,
    indices: Array,
    x: Array,
    *,
    interpret: bool = False,
) -> Array:
    """Y = A @ X^T for a batch of signals, one structure sweep total.

    panels:  (nrb, br, slots * bc) Block-ELL row panels
             (`core.graph.block_panels`) — padded slots must be zero.
    indices: (nrb, slots) int32 column-block index per slot.
    x: (..., nrb_cols * bc) padded signals with arbitrary leading batch
    dims (a 1-D x is a batch of one).  Returns (..., nrb * br).  Each grid
    step loads one row block's panel once, gathers the slots' (bc, B)
    column tiles of all batch signals, and multiplies — the panel loads
    (the HBM-bound part of the sweep) are amortized over B.
    """
    nrb, br, width = panels.shape
    slots = indices.shape[1]
    bc = width // slots
    batch_shape = x.shape[:-1]
    B = x.size // x.shape[-1]
    # (B, ncb, bc) -> (ncb, bc, Bp): batch innermost (zero-padded to whole
    # 128-lane vregs) so every row block is one MXU-shaped
    # (br, slots * bc) x (slots * bc, Bp) product
    # (a 2-D transpose, then a free major-dim split: XLA compiles the 3-D
    # transpose of a B=1 batch very slowly)
    Bp = lane_pad(B)
    n = x.shape[-1]
    with obs.scope("layout"):
        xt = pad_lanes(x.reshape(B, n).T, Bp).reshape(n // bc, bc, Bp)
    rows = max(1, SMEM_INDEX_WORDS // slots)
    y = None
    for r0 in range(0, nrb, rows):
        r1 = min(nrb, r0 + rows)
        y = _sweep_rows(panels, indices[r0:r1].reshape(-1), xt, y, r0, r1,
                        slots, interpret)
    with obs.scope("layout"):
        y = y.reshape(nrb * br, Bp)[:, :B].T
        return y.reshape(batch_shape + (nrb * br,))
