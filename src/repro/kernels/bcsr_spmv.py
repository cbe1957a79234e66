"""Block-ELL sparse matvec Pallas kernels — the Algorithm 1 hot loop on TPU.

The paper's per-Chebyshev-order cost is one sparse matvec with P (cost
proportional to |E|, Section IV-A). On TPU we store P in Block-ELL
(`core.graph.BlockELL`): every row block keeps a fixed number of
column-block slots, so the kernels are fully static.  They stream the
structure's row panels (`BlockELL.panels`, built once with the structure
by `core.graph.block_panels`): a row block's slots side by side as one
(br, slots * bc) panel, so each row block is a single
(br, slots * bc) x (slots * bc, B) MXU product.

Batched layout (a 1-D signal is a batch of one): the (..., N) signal
contract makes B signals ride one sweep of the sparsity structure — the
iterate is laid out (ncb, bc, B) with the batch on lanes, amortizing
every panel load (and every index read) across the whole batch.

The two kernels differ only in how a row block's column tiles reach VMEM:

* `block_ell_spmv_window` — grid (n_groups,) over groups of `rows` row
  blocks.  When every valid slot lies within `band` column blocks of its
  row block's own (`BlockELL.band`), a group's column tiles all lie in
  one window of about rows + 2 * band column blocks.  One async copy
  brings the next group's window into VMEM while this group computes
  (two buffers); each row block then gathers its slots' (bc, B) tiles
  from the window, as `cheb_sweep._row_product` does from its resident
  iterate.  The group's panels, its slice of the column-index table (in
  SMEM) and its output rows ride per-group blocks, so a structure of any
  size is one launch.
* `block_ell_spmv_batched` — the gather path, for any structure.  Grid
  (n_row_blocks,); each step loads one panel and gathers the slots'
  column tiles through `slots` x BlockSpecs, whose index maps read the
  scalar-prefetched column-block indices (in row-block chunks that fit
  SMEM, one launch per chunk).

`kernels.ops.spmv` takes the window when the band is known and the
window's buffers fit its VMEM budget, else the gather path.

Both are one *launch per matvec*: an order-K recurrence pays K launches
plus the `cheb_step` AXPYs in between.  `cheb_sweep` runs the whole
recurrence in one launch for structures that fit VMEM; these stay the
per-matvec primitive for larger ones and for sharded matvecs whose
orders are separated by halo exchanges.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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


def _to_lanes(x: Array, bc: int):
    """(..., n) signals -> ((n // bc, bc, Bp) iterate, B): batch innermost,
    zero-padded to whole 128-lane vregs, so every row block is one
    MXU-shaped product (a 2-D transpose, then a free major-dim split: XLA
    compiles the 3-D transpose of a B=1 batch very slowly)."""
    n = x.shape[-1]
    B = x.size // n
    with obs.scope("layout"):
        xt = pad_lanes(x.reshape(B, n).T, lane_pad(B))
        return xt.reshape(n // bc, bc, xt.shape[-1]), B


def _from_lanes(y: Array, B: int, batch_shape):
    """(nrb, br, Bp) kernel output -> (..., nrb * br)."""
    nrb, br, Bp = y.shape
    with obs.scope("layout"):
        y = y.reshape(nrb * br, Bp)[:, :B].T
        return y.reshape(batch_shape + (nrb * br,))


def _row_product(panel, xs, dtype):
    """One row block: its (br, slots * bc) panel times the stacked
    (slots * bc, B) column tiles, a single MXU product."""
    return jnp.dot(panel, xs.astype(panel.dtype),
                   preferred_element_type=jnp.float32,
                   precision=mxu_precision(panel.dtype)).astype(dtype)


def _spmv_kernel(slots, idx_ref, panel_ref, *refs):
    """One row block of the gather path."""
    x_refs, y_ref = refs[:slots], refs[-1]  # between: aliased earlier rows
    xs = jnp.concatenate([x[0] for x in x_refs], axis=0)
    y_ref[0] = _row_product(panel_ref[0], xs, y_ref.dtype)


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
    xt, B = _to_lanes(x, width // slots)
    rows = max(1, SMEM_INDEX_WORDS // slots)
    y = None
    for r0 in range(0, nrb, rows):
        r1 = min(nrb, r0 + rows)
        y = _sweep_rows(panels, indices[r0:r1].reshape(-1), xt, y, r0, r1,
                        slots, interpret)
    return _from_lanes(y, B, x.shape[:-1])


# ---------------------------------------------------------------------------
# The band-windowed grid
# ---------------------------------------------------------------------------
#: Row blocks per iteration of a group's loop: unrolled so the scalar
#: index reads and tile loads of one row block overlap the MXU product of
#: the one before (a sensor1m-shaped SpMV, 125,000 row blocks of
#: (8, 184) x (184, 128) products, took 14.7 ms on a TPU v5e at 16
#: against 31.6 ms at 1).
ROW_UNROLL = 16


def window_starts(nrb: int, br: int, bc: int, band: int, rows: int):
    """(first column block of each group's window, (n_groups,) int32;
    the window's length in column blocks).

    Group g holds row blocks [g * rows, (g + 1) * rows) ∩ [0, nrb); its
    rows span column blocks lo_g .. hi_g, and a structure of band `band`
    reads only [lo_g - band, hi_g + band].  Every window has the length
    of the widest such range, clamped to the ncb column blocks, and an
    edge group's window slides inward instead of shrinking."""
    ncb = nrb * br // bc
    first = np.arange(0, nrb, rows, dtype=np.int64)
    lo = first * br // bc
    hi = (np.minimum(first + rows, nrb) * br - 1) // bc
    span = int(min(ncb, (hi - lo).max() + 1 + 2 * band))
    starts = np.clip(lo - band, 0, ncb - span).astype(np.int32)
    return starts, span


def window_buffers(rows: int, span: int, block, slots: int, lanes: int,
                   dtype, panel_dtype):
    """Every VMEM buffer one `block_ell_spmv_window` launch holds, each
    twice (the pipeline's two buffers): the x window, the group's panels
    and its output rows.  The group's column indices live in SMEM."""
    br, bc = block
    return 2 * [((span, bc, lanes), dtype),
                ((rows, br, slots * bc), panel_dtype),
                ((rows, br, lanes), dtype)]


def _window_kernel(starts_ref, idx_ref, panel_ref, x_hbm, y_ref, win, sem,
                   *, rows: int, slots: int, span: int):
    """One group of `rows` row blocks.  Step g starts the copy of group
    g + 1's window into the other buffer, waits for its own, then runs
    its row blocks from VMEM.  idx_ref holds the group's column indices
    already relative to its window."""
    g = pl.program_id(0)

    def fetch(group, buf):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(starts_ref[group], span)], win.at[buf],
            sem.at[buf])

    @pl.when(g == 0)
    def _():
        fetch(0, 0).start()

    @pl.when(g + 1 < pl.num_programs(0))
    def _():
        fetch(g + 1, (g + 1) % 2).start()

    buf = g % 2
    fetch(g, buf).wait()
    tiles = win.at[buf]

    def row(r):
        xs = jnp.concatenate([tiles[idx_ref[r, s]] for s in range(slots)],
                             axis=0)
        y_ref[r] = _row_product(panel_ref[r], xs, y_ref.dtype)

    unroll = min(ROW_UNROLL, rows)

    def rows_body(i, carry):
        for j in range(unroll):
            row(i * unroll + j)
        return carry

    jax.lax.fori_loop(0, rows // unroll, rows_body, 0)
    for r in range(rows - rows % unroll, rows):
        row(r)


@functools.partial(jax.jit, static_argnames=("band", "rows", "interpret"))
def block_ell_spmv_window(
    panels: Array,
    indices: Array,
    x: Array,
    *,
    band: int,
    rows: int,
    interpret: bool = False,
) -> Array:
    """Y = A @ X^T, as `block_ell_spmv_batched`, over a structure of
    known band: one launch whose grid steps are groups of `rows` row
    blocks, each reading its column tiles from a VMEM window of x.

    band: every valid slot's column block lies within `band` column
    blocks of the ones its row block's rows span
    (`core.graph.block_ell_band`).  rows: row blocks per group; the last
    group may be partial (on TPU, a multiple of 8 or nrb).  Column
    indices are made relative to their group's window first, padded
    slots clamped into it (their blocks are zero).  The arithmetic per
    row block is the gather path's, so the two agree to rounding.
    """
    nrb, br, width = panels.shape
    slots = indices.shape[1]
    bc = width // slots
    xt, B = _to_lanes(x, bc)
    rows = min(rows, nrb)
    starts, span = window_starts(nrb, br, bc, band, rows)
    local = jnp.clip(indices - np.repeat(starts, rows)[:nrb, None], 0,
                     span - 1)
    lanes = xt.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(starts.size,),
        in_specs=[
            pl.BlockSpec((rows, slots), lambda g, st: (g, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, br, width), lambda g, st: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((rows, br, lanes), lambda g, st: (g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, span, bc, lanes), xt.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    y = pl.pallas_call(
        functools.partial(_window_kernel, rows=rows, slots=slots, span=span),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrb, br, lanes), xt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(starts), local, panels, xt)
    return _from_lanes(y, B, x.shape[:-1])
