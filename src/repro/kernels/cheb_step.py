"""Fused Chebyshev recurrence step Pallas kernel.

One order of Algorithm 1 after the sparse matvec `pt = P @ t_{k-1}`:

    t_k   = (2/alpha) * pt - 2 * t_{k-1} - t_{k-2}      (line 9)
    acc_j += c_{j,k} * t_k   for every multiplier j       (line 12 running sum)

Fusing the AXPYs keeps the iterate traffic at one HBM round-trip per order
instead of four (the memory-bound part of the recurrence; see
docs/ARCHITECTURE.md "Perf accounting" for the full model).  The next rung
on that ladder is `cheb_sweep.cheb_sweep`, which collapses the K per-order
launches into ONE persistent kernel with the iterates pinned in VMEM —
this per-order kernel remains the fallback when the sweep's VMEM-footprint
guard trips, and the per-shard step for sharded matvecs that carry
collectives.

Halo-aware tiling: the kernel is also the per-shard recurrence step of the
`pallas_halo` backend, where it runs inside a shard_map on each shard's
local block (size nl, generally *not* a 128 multiple).  The grid is
``cdiv``-sized over (batch, lane) tiles, so the same tiling serves the
global (padded_n) and the per-shard (nl) iterate shapes without padding.

Batched iterates ((..., n) under the repo-wide (..., N) signal contract)
flatten to one (B, n) operand whose (bb, blk) tiles keep the batch on
sublanes: one kernel launch advances every batch signal one Chebyshev
order, keeping the per-order HBM traffic at one round-trip for the whole
batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

_BLOCK = 1024
#: Elements of one (batch, lane) iterate tile: with the eta accumulator
#: planes (padded to 8 sublanes) double-buffered in and out, a tile this
#: size keeps a launch near 5 MiB of VMEM, inside the default scoped limit.
_TILE_ELEMS = 32 * 1024
#: Batch rows per tile once the batch outgrows one tile (a multiple of 8,
#: the f32 sublane count).
_BATCH_TILE = 64


def pick_block(n: int, maximum: int = _BLOCK) -> int:
    """Largest 128-multiple block size <= maximum that divides n."""
    for b in range(min(maximum, n), 127, -128):
        if n % b == 0 and b % 128 == 0:
            return b
    raise ValueError(f"pad n (={n}) to a multiple of 128")


def batch_tiles(batch: int, n: int):
    """(bb, blk) tile of a (batch, n) iterate for the elementwise kernels.

    The whole batch rides one tile up to :data:`_BATCH_TILE` rows (a block
    dim equal to the array dim is always legal), else 64-row tiles; the
    lane tile is a 128 multiple sized so bb * blk stays near
    :data:`_TILE_ELEMS`.  Neither needs to divide the array: the grid is
    ``cdiv``-sized and Pallas masks the ragged edge tiles.
    """
    bb = batch if batch <= _BATCH_TILE else _BATCH_TILE
    cap = max(128, min(_BLOCK, _TILE_ELEMS // bb // 128 * 128))
    return bb, min(cap, -(-n // 128) * 128)


def _cheb_step_kernel(coef_ref, pt_ref, t1_ref, t2_ref, acc_ref,
                      tk_out_ref, acc_out_ref, *, two_over_alpha):
    tk = two_over_alpha * pt_ref[...] - 2.0 * t1_ref[...] - t2_ref[...]
    tk_out_ref[...] = tk                                   # (bb, blk)
    # coef_ref: (eta, 1), broadcast against tk over the (bb, eta, blk) tile
    acc_out_ref[...] = acc_ref[...] + coef_ref[...][None] * tk[:, None, :]


@functools.partial(jax.jit, static_argnames=("alpha", "interpret"))
def cheb_step(
    pt: Array,
    t_km1: Array,
    t_km2: Array,
    acc: Array,
    coef: Array,
    *,
    alpha: float,
    interpret: bool = False,
):
    """Returns (t_k, acc + outer(coef, t_k)).

    pt, t_km1, t_km2: (..., n) — any n.  acc: (..., eta, n); coef: (eta,).
    Leading batch dims flatten to one (B, n) iterate tiled by
    :func:`batch_tiles`, so the whole batch advances one Chebyshev order
    in a single kernel launch; the accumulator is updated in place
    (aliased input/output).
    """
    n = pt.shape[-1]
    eta = acc.shape[-2]
    batch_shape = pt.shape[:-1]
    B = pt.size // n
    bb, blk = batch_tiles(B, n)
    it = pl.BlockSpec((bb, blk), lambda b, i: (b, i))
    acc_spec = pl.BlockSpec((bb, eta, blk), lambda b, i: (b, 0, i))
    kernel = functools.partial(_cheb_step_kernel, two_over_alpha=2.0 / alpha)
    tk, acc_out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(B, bb), pl.cdiv(n, blk)),
        in_specs=[pl.BlockSpec((eta, 1), lambda b, i: (0, 0)),
                  it, it, it, acc_spec],
        out_specs=[it, acc_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, n), pt.dtype),
            jax.ShapeDtypeStruct((B, eta, n), acc.dtype),
        ],
        input_output_aliases={4: 1},
        interpret=interpret,
    )(coef[:, None], pt.reshape(B, n), t_km1.reshape(B, n),
      t_km2.reshape(B, n), acc.reshape(B, eta, n))
    return (tk.reshape(batch_shape + (n,)),
            acc_out.reshape(batch_shape + (eta, n)))
