"""Chebyshev recurrence step Pallas kernel, with the deferred fold.

One order of Algorithm 1 after the sparse matvec `pt = P @ t_{k-1}`:

    t_k = (2/alpha) * pt - 2 * t_{k-1} - t_{k-2}          (line 9)

and, on the order that closes a chunk of s = eta + 1 orders, the line-12
running sum for the whole chunk at once:

    acc_j += sum_i c_{j,i} * T_i   over the chunk's held iterates and t_k

The caller (`ops._cheb_recurrence_loop`) keeps a chunk's iterates, each
its own buffer, until that order.  A plain order then reads 3 iterates
and writes 1; the (eta, B, n) accumulator, eta times an iterate, is read
and written once a chunk instead of once an order (the memory-bound part
of the recurrence; see docs/ARCHITECTURE.md "Perf accounting").  The
accumulator is multiplier-major, so a fold is scalar-times-tile f32
multiply-adds on the VPU over each multiplier's (bb, blk) plane, with no
padding of eta to the sublanes.  The next rung on that ladder is
`cheb_sweep.cheb_sweep`, which collapses the K per-order launches into
ONE persistent kernel with the iterates pinned in VMEM — this per-order
kernel remains the fallback when the sweep's VMEM-footprint guard trips,
and the per-shard step for sharded matvecs that carry collectives.

Halo-aware tiling: the kernel is also the per-shard recurrence step of the
`pallas_halo` backend, where it runs inside a shard_map on each shard's
local block (size nl, generally *not* a 128 multiple).  The grid is
``cdiv``-sized over (batch, lane) tiles, so the same tiling serves the
global (padded_n) and the per-shard (nl) iterate shapes without padding.

Batched iterates ((..., n) under the repo-wide (..., N) signal contract)
flatten to one (B, n) operand whose (bb, blk) tiles keep the batch on
sublanes: one kernel launch advances every batch signal one Chebyshev
order.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_BLOCK = 1024
#: Elements of one (batch, lane) iterate tile: with a chunk's held
#: iterates and the eta accumulator planes double-buffered in and out, a
#: tile this size keeps a fold near 6 MiB of VMEM at eta = 7, inside the
#: default scoped limit.
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


def _cheb_step_kernel(*refs, two_over_alpha, i1, i2, held, fold, has_acc):
    """refs: [coef (eta * m,) in SMEM] if folding, pt, the distinct
    iterates, [acc] if it is read; then t_k and, if folding, the
    accumulator."""
    refs = list(refs)
    coef_ref = refs.pop(0) if fold else None
    pt_ref = refs.pop(0)
    n_out = 2 if fold else 1
    outs = refs[len(refs) - n_out:]
    ins = refs[:len(refs) - n_out]
    acc_ref = ins.pop() if has_acc else None
    tk = two_over_alpha * pt_ref[...] - 2.0 * ins[i1][...] - ins[i2][...]
    outs[0][...] = tk                                      # (bb, blk)
    if not fold:
        return
    # one (bb, blk) plane a multiplier: scalar-times-tile f32
    # multiply-adds on the VPU, the terms added in order
    terms = [ins[j][...] for j in held] + [tk]
    m = len(terms)
    for e in range(outs[1].shape[0]):
        acc = acc_ref[e] if has_acc else None
        for i, t in enumerate(terms):
            term = coef_ref[e * m + i] * t
            acc = term if acc is None else acc + term
        outs[1][e] = acc


def cheb_step(
    pt: Array,
    t_km1: Array,
    t_km2: Array,
    acc: Optional[Array] = None,
    coef: Optional[Array] = None,
    *,
    alpha: float,
    held: Sequence[Array] = (),
    interpret: bool = False,
):
    """One order t_k, and on the order that closes a chunk the fold of the
    chunk's iterates into the accumulator.

    pt, t_km1, t_km2 and each of `held`: (..., n) — any n.  Without
    `coef` it returns t_k alone (3 reads, 1 write).  With `coef` ((eta,)
    or (eta, m), m = len(held) + 1) it returns (t_k, fold) where

        fold[j] = acc[j] + sum_i coef[j, i] * term_i,  terms = (*held, t_k)

    as f32 multiply-adds on the VPU.  The accumulator is multiplier-major,
    (eta, ..., n): each multiplier's plane is tiled as an iterate is.  It
    is updated in place (aliased input/output), and ``acc=None`` writes
    the fold without reading an accumulator.  Iterates that are the same
    array (``held[-1] is t_km1``) are read once.  Leading batch dims
    flatten to one (B, n) operand tiled by :func:`batch_tiles`, so the
    whole batch advances one Chebyshev order in a single kernel launch.
    """
    iterates = []

    def slot(a):
        for i, b in enumerate(iterates):
            if a is b:
                return i
        iterates.append(a)
        return len(iterates) - 1

    i1, i2 = slot(t_km1), slot(t_km2)
    held_slots = tuple(slot(h) for h in held)
    if coef is not None:
        coef = coef.reshape(coef.shape[0], -1)
        if coef.shape[1] != len(held) + 1:
            raise ValueError(f"coef has {coef.shape[1]} columns for "
                             f"{len(held) + 1} terms")
    return _cheb_step(pt, tuple(iterates), acc, coef, alpha=alpha, i1=i1,
                      i2=i2, held=held_slots, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("alpha", "i1", "i2", "held",
                                             "interpret"))
def _cheb_step(pt, iterates, acc, coef, *, alpha, i1, i2, held, interpret):
    n = pt.shape[-1]
    batch_shape = pt.shape[:-1]
    B = pt.size // n
    bb, blk = batch_tiles(B, n)
    it = pl.BlockSpec((bb, blk), lambda b, i: (b, i))
    fold = coef is not None
    has_acc = acc is not None
    kernel = functools.partial(_cheb_step_kernel, two_over_alpha=2.0 / alpha,
                               i1=i1, i2=i2, held=held, fold=fold,
                               has_acc=has_acc)
    in_specs = [it] * (1 + len(iterates))
    args = [pt.reshape(B, n)] + [t.reshape(B, n) for t in iterates]
    out_specs = [it]
    out_shape = [jax.ShapeDtypeStruct((B, n), pt.dtype)]
    aliases = {}
    if fold:
        eta = coef.shape[0]
        acc_spec = pl.BlockSpec((eta, bb, blk), lambda b, i: (0, b, i))
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        args.insert(0, coef.reshape(-1))
        out_specs.append(acc_spec)
        out_shape.append(jax.ShapeDtypeStruct((eta, B, n), pt.dtype))
        if has_acc:
            in_specs.append(acc_spec)
            args.append(acc.reshape(eta, B, n))
            aliases = {len(args) - 1: 1}
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(B, bb), pl.cdiv(n, blk)),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="cheb_step",
    )(*args)
    tk = outs[0].reshape(batch_shape + (n,))
    if not fold:
        return tk
    return tk, outs[1].reshape((eta,) + batch_shape + (n,))
