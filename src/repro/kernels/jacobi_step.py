"""Fused (accelerated-)Jacobi update Pallas kernel — Section V-A/V-B.

One iteration of the Section-V solvers after the matvec ``qx = Q @ x``:

    x_next = w * (x + D^{-1} (y - qx)) - s * x_prev

with ``w = 1, s = 0`` the plain Jacobi sweep (Eq. (24)) and the per-
iteration Chebyshev-accelerated weights of Eq. (25) otherwise.  Fusing the
five elementwise reads/writes into one pass keeps the iterate traffic at a
single HBM round-trip per solver round — the same treatment `cheb_step`
gives the Section-IV recurrence, extended to the Section-V solvers (see
docs/ARCHITECTURE.md "Perf accounting").  As with `cheb_step`, the
single-launch `cheb_sweep.jacobi_sweep` kernel subsumes this one when the
whole solve fits in VMEM; this per-round kernel is the guard fallback and
the collective-bearing sharded path.

Tiling mirrors `cheb_step` (`cheb_step.batch_tiles`): leading batch dims
flatten onto sublanes of (bb, blk) tiles over a ``cdiv`` grid (one kernel
launch advances the whole (..., n) batch one round), and per-shard sizes
(the `pallas_halo` backend runs this inside shard_map) need not be 128
multiples.  The acceleration weights (w, s) vary per iteration and ride in
SMEM so the kernel stays trace-once inside `lax.scan`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cheb_step import batch_tiles

Array = jax.Array


def _jacobi_step_kernel(ws_ref, qx_ref, x_ref, xp_ref, y_ref, invd_ref,
                        out_ref):
    w = ws_ref[0]
    s = ws_ref[1]
    out_ref[...] = (w * (x_ref[...] + invd_ref[...]
                         * (y_ref[...] - qx_ref[...])) - s * xp_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def jacobi_step(
    qx: Array,
    x: Array,
    x_prev: Array,
    y: Array,
    inv_d: Array,
    *,
    w,
    s,
    interpret: bool = False,
) -> Array:
    """Returns ``w * (x + inv_d * (y - qx)) - s * x_prev``.

    qx/x/x_prev: (..., n) — any n.  y: (..., n) with the same batch shape
    or unbatched (n,); inv_d likewise (typically the (n,) reciprocal
    diagonal — zero on padded/virtual rows, which keeps them exactly
    zero).  w/s: scalars, traced or concrete (the accelerated weights
    change per scan step); they ride in SMEM.
    """
    n = x.shape[-1]
    batch_shape = x.shape[:-1]
    qx, x_prev = (jnp.broadcast_to(a, x.shape) for a in (qx, x_prev))
    B = x.size // n
    bb, blk = batch_tiles(B, n)
    it = pl.BlockSpec((bb, blk), lambda b, i: (b, i))
    # y / inv_d keep their own (possibly unbatched) row count; a shared
    # row is one (1, blk) tile pinned at row 0
    shared = pl.BlockSpec((1, blk), lambda b, i: (0, i))

    def operand(a):
        a2 = a.reshape(-1, n)
        if a2.shape[0] == 1:
            return a2, shared
        return jnp.broadcast_to(a, x.shape).reshape(B, n), it

    y2, y_spec = operand(y)
    d2, d_spec = operand(inv_d)
    ws = jnp.stack([jnp.asarray(w, x.dtype), jnp.asarray(s, x.dtype)])
    out = pl.pallas_call(
        _jacobi_step_kernel,
        grid=(pl.cdiv(B, bb), pl.cdiv(n, blk)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  it, it, it, y_spec, d_spec],
        out_specs=it,
        out_shape=jax.ShapeDtypeStruct((B, n), x.dtype),
        interpret=interpret,
    )(ws, qx.reshape(B, n), x.reshape(B, n), x_prev.reshape(B, n), y2, d2)
    return out.reshape(batch_shape + (n,))
