"""Single-launch persistent Chebyshev / Jacobi sweep Pallas kernels.

The per-order hot path (`bcsr_spmv.block_ell_spmv_batched` + `cheb_step`)
launches two kernels per Chebyshev order and round-trips the iterates
``t_k, t_{k-1}, acc`` through HBM between them: O(K * (3 + eta) * n)
iterate traffic for a K-order union.  The sweep kernels here move the
order loop *inside* the kernel body instead:

  * `cheb_sweep` — the full Algorithm-1 recurrence as ONE `pallas_call`.
    A `lax.fori_loop` over the K orders runs in-kernel; ``t_k / t_{k-1}``
    live in a VMEM ping-pong pair across all orders, the accumulator is
    the (VMEM-resident) output ref, and the Block-ELL blocks stay
    resident.  Iterate HBM traffic drops to one load (x) + one store
    (acc) per application, and kernel launches from 2K to 1.
  * `jacobi_sweep` — the Section-V analog: a whole (accelerated-)Jacobi
    solve of ``den(P) x = b`` in one launch, the Horner evaluation of
    ``den(P) x`` (deg(den) in-kernel SpMV passes) and the Eq. (24)/(25)
    update fused into the last pass, iterates pinned in VMEM for all
    ``n_iters`` rounds.

Everything must fit in VMEM at once — iterates, accumulator, and the
Block-ELL structure — so the `kernels.ops` dispatchers guard on the tiled
footprint of :func:`cheb_sweep_buffers` / :func:`jacobi_sweep_buffers`
and fall back to the per-order kernels when the budget is exceeded (see
``docs/ARCHITECTURE.md`` "Perf accounting", and `ops.fused_cheb_sweep` /
`ops.fused_jacobi_sweep` for the dispatch).

Layout: iterates are (n, B) — vertices on sublanes, the batch on the
128 lanes (padded up to whole vregs).  Each order is one pass over the
row blocks: a row block's SpMV gathers (bc, B) tiles at bc-aligned
sublane offsets, then the recurrence and the accumulate run on that
(br, B) row block and store it at a br-aligned offset, overwriting
t_{k-2} in place.  The coefficient table, the Jacobi weights and the
Block-ELL column indices ride flat in SMEM.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .layout import lane_pad, mxu_precision, pad_lanes

Array = jax.Array

#: Sanctioned sweep scratch dtypes.  "bf16" is the mixed-precision mode:
#: the x operand, the t_k pair and the Block-ELL blocks live in bfloat16
#: VMEM (halving those terms of the guarded footprint), while every
#: update runs in f32 — the MXU products via
#: ``preferred_element_type=jnp.float32`` in :func:`_row_product`, the
#: recurrence and the accumulator by explicit widening casts.
SCRATCH_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def cheb_sweep_buffers(n: int, batch: int, eta: int, blocks_shape,
                       scratch_dtype, dtype=jnp.float32):
    """Every VMEM buffer one `cheb_sweep` launch holds: the x operand and
    the t_k ping-pong pair at the scratch dtype, the eta accumulator
    planes at `dtype`, and the Block-ELL blocks.  The (K+1, eta) table
    and the column indices live in SMEM."""
    return ([((n, batch), scratch_dtype)] * 3
            + [((n, batch), dtype)] * eta
            + [(tuple(blocks_shape), scratch_dtype)])


def jacobi_sweep_buffers(n: int, batch: int, n_den: int, blocks_shape,
                         scratch_dtype, dtype=jnp.float32):
    """Every VMEM buffer one `jacobi_sweep` launch holds: b, D^{-1} and
    the x ping-pong pair (which x0 enters through, aliased) at `dtype`,
    the Horner pair at the scratch dtype (only when deg(den) >= 2), and
    the Block-ELL blocks."""
    n_h = 2 if n_den > 2 else 0
    return ([((n, batch), dtype)] * 4 + [((n, batch), scratch_dtype)] * n_h
            + [(tuple(blocks_shape), scratch_dtype)])


def _row_product(idx_ref, blocks_ref, src, rb, *, slots: int, bc: int):
    """Row block `rb` of the in-kernel Block-ELL SpMV ``A @ src``.

    src: a (n, B) VMEM ref view, vertices on sublanes and the batch on
    lanes.  Every slot gathers the (bc, B) tile of its scalar-prefetched
    column block at a bc-aligned sublane offset and hits it with one
    (br, bc) x (bc, B) MXU product; padded slots hold zero blocks.
    Returns the (br, B) f32 product.
    """
    br = blocks_ref.shape[2]

    def slot_body(s, acc):
        start = pl.multiple_of(idx_ref[rb * slots + s] * bc, bc)
        blk = blocks_ref[rb, s]
        tile = src[pl.ds(start, bc), :].astype(blk.dtype)
        return acc + jnp.dot(blk, tile, preferred_element_type=jnp.float32,
                             precision=mxu_precision(blk.dtype))

    return jax.lax.fori_loop(0, slots, slot_body,
                             jnp.zeros((br, src.shape[-1]), jnp.float32))


def _rows(rb, br: int):
    return pl.ds(pl.multiple_of(rb * br, br), br)


def _cheb_sweep_kernel(idx_ref, coef_ref, blocks_ref, x_ref, acc_ref, t_ref,
                       *, K: int, alpha: float, nrb: int, slots: int,
                       br: int, bc: int):
    """t_ref: (2, n, B) ping-pong pair; t_ref[k % 2] holds t_k.  Each row
    block of order k reads P t_{k-1} (the other half, whole) and overwrites
    t_{k-2} with t_k row by row: t_{k-2} is only read at its own rows."""
    eta = acc_ref.shape[0]
    prod = functools.partial(_row_product, idx_ref, blocks_ref,
                             slots=slots, bc=bc)
    f32 = jnp.float32
    out_dt = acc_ref.dtype

    # orders 0 and 1: acc = (c_0/2) x + c_1 t_1,  t_1 = (P x)/alpha - x
    def first_row(rb, _):
        r = _rows(rb, br)
        x = x_ref[r, :].astype(f32)
        t1 = prod(x_ref, rb) / alpha - x
        t_ref[0, r, :] = x_ref[r, :]
        t_ref[1, r, :] = t1.astype(t_ref.dtype)
        for j in range(eta):
            acc_ref[j, r, :] = (0.5 * coef_ref[j] * x
                                + coef_ref[eta + j] * t1).astype(out_dt)
        return 0

    jax.lax.fori_loop(0, nrb, first_row, 0)

    # t_k = (2/alpha) P t_{k-1} - 2 t_{k-1} - t_{k-2}      (Algorithm 1 l.9)
    def order_body(k, _):
        cur = k % 2
        prev = 1 - cur

        def row(rb, _):
            r = _rows(rb, br)
            tk = ((2.0 / alpha) * prod(t_ref.at[prev], rb)
                  - 2.0 * t_ref[prev, r, :].astype(f32)
                  - t_ref[cur, r, :].astype(f32))
            t_ref[cur, r, :] = tk.astype(t_ref.dtype)
            for j in range(eta):
                acc_ref[j, r, :] = acc_ref[j, r, :] + (
                    coef_ref[k * eta + j] * tk).astype(out_dt)
            return 0

        jax.lax.fori_loop(0, nrb, row, 0)
        return 0

    jax.lax.fori_loop(2, K + 1, order_body, 0)


def _vmem():
    return pl.BlockSpec(memory_space=pltpu.VMEM)


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "interpret", "scratch_dtype"))
def cheb_sweep(
    blocks: Array,
    indices: Array,
    x: Array,
    coeffs: Array,
    *,
    alpha: float,
    interpret: bool = False,
    scratch_dtype: str = "f32",
) -> Array:
    """Full K-order shifted-Chebyshev recurrence in one kernel launch.

    blocks/indices: Block-ELL structure as in
    `bcsr_spmv.block_ell_spmv_batched`.
    x: (..., n) with n the Block-ELL padded size (n = nrb * br); leading
    batch dims flatten to one VMEM-resident (B, n) iterate that advances
    through all orders without touching HBM.  coeffs: (eta, K+1), K >= 1.
    Returns (..., eta, n) — the same contract as the per-order path
    (`ops.fused_cheb_apply`), whose `cheb_step` docs and the
    ``docs/ARCHITECTURE.md`` "Perf accounting" section give the HBM
    round-trip model this kernel collapses.

    scratch_dtype: "f32" (default) or "bf16" — the mixed-precision mode
    of :data:`SCRATCH_DTYPES`: iterates, SpMV product, the x operand and
    the Block-ELL blocks are cast to bfloat16, the coefficient table and
    the (B, eta, n) accumulator output stay at x's dtype with f32 MXU
    accumulation (`preferred_element_type`).
    """
    if scratch_dtype not in SCRATCH_DTYPES:
        raise ValueError(f"scratch_dtype must be one of "
                         f"{tuple(SCRATCH_DTYPES)}, got {scratch_dtype!r}")
    sdt = SCRATCH_DTYPES[scratch_dtype]
    nrb, slots, br, bc = blocks.shape
    n = x.shape[-1]
    eta, K1 = coeffs.shape
    batch_shape = x.shape[:-1]
    B = x.size // n
    Bp = lane_pad(B)
    # (B, n) -> (n, Bp): vertices on sublanes, so row-block stores land at
    # br-aligned sublane offsets and column gathers at bc-aligned ones
    with obs.scope("layout"):
        xt = pad_lanes(x.reshape(B, n).T, Bp).astype(sdt)
        # SMEM pads 2-D arrays to 128-word rows: the (K+1, eta)
        # order-major table and the (nrb, slots) indices ride flat
        coef_flat = jnp.asarray(coeffs, jnp.float32).T.reshape(-1)

    kernel = functools.partial(
        _cheb_sweep_kernel, K=K1 - 1, alpha=float(alpha),
        nrb=nrb, slots=slots, br=br, bc=bc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[_smem(), _vmem(), _vmem()],
        out_specs=_vmem(),
        scratch_shapes=[pltpu.VMEM((2, n, Bp), sdt)],    # t_k ping-pong
    )
    acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((eta, n, Bp), x.dtype),
        interpret=interpret,
    )(indices.reshape(-1), coef_flat, blocks.astype(sdt), xt)
    with obs.scope("layout"):
        return acc[..., :B].transpose(2, 0, 1).reshape(
            batch_shape + (eta, n))


def _jacobi_sweep_kernel(idx_ref, ws_ref, blocks_ref, b_ref, invd_ref,
                         x0_ref, x_ref, *h_refs, n_iters: int,
                         den: Tuple[float, ...], nrb: int, slots: int,
                         br: int, bc: int):
    """x_ref: (2, n, B) ping-pong output, aliased to x0_ref = (x0, x0);
    x_ref[t % 2] holds x_t and the round's last Horner stage overwrites
    x_{t-1} with x_{t+1} row by row.  h_refs: the (2, n, B) Horner pair,
    present when deg(den) >= 2."""
    del x0_ref  # the same buffer as x_ref
    prod = functools.partial(_row_product, idx_ref, blocks_ref,
                             slots=slots, bc=bc)
    deg = len(den) - 1
    wide = x_ref.dtype

    def round_body(t, _):
        cur = t % 2
        nxt = 1 - cur
        w = ws_ref[2 * t]
        s = ws_ref[2 * t + 1]
        # den(P) x by Horner, one row-blocked SpMV pass per degree:
        # h_1 = den[d] P x + den[d-1] x,  h_i = P h_{i-1} + den[d-i] x;
        # the last pass fuses the Eq. (24)/(25) update
        # x_next = w (x + D^{-1}(b - den(P) x)) - s x_prev
        for i in range(1, max(deg, 1) + 1):
            def stage_row(rb, _, i=i):
                r = _rows(rb, br)
                x = x_ref[cur, r, :]
                if deg == 0:
                    h = den[0] * x
                elif i == 1:
                    h = (den[deg] * prod(x_ref.at[cur], rb)
                         + den[deg - 1] * x)
                else:
                    h = (prod(h_refs[0].at[i % 2], rb).astype(wide)
                         + den[deg - i] * x)
                if i < deg:
                    h_refs[0][(i + 1) % 2, r, :] = h.astype(h_refs[0].dtype)
                else:
                    x_ref[nxt, r, :] = (
                        w * (x + invd_ref[r, :] * (b_ref[r, :] - h))
                        - s * x_ref[nxt, r, :])
                return 0

            jax.lax.fori_loop(0, nrb, stage_row, 0)
        return 0

    jax.lax.fori_loop(0, n_iters, round_body, 0)


@functools.partial(jax.jit,
                   static_argnames=("den", "interpret", "scratch_dtype"))
def jacobi_sweep(
    blocks: Array,
    indices: Array,
    b: Array,
    inv_d: Array,
    weights: Array,
    x0: Array,
    *,
    den: Tuple[float, ...],
    interpret: bool = False,
    scratch_dtype: str = "f32",
) -> Array:
    """Whole (accelerated-)Jacobi solve of den(P) x = b in one launch.

    b / x0: (..., n) at the Block-ELL padded size; inv_d broadcastable to
    them (zeros on padded rows keep those rows exactly zero, the repo-wide
    zero-padding convention).  weights: (n_iters, 2) per-round (w_t, s_t)
    schedule — all (1, 0) for plain Jacobi (Eq. (24)),
    `core.jacobi.cheb_jacobi_weights` for Eq. (25).  den: monomial
    coefficients of the split polynomial, low-degree-first (static).
    Returns x after n_iters rounds, shape (..., n).

    scratch_dtype: "f32" or "bf16" (:data:`SCRATCH_DTYPES`) — under bf16
    the x_prev / SpMV-product / Horner scratch and the streamed blocks
    halve, while the x iterate, b, D^{-1} and the Eq. (24)/(25) update
    stay at b's dtype.
    """
    if scratch_dtype not in SCRATCH_DTYPES:
        raise ValueError(f"scratch_dtype must be one of "
                         f"{tuple(SCRATCH_DTYPES)}, got {scratch_dtype!r}")
    sdt = SCRATCH_DTYPES[scratch_dtype]
    nrb, slots, br, bc = blocks.shape
    n = b.shape[-1]
    batch_shape = jnp.broadcast_shapes(b.shape, x0.shape)[:-1]
    full = batch_shape + (n,)
    B = 1
    for d in batch_shape:
        B *= d

    Bp = lane_pad(B)

    def lanes(a):  # (..., n) -> (n, Bp), the batch on lanes
        with obs.scope("layout"):
            return pad_lanes(jnp.broadcast_to(a, full).reshape(B, n).T, Bp)

    ws = jnp.asarray(weights, jnp.float32)
    n_iters = ws.shape[0]
    ws = ws.reshape(-1)                                  # (w_0, s_0, w_1, ...)
    den = tuple(float(c) for c in den)
    n_h = 2 if len(den) > 2 else 0

    kernel = functools.partial(
        _jacobi_sweep_kernel, n_iters=n_iters, den=den,
        nrb=nrb, slots=slots, br=br, bc=bc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[_smem()] + [_vmem()] * 4,
        out_specs=_vmem(),
        scratch_shapes=[pltpu.VMEM((2, n, Bp), sdt)] * (n_h // 2),
    )
    with obs.scope("layout"):
        x0t = jnp.stack([lanes(x0).astype(b.dtype)] * 2)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((2, n, Bp), b.dtype),
        input_output_aliases={5: 0},
        interpret=interpret,
    )(indices.reshape(-1), ws, blocks.astype(sdt), lanes(b), lanes(inv_d),
      x0t)
    with obs.scope("layout"):
        return out[n_iters % 2, :, :B].T.reshape(full)
