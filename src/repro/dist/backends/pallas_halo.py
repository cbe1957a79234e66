"""'pallas_halo' execution backend: sharded Block-ELL + fused Pallas kernels
with boundary-row ("halo") exchange.

This backend unites the two fastest paths in the registry:

* the `pallas` backend's hot loop — Block-ELL SpMV + the fused Chebyshev
  step kernel (`kernels.ops.fused_cheb_recurrence`), one HBM round-trip per
  order — but run *per shard* inside a shard_map;
* the `halo` backend's distribution strategy — a block-tridiagonal partition
  of a banded (spatially sorted) P over a 1-D device mesh with ring
  neighbour exchange per Chebyshev order.

Where `halo` ships each shard's **entire** block (nl values) to both
neighbours per order, this backend ships only the **boundary rows** that the
neighbour actually reads: the halo width `h` is the bandwidth of the
off-diagonal coupling blocks, so per order each shard sends 2·h values
instead of 2·nl.  That is the TPU analog of the paper's accounting — one
scalar per directed edge per order, 2K|E| messages per application
(Section IV-B) — with the intra-shard edges folded into the local Block-ELL
SpMV and only the cut edges crossing the network.

Per-shard structure (shard s owns rows [s·nl, (s+1)·nl)):

    y_s = D_s x_s  +  L_s x_{s-1}[-h:]  +  R_s x_{s+1}[:h]

`D_s` is the shard's diagonal block in Block-ELL form driven through the
Pallas SpMV kernel; `L_s`/`R_s` are the (nl, h) boundary couplings applied
as small dense matmuls to the halo rows received from the ring neighbours.

Communication per application: K orders x 2 ppermutes of an (h,)-block
(forward/gram; (eta, h) for the adjoint; (..., h) tiles for batched
signals — the round count is batch-invariant, only the tile grows) —
measurable with :mod:`repro.dist.commstats` and compared against the
paper's closed form in ``benchmarks/bench_scaling.py``.

Latency structure (docs/ARCHITECTURE.md "Perf accounting"): the per-order
matvec is an explicit **interior/boundary split** — the boundary-tile
ppermutes are issued first, the interior Block-ELL SpMV (no remote data)
runs while they are in flight, and the received halo rows are applied on
arrival, so the exchange hides behind interior compute instead of
serializing in front of it.  The whole per-shard recurrence runs on the
shard's Block-ELL padded domain (padded once on entry, cropped once on
exit — no per-order pad/crop traffic), and on a 1-shard mesh, where the
exchange is a no-op, the matvec record carries its Block-ELL for the
single-launch `cheb_sweep` kernel so the entire K-order loop collapses
into one `pallas_call`.  :func:`build` hands this as a local form to the
plan body (`repro.dist.operator.plan_from_form`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ...core import chebyshev as cheb
from ...core import graph as graphmod
from ...kernels import ops
from .. import faults, quantize
from ..sharding import auto_mesh
from . import register_backend
from .halo import (BandedPartition, _coupling_bandwidth, pad_signal,
                   partition_banded)

Array = jax.Array


# ---------------------------------------------------------------------------
# Sharded Block-ELL partition
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardedBlockELL:
    """Per-shard Block-ELL diagonal blocks + dense boundary couplings.

    blocks:  (S, nrb, slots, br, bc) per-shard Block-ELL values of D_s
    indices: (S, nrb, slots) int32 column-block index per slot
    mask:    (S, nrb, slots) bool slot validity
    left:    (S, nl, h) coupling of shard s's rows to the *last* h columns
             of shard s-1 (zero for s = 0)
    right:   (S, nl, h) coupling of shard s's rows to the *first* h columns
             of shard s+1 (zero for s = S-1)
    n:       logical (unpadded) global size; S * nl >= n
    n_local: rows per shard (nl)
    halo:    boundary bandwidth h (rows exchanged per direction per order)
    """

    blocks: Array
    indices: Array
    mask: Array
    left: Array
    right: Array
    n: int
    n_local: int
    halo: int

    @property
    def n_shards(self) -> int:
        return self.blocks.shape[0]

    @property
    def n_padded(self) -> int:
        """Global padded signal size consumed by the plan (S * nl);
        `halo.pad_signal` reads this, so the partition is passed to it
        directly."""
        return self.n_shards * self.n_local

    @property
    def nnz_blocks(self) -> int:
        return int(np.asarray(self.mask).sum())


def partition_block_ell(
    P_dense: np.ndarray,
    n_shards: int,
    block: Tuple[int, int] = (8, 128),
    max_slots: Optional[int] = None,
) -> Tuple[ShardedBlockELL, float]:
    """Split P into per-shard Block-ELL diagonals + boundary couplings.

    Returns (partition, leak); `leak` is the Frobenius norm of entries
    outside the block-tridiagonal band (see `halo.partition_banded` — must
    be ~0 for exactness, use `graph.spatial_sort` first).  ``max_slots``
    bounds the uniform slot count and *raises*
    `repro.dist.partition.OverfullSlotsError` when a row block needs more —
    never truncates (dropped blocks would be silently wrong matvecs).
    """
    banded, leak = partition_banded(np.asarray(P_dense), n_shards)
    diag = np.asarray(banded.diag)
    left = np.asarray(banded.left)
    right = np.asarray(banded.right)
    nl = banded.n_local
    h = _coupling_bandwidth(left, right)

    cells = [graphmod.to_block_ell(diag[s], block) for s in range(n_shards)]
    slots = max(c.blocks.shape[1] for c in cells)
    if max_slots is not None and slots > max_slots:
        from ..partition import OverfullSlotsError

        raise OverfullSlotsError(
            f"a row block couples {slots} column blocks but the uniform "
            f"slot budget is {max_slots} — refusing to truncate (silently "
            "dropped blocks = silently wrong matvecs); raise max_slots or "
            "shrink the column block")
    blocks, indices, mask = [], [], []
    for c in cells:
        pad = slots - c.blocks.shape[1]
        blocks.append(np.pad(np.asarray(c.blocks),
                             ((0, 0), (0, pad), (0, 0), (0, 0))))
        indices.append(np.pad(np.asarray(c.indices), ((0, 0), (0, pad))))
        mask.append(np.pad(np.asarray(c.mask), ((0, 0), (0, pad))))
    return (
        ShardedBlockELL(
            blocks=jnp.asarray(np.stack(blocks)),
            indices=jnp.asarray(np.stack(indices)),
            mask=jnp.asarray(np.stack(mask)),
            left=jnp.asarray(left[:, :, nl - h:]),
            right=jnp.asarray(right[:, :, :h]),
            n=banded.n,
            n_local=nl,
            halo=h,
        ),
        leak,
    )


# ---------------------------------------------------------------------------
# Per-shard matvec (runs inside shard_map)
# ---------------------------------------------------------------------------
def _halo_row_matvec(local_A: graphmod.BlockELL, left: Array, right: Array,
                     nl: int, h: int, axis: str, use_pallas,
                     vmem_budget=None, n_shards=None,
                     exchange_dtype: str = "f32",
                     error_feedback: bool = True,
                     sweep_dtype: Optional[str] = None,
                     fault_spec=None, degradation: str = "zero_fill"):
    """Interior/boundary-split matvec along the last axis of x.

    x: (..., pnl) local block on the shard's **Block-ELL padded domain**
    (pnl = local_A.padded_n; callers pad once per application, not per
    order — rows past nl are zero and stay zero).  left/right are the
    boundary couplings row-padded to (pnl, h).  Per call:

    1. **boundary tiles encoded and on the wire first** — each shard's
       first/last h *logical* entries are compressed to `exchange_dtype`
       (`repro.dist.quantize`: identity for f32, truncating cast for
       bf16, per-tile-scale int8 with the scale bitcast-packed into the
       same wire buffer) and ppermute to the ring neighbours (the only
       inter-shard traffic — a (..., h) tile, so B batched signals ship
       (B, h) per direction in the same exchange round);
    2. **interior compute while the exchange is in flight** — the Pallas
       Block-ELL SpMV over the shard's diagonal block reads no remote
       data, so it overlaps the collective (batched tile path: one
       structure sweep for the whole batch);
    3. **decode + boundary coupling on arrival** — the received tiles
       widen back to the compute dtype, then two small (pnl, h) dense
       products.

    Returns a `core.chebyshev.LocalMatvec`.  Under
    ``exchange_dtype="int8"`` with ``error_feedback=True`` on a real
    multi-shard axis, it follows the dual-signature stateful protocol
    (see `halo._halo_matvec`): ``mv(x)`` stays stateless (plain
    quantize), ``mv(x, state) -> (y, state)`` threads the per-tile
    quantization residuals across orders, and its ``init_state(x)``
    builds the zero residuals.

    The ring wraps; the first/last shard's out-of-range contribution is
    killed by the zero left/right coupling blocks.  On a 1-shard mesh the
    exchange is a no-op and the record carries `local_A` as its
    `block_ell`, so `ops.fused_cheb_recurrence` / the Section-V solvers
    collapse the whole iteration into a single-launch sweep kernel (the
    couplings are identically zero there), with `vmem_budget` and
    `sweep_dtype` (the mixed-precision scratch mode) for those kernels.
    """
    size = n_shards if n_shards is not None else jax.lax.axis_size(axis)
    dt = quantize.validate_exchange_dtype(exchange_dtype)
    inj = faults.make_injector(fault_spec, degradation, axis, size > 1)
    use_ef = dt == "int8" and error_feedback and size > 1

    def _run(x, state):
        with obs.scope("exchange"):
            head = x[..., :h]
            tail = x[..., nl - h:nl]
        if inj is not None:
            k, carried, ef_state = state
        else:
            ef_state = state
        if size > 1:
            with obs.scope("exchange"):
                if ef_state is None:
                    wire_tail = quantize.encode(tail, dt)
                    wire_head = quantize.encode(head, dt)
                    new_ef = None
                else:
                    r_tail, r_head = ef_state
                    wire_tail, r_tail = quantize.ef_encode(tail, r_tail, dt)
                    wire_head, r_head = quantize.ef_encode(head, r_head, dt)
                    new_ef = (r_tail, r_head)
                # (1) boundary-row exchange: shard s receives s-1's tail
                # (read by `left`) and s+1's head (read by `right`); one
                # ppermute per direction keeps measured rounds at the
                # paper's 2K|E|
                from_left = jax.lax.ppermute(
                    wire_tail, axis,
                    perm=[(i, (i + 1) % size) for i in range(size)])
                from_right = jax.lax.ppermute(
                    wire_head, axis,
                    perm=[(i, (i - 1) % size) for i in range(size)])
            # (2) interior Block-ELL SpMV — overlaps the exchange
            y = ops.spmv(local_A, x, use_pallas=use_pallas)
            # (3) decode + boundary couplings on arrival; injected faults
            # perturb only what the receiver consumes — the wire traffic
            # above is already committed
            with obs.scope("exchange"):
                if inj is not None:
                    from_left = inj.wire(from_left, k, 0, dt)
                    from_right = inj.wire(from_right, k, 1, dt)
                from_left = quantize.decode(from_left, dt, x.dtype)
                from_right = quantize.decode(from_right, dt, x.dtype)
                if inj is not None:
                    c_l, c_r = carried
                    from_left, c_l = inj.recv(from_left, c_l, k, 0)
                    from_right, c_r = inj.recv(from_right, c_r, k, 1)
                    new_state = (k + 1, (c_l, c_r), new_ef)
                else:
                    new_state = new_ef
        else:
            from_left, from_right = tail, head
            new_state = state
            y = ops.spmv(local_A, x, use_pallas=use_pallas)
        with obs.scope("exchange"):
            y = y + jnp.einsum("ij,...j->...i", left, from_left)
            y = y + jnp.einsum("ij,...j->...i", right, from_right)
        return y, new_state

    if inj is not None:
        def init_state(x):
            tail = x[..., nl - h:nl]
            head = x[..., :h]
            ef0 = ((quantize.ef_init(tail), quantize.ef_init(head))
                   if use_ef else None)
            return (inj.init_round(), inj.init_carried((tail, head)), ef0)
    elif use_ef:
        def init_state(x):
            return (quantize.ef_init(x[..., nl - h:nl]),
                    quantize.ef_init(x[..., :h]))
    else:
        init_state = None

    def mv(x, state=None):
        if state is None:
            if inj is not None:
                return _run(x, init_state(x))[0]
            return _run(x, None)[0]
        return _run(x, state)

    return cheb.LocalMatvec(mv, block_ell=local_A if size == 1 else None,
                            vmem_budget=vmem_budget, sweep_dtype=sweep_dtype,
                            init_state=init_state)


def pallas_halo_bytes_per_apply(parts: ShardedBlockELL, K: int, eta: int = 1,
                                dtype_bytes: int = 4,
                                exchange_dtype: Optional[str] = None) -> int:
    """Collective-traffic model for one application: per order each shard
    sends its h boundary rows left+right; K orders, S shards.  Since the
    interior/boundary split, `halo.halo_bytes_per_apply` follows the same
    boundary-tile formula (it used to ship the full nl block); this one
    reads the width off a `ShardedBlockELL`, that one off a
    `BandedPartition`.  With `exchange_dtype` given, the per-row wire
    width comes from `quantize.tile_wire_bytes` (4h / 2h / h + 4 bytes
    for f32 / bf16 / int8+packed-scale) instead of ``h * dtype_bytes``."""
    if exchange_dtype is not None:
        row = quantize.tile_wire_bytes(parts.halo, exchange_dtype)
    else:
        row = parts.halo * dtype_bytes
    return 2 * K * parts.n_shards * eta * row


# ---------------------------------------------------------------------------
# Plan builder
# ---------------------------------------------------------------------------
@register_backend("pallas_halo")
def build(op, *, mesh=None, partition=None, axis: Optional[str] = None,
          allow_leak: bool = False, block: Tuple[int, int] = (8, 128),
          use_pallas: Optional[bool] = None,
          vmem_budget: Optional[int] = None,
          exchange_dtype: str = "f32", error_feedback: bool = True,
          sweep_dtype: Optional[str] = None,
          partition_method: str = "bfs",
          fault_spec=None, degradation: str = "zero_fill", **options):
    """Build an ExecutionPlan running the fused Pallas Chebyshev recurrence
    per shard with boundary-row halo exchange.

    Requires a dense, banded P (spatially sorted sensor graph) or a
    precomputed `partition=` (a `ShardedBlockELL`, or a `halo.
    BandedPartition` which is converted).  `partition="general"` (or a
    `repro.dist.partition.GeneralPartition`) switches to the edge-cut
    exchange plan for arbitrary sparse graphs — `partition_method`
    ("bfs" | "spectral") picks the partitioner when the string form is
    used.  Without `mesh=`, a 1-D "graph" mesh over every visible
    device is built.  `use_pallas` follows the
    `kernels.ops` dispatch policy (None: native on TPU, jnp oracle on CPU);
    `vmem_budget` overrides the single-launch sweep kernel's VMEM guard
    (`ops.DEFAULT_SWEEP_VMEM_BUDGET`) on 1-shard meshes, where the whole
    per-shard recurrence collapses into one `cheb_sweep` launch.

    ``exchange_dtype`` ("f32" | "bf16" | "int8") sets the wire precision
    of the boundary tiles and ``error_feedback`` (int8 only) threads the
    quantization residual across orders — see `repro.dist.quantize`.
    ``sweep_dtype`` (None/"f32" or "bf16") selects the mixed-precision
    scratch mode of the single-launch sweep kernels; the plan's
    ``sweep_vmem_bytes`` guard value is recomputed from the actual
    scratch dtype, so bf16 roughly doubles the admissible tile.
    """
    from ..operator import LocalForm, plan_from_form
    from ..partition import build_general_plan, resolve_partition_arg

    quantize.validate_exchange_dtype(exchange_dtype)
    faults.validate_degradation(degradation)
    fault_spec = faults.resolve_fault_spec(fault_spec)
    mesh = auto_mesh(mesh)
    axis = axis or mesh.axis_names[0]
    n_shards = int(mesh.shape[axis])
    general = resolve_partition_arg(op, partition, n_shards, block=block,
                                    method=partition_method)
    if general is not None:
        return build_general_plan(op, general, mesh, axis,
                                  interior="block_ell",
                                  use_pallas=use_pallas,
                                  vmem_budget=vmem_budget,
                                  sweep_dtype=sweep_dtype,
                                  exchange_dtype=exchange_dtype,
                                  error_feedback=error_feedback,
                                  fault_spec=fault_spec,
                                  degradation=degradation,
                                  backend_name="pallas_halo")
    if isinstance(partition, str):
        partition = None
    leak = 0.0
    if partition is None:
        if callable(op.P):
            raise ValueError("pallas_halo backend needs a dense P or "
                             "partition=")
        partition, leak = partition_block_ell(np.asarray(op.P), n_shards,
                                              block)
        if leak > 1e-10 and not allow_leak:
            raise ValueError(
                f"P is not block-tridiagonal under {n_shards} shards "
                f"(leak={leak:.3e}); spatial_sort the graph first, pass "
                "allow_leak=True, or use backend='allgather'")
    elif isinstance(partition, BandedPartition):
        repacked, leak = partition_block_ell(
            np.asarray(_banded_to_dense(partition)), partition.n_shards,
            block)
        partition = repacked
    parts = partition
    if parts.n_shards != n_shards:
        raise ValueError(f"partition has {parts.n_shards} shards but mesh "
                         f"axis {axis!r} has {n_shards}")
    n, nl, h = parts.n, parts.n_local, parts.halo
    pnl = parts.blocks.shape[1] * parts.blocks.shape[3]
    left_p = ops.pad_trailing(parts.left.swapaxes(-1, -2),
                              pnl).swapaxes(-1, -2)
    right_p = ops.pad_trailing(parts.right.swapaxes(-1, -2),
                               pnl).swapaxes(-1, -2)
    band = graphmod.block_ell_band(parts.indices, parts.mask,
                                   parts.blocks.shape[3:])

    def local(mine):
        blocks, indices, mask, left, right = mine
        local_A = graphmod.BlockELL(blocks=blocks, indices=indices,
                                    mask=mask, n=nl, band=band)
        return _halo_row_matvec(local_A, left, right, nl, h, axis,
                                use_pallas, vmem_budget, n_shards,
                                exchange_dtype, error_feedback, sweep_dtype,
                                fault_spec, degradation)

    # The whole per-shard recurrence runs on the shard's Block-ELL padded
    # domain: padded once on entry and cropped once on exit (padded rows
    # stay zero: zero signal, zero blocks, zero couplings).  A 1-shard mesh
    # needs no collectives and no shard_map: its matvec, built once on the
    # concrete local Block-ELL, carries it for the single-launch sweep.
    if n_shards == 1:
        places = dict(enter=lambda x, mine: ops.pad_trailing(x, pnl),
                      leave=lambda y, mine: ops.crop(y, n))
    else:
        places = dict(glob_in=lambda x: pad_signal(x, parts),
                      enter=lambda x, mine: ops.pad_trailing(x, pnl),
                      leave=lambda y, mine: ops.crop(y, nl),
                      glob_out=lambda y: ops.crop(y, n),
                      mesh=mesh, axis=axis)
    form = LocalForm(
        local=local,
        recurrence=functools.partial(ops.fused_cheb_recurrence,
                                     use_pallas=use_pallas),
        arrays=(parts.blocks, parts.indices, parts.mask, left_p, right_p),
        **places)
    return plan_from_form(op, "pallas_halo", form, info={
        "mesh_axis": axis,
        "n_shards": n_shards,
        "n_local": nl,
        "n_local_padded": pnl,
        "halo_width": h,
        "partition": "banded",
        "partition_leak": leak,
        # one exchange round = the left+right ppermute pair (commstats
        # divides the measured ppermute tally by this)
        "exchange_collectives_per_round": 2,
        "block": block,
        "nnz_blocks": parts.nnz_blocks,
        "blockell_fill": graphmod.block_ell_fill(parts.blocks),
        "spmv_band": band,
        "exchange_dtype": exchange_dtype,
        "error_feedback": bool(error_feedback),
        "fault_spec": faults.spec_info(fault_spec),
        "degradation": degradation,
        "fault_key": faults.fault_key(fault_spec, degradation),
        "sweep_dtype": sweep_dtype or "f32",
        "sweep_vmem_bytes": ops.cheb_sweep_vmem_bytes(
            parts.blocks.shape[1:], pnl, op.eta, scratch_dtype=sweep_dtype),
        "halo_bytes_per_apply": pallas_halo_bytes_per_apply(
            parts, op.K, 1, exchange_dtype=exchange_dtype),
        "halo_bytes_per_adjoint": pallas_halo_bytes_per_apply(
            parts, op.K, op.eta, exchange_dtype=exchange_dtype),
    })


def _banded_to_dense(parts: BandedPartition) -> np.ndarray:
    """Reassemble the dense (padded) P from a halo `BandedPartition`."""
    S, nl = parts.n_shards, parts.n_local
    diag = np.asarray(parts.diag)
    left = np.asarray(parts.left)
    right = np.asarray(parts.right)
    out = np.zeros((S * nl, S * nl), diag.dtype)
    for s in range(S):
        r = slice(s * nl, (s + 1) * nl)
        out[r, r] = diag[s]
        if s > 0:
            out[r, (s - 1) * nl: s * nl] = left[s]
        if s < S - 1:
            out[r, (s + 1) * nl: (s + 2) * nl] = right[s]
    return out[: parts.n, : parts.n]
