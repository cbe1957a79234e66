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
exchange is a no-op, the matvec is tagged for the single-launch
`cheb_sweep` kernel so the entire K-order loop collapses into one
`pallas_call`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ... import obs
from ...core import chebyshev as cheb
from ...core import graph as graphmod
from ...core.lasso import soft_threshold
from ...kernels import ops
from .. import faults, quantize
from ..sharding import ShardingRules, auto_mesh, make_rules
from . import register_backend
from .halo import (BandedPartition, _coupling_bandwidth, _sharded,
                   pad_signal, partition_banded)

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

    Under ``exchange_dtype="int8"`` with ``error_feedback=True`` on a
    real multi-shard axis, the closure follows the dual-signature
    stateful protocol (see `halo._halo_matvec`): ``mv(x)`` stays
    stateless (plain quantize), ``mv(x, state) -> (y, state)`` threads
    the per-tile quantization residuals across orders, and
    ``mv.init_state(x)`` builds the zero residuals.

    The ring wraps; the first/last shard's out-of-range contribution is
    killed by the zero left/right coupling blocks.  On a 1-shard mesh the
    exchange is a no-op and the returned closure is tagged with
    ``mv.block_ell`` so `ops.fused_cheb_recurrence` / the Section-V
    solvers collapse the whole iteration into a single-launch sweep
    kernel (the couplings are identically zero there); ``mv.sweep_dtype``
    forwards the mixed-precision scratch mode to those sweep kernels.
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

    def mv(x, state=None):
        if state is None:
            if inj is not None:
                return _run(x, mv.init_state(x))[0]
            return _run(x, None)[0]
        return _run(x, state)

    if inj is not None:
        def init_state(x):
            tail = x[..., nl - h:nl]
            head = x[..., :h]
            ef0 = ((quantize.ef_init(tail), quantize.ef_init(head))
                   if use_ef else None)
            return (inj.init_round(), inj.init_carried((tail, head)), ef0)

        mv.init_state = init_state
    elif use_ef:
        def init_state(x):
            return (quantize.ef_init(x[..., nl - h:nl]),
                    quantize.ef_init(x[..., :h]))

        mv.init_state = init_state
    if size == 1:
        mv.block_ell = local_A
        mv.vmem_budget = vmem_budget
        mv.sweep_dtype = sweep_dtype
    return mv


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
    from ..operator import ExecutionPlan

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
    # the shard's Block-ELL padded domain: the whole recurrence runs here,
    # padded once on entry and cropped once on exit (no per-order pads)
    pnl = parts.blocks.shape[1] * parts.blocks.shape[3]
    left_p = ops.pad_trailing(parts.left.swapaxes(-1, -2),
                              pnl).swapaxes(-1, -2)
    right_p = ops.pad_trailing(parts.right.swapaxes(-1, -2),
                               pnl).swapaxes(-1, -2)
    coeffs = op.coeffs
    lmax = op.lmax
    band = graphmod.block_ell_band(parts.indices, parts.mask,
                                   parts.blocks.shape[3:])

    def _mk_mv(blocks, indices, mask, left, right):
        local_A = graphmod.BlockELL(blocks=blocks[0], indices=indices[0],
                                    mask=mask[0], n=nl, band=band)
        return _halo_row_matvec(local_A, left[0], right[0], nl, h, axis,
                                use_pallas, vmem_budget, n_shards,
                                exchange_dtype, error_feedback, sweep_dtype,
                                fault_spec, degradation)

    info = {
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
    }

    if n_shards == 1:
        # A 1-shard mesh needs no collectives and no shard_map: build the
        # plan directly on the (concrete) local Block-ELL — the matvec's
        # `block_ell` tag holds plan-time constants, so the single-launch
        # sweep dispatch (and its eager-dense CPU oracle) engages exactly
        # as in the `pallas` backend, minus the shard_map trace overhead.
        return _build_single_shard(op, parts, pnl, left_p, right_p,
                                   use_pallas, vmem_budget, info,
                                   sweep_dtype)

    # PartitionSpecs through the logical-axis rules: every per-shard tensor
    # is sharded on its leading "vertex"-block dimension.  The shared _BASE
    # vocabulary maps "vertex" to the conventional "graph" mesh axis; a
    # mesh with a differently-named axis gets a local override.  Signals
    # carry leading batch dims ((..., N) contract), so their specs are
    # built per input rank: batch/eta axes replicate, vertex axis shards.
    rules = (make_rules(mesh) if axis == "graph"
             else ShardingRules(mapping={"vertex": axis}, mesh=mesh))
    vspec = rules.spec("vertex")
    mats = (parts.blocks, parts.indices, parts.mask, left_p, right_p)
    mat_specs = (vspec,) * 5

    def _sig_spec(ndim: int) -> P:
        return rules.spec(*([None] * (ndim - 1)), "vertex")

    def apply(f: Array) -> Array:
        def run(blocks, indices, mask, left, right, xl, c):
            mv = _mk_mv(blocks, indices, mask, left, right)
            out = ops.fused_cheb_recurrence(mv, ops.pad_trailing(xl, pnl),
                                            c, lmax, use_pallas=use_pallas)
            return ops.crop(out, nl)

        c2 = jnp.atleast_2d(jnp.asarray(coeffs, f.dtype))
        out = _sharded(run, mesh, mat_specs + (_sig_spec(f.ndim), P()),
                       _sig_spec(f.ndim + 1))(*mats,
                                              pad_signal(f, parts),
                                              c2)
        return ops.crop(out, n)

    def apply_adjoint(a: Array) -> Array:
        def run(blocks, indices, mask, left, right, al, c):
            mv = _mk_mv(blocks, indices, mask, left, right)
            out = cheb.cheb_apply_adjoint(mv, ops.pad_trailing(al, pnl),
                                          c, lmax)
            return ops.crop(out, nl)

        c = jnp.asarray(coeffs, a.dtype)
        out = _sharded(run, mesh, mat_specs + (_sig_spec(a.ndim), P()),
                       _sig_spec(a.ndim - 1))(*mats, pad_signal(a, parts), c)
        return ops.crop(out, n)

    def apply_gram(f: Array) -> Array:
        def run(blocks, indices, mask, left, right, xl, d):
            mv = _mk_mv(blocks, indices, mask, left, right)
            out = ops.fused_cheb_recurrence(mv, ops.pad_trailing(xl, pnl),
                                            d, lmax, use_pallas=use_pallas)
            return ops.crop(out[..., 0, :], nl)

        d = jnp.asarray(cheb.gram_coeffs(coeffs), f.dtype)[None]
        out = _sharded(run, mesh, mat_specs + (_sig_spec(f.ndim), P()),
                       _sig_spec(f.ndim))(*mats, pad_signal(f, parts), d)
        return ops.crop(out, n)

    def solve_lasso(y, mu, gamma, n_iters):
        from ...core.lasso import LassoResult, _mu_threshold

        def run(blocks, indices, mask, left, right, yl, c, thresh):
            mv = _mk_mv(blocks, indices, mask, left, right)
            # the whole ISTA loop runs on the padded Block-ELL domain;
            # padded rows stay identically zero (zero signal, zero blocks,
            # zero couplings), cropped once on the way out
            phi_y = ops.fused_cheb_recurrence(mv, ops.pad_trailing(yl, pnl),
                                              c, lmax, use_pallas=use_pallas)

            def body(a, _):
                back = cheb.cheb_apply_adjoint(mv, a, c, lmax)
                gram_a = ops.fused_cheb_recurrence(mv, back, c, lmax,
                                                   use_pallas=use_pallas)
                a_new = soft_threshold(a + gamma * (phi_y - gram_a), thresh)
                return a_new, None

            a0 = jnp.zeros_like(phi_y)
            a_star, _ = jax.lax.scan(body, a0, None, length=n_iters)
            y_star = cheb.cheb_apply_adjoint(mv, a_star, c, lmax)
            return a_star[..., :nl], y_star[..., :nl]

        c = jnp.asarray(coeffs, y.dtype)
        thresh = _mu_threshold(mu, op.eta, y.dtype, gamma)
        a_star, y_star = _sharded(
            run, mesh, mat_specs + (_sig_spec(y.ndim), P(), P()),
            (_sig_spec(y.ndim + 1), _sig_spec(y.ndim)),
        )(*mats, pad_signal(y, parts), c, thresh)
        return LassoResult(coeffs=a_star[..., :n], signal=y_star[..., :n],
                           objective=jnp.nan, n_iters=n_iters, fused=True)

    def matvec_runner(fn, signals, consts=()):
        # Section-V solver substrate: one shard_map running `fn` against
        # the per-shard Block-ELL matvec with boundary-rows-only halo
        # exchange — a solver round costs the same 2·h-row traffic as one
        # Chebyshev order.  Vertex-last signals shard (zero-padded tails
        # stay zero under the solvers' reciprocal-diagonal updates) and are
        # lifted to the shard's Block-ELL padded domain once per call, so
        # the iteration bodies run pad-free; every output's vertex axis is
        # cropped per shard, then to the logical n.  On a 1-shard mesh the
        # matvec carries its `block_ell` tag, so eligible solver bodies
        # collapse into the single-launch sweep kernels.
        padded = tuple(pad_signal(jnp.asarray(s), parts) for s in signals)
        local = tuple(
            jax.ShapeDtypeStruct(s.shape[:-1] + (pnl,), s.dtype)
            for s in padded)
        out_sds = jax.eval_shape(
            lambda *a: jax.tree.map(
                lambda o: o[..., :nl], fn(lambda v: v, *a)),
            *local, *consts)
        in_specs = (mat_specs
                    + tuple(_sig_spec(s.ndim) for s in padded)
                    + tuple(P() for _ in consts))
        out_specs = jax.tree.map(lambda sd: _sig_spec(len(sd.shape)),
                                 out_sds)

        def run(blocks, indices, mask, left, right, *rest):
            mv = _mk_mv(blocks, indices, mask, left, right)
            sigs = tuple(ops.pad_trailing(s, pnl) for s in rest[:len(padded)])
            outs = fn(mv, *sigs, *rest[len(padded):])
            return jax.tree.map(lambda o: ops.crop(o, nl), outs)

        outs = _sharded(run, mesh, in_specs, out_specs)(
            *mats, *padded, *consts)
        return jax.tree.map(lambda o: ops.crop(o, n), outs)

    return ExecutionPlan(
        op=op, backend="pallas_halo",
        apply=apply, apply_adjoint=apply_adjoint, apply_gram=apply_gram,
        solve_lasso_fn=solve_lasso,
        matvec_runner=matvec_runner,
        info=info,
    )


def _build_single_shard(op, parts, pnl, left_p, right_p, use_pallas,
                        vmem_budget, info, sweep_dtype=None):
    """The 1-shard degenerate of the pallas_halo plan: same partition, same
    matvec (the zero boundary couplings included, so `plan.info` and the
    byte models stay comparable), but no shard_map and a concrete
    Block-ELL — the single-launch sweep path of `kernels.ops` applies."""
    from ...core.lasso import LassoResult, _mu_threshold
    from ..operator import ExecutionPlan

    n, nl, h = parts.n, parts.n_local, parts.halo
    coeffs = op.coeffs
    lmax = op.lmax
    local_A = graphmod.BlockELL(blocks=parts.blocks[0],
                                indices=parts.indices[0],
                                mask=parts.mask[0], n=nl,
                                band=info["spmv_band"])
    mv = _halo_row_matvec(local_A, left_p[0], right_p[0], nl, h,
                          info["mesh_axis"], use_pallas, vmem_budget,
                          n_shards=1, sweep_dtype=sweep_dtype)

    def _pad(x):
        return ops.pad_trailing(jnp.asarray(x), pnl)

    def apply(f: Array) -> Array:
        c2 = jnp.atleast_2d(jnp.asarray(coeffs, f.dtype))
        out = ops.fused_cheb_recurrence(mv, _pad(f), c2, lmax,
                                        use_pallas=use_pallas)
        return ops.crop(out, n)

    def apply_adjoint(a: Array) -> Array:
        c = jnp.asarray(coeffs, a.dtype)
        return ops.crop(cheb.cheb_apply_adjoint(mv, _pad(a), c, lmax), n)

    def apply_gram(f: Array) -> Array:
        d = jnp.asarray(cheb.gram_coeffs(coeffs), f.dtype)[None]
        out = ops.fused_cheb_recurrence(mv, _pad(f), d, lmax,
                                        use_pallas=use_pallas)
        return ops.crop(out[..., 0, :], n)

    def solve_lasso(y, mu, gamma, n_iters):
        c = jnp.asarray(coeffs, y.dtype)
        thresh = _mu_threshold(mu, op.eta, y.dtype, gamma)
        phi_y = ops.fused_cheb_recurrence(mv, _pad(y), c, lmax,
                                          use_pallas=use_pallas)

        def body(a, _):
            back = cheb.cheb_apply_adjoint(mv, a, c, lmax)
            gram_a = ops.fused_cheb_recurrence(mv, back, c, lmax,
                                               use_pallas=use_pallas)
            a_new = soft_threshold(a + gamma * (phi_y - gram_a), thresh)
            return a_new, None

        a_star, _ = jax.lax.scan(body, jnp.zeros_like(phi_y), None,
                                 length=n_iters)
        y_star = cheb.cheb_apply_adjoint(mv, a_star, c, lmax)
        return LassoResult(coeffs=a_star[..., :n], signal=y_star[..., :n],
                           objective=jnp.nan, n_iters=n_iters, fused=True)

    def matvec_runner(fn, signals, consts=()):
        padded = tuple(_pad(s) for s in signals)
        outs = fn(mv, *padded, *consts)
        return jax.tree.map(lambda o: ops.crop(o, n), outs)

    return ExecutionPlan(
        op=op, backend="pallas_halo",
        apply=apply, apply_adjoint=apply_adjoint, apply_gram=apply_gram,
        solve_lasso_fn=solve_lasso,
        matvec_runner=matvec_runner,
        info=info,
    )


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
