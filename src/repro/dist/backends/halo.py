"""'halo' execution backend: sharded Algorithm 1/2/3 via shard_map with ring
halo exchange.

TPU adaptation of the paper's distributed model (DESIGN.md §3): one device
holds a contiguous *block* of vertices instead of one sensor holding one
vertex.  Spatially sorted sensor graphs are banded, so inter-shard coupling
touches only adjacent shards; per Chebyshev order each shard exchanges its
boundary *tile* — the h = coupling-bandwidth rows a neighbour actually
reads — with its two ring neighbours: one collective_permute pair per
order, matching the paper's 2K|E| message accounting.

Interior/boundary split (see docs/ARCHITECTURE.md "Perf accounting"): the
per-order matvec issues the two boundary-tile ppermutes *first*, computes
the interior contribution (the diagonal block product, which needs no
remote data) while the exchange is in flight, and applies the small
(nl, h) boundary couplings only on arrival — the exchange latency hides
behind interior compute instead of serializing in front of it, and the
wire carries 2h values per shard per order instead of the full 2·nl
block.  The measured exchange-round count (and hence the paper-level
2K|E| message count) is unchanged; only the payload shrinks.

:func:`build` hands the plan body (`repro.dist.operator.plan_from_form`)
this matvec and the banded partition; the reference recurrence
(`core.chebyshev.cheb_apply`) runs over it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ...core import chebyshev as cheb
from ...kernels import ops
from .. import faults, quantize
from ..sharding import auto_mesh
from . import register_backend

Array = jax.Array


# ---------------------------------------------------------------------------
# Banded partition of P
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BandedPartition:
    """P split into per-shard tridiagonal block structure.

    diag:  (S, nl, nl)  coupling within shard i
    left:  (S, nl, nl)  coupling of shard i's rows to shard i-1's columns
    right: (S, nl, nl)  coupling of shard i's rows to shard i+1's columns
    n:     logical size (before padding); S * nl >= n
    """

    diag: Array
    left: Array
    right: Array
    n: int

    @property
    def n_shards(self) -> int:
        return self.diag.shape[0]

    @property
    def n_local(self) -> int:
        return self.diag.shape[1]

    @property
    def n_padded(self) -> int:
        return self.n_shards * self.n_local

    @property
    def halo(self) -> int:
        """Coupling bandwidth h: boundary rows a neighbour actually reads
        (the per-order exchange tile).  Computed once and memoized in the
        instance __dict__ (the frozen-dataclass cache idiom)."""
        h = self.__dict__.get("_halo")
        if h is None:
            h = _coupling_bandwidth(np.asarray(self.left),
                                    np.asarray(self.right))
            self.__dict__["_halo"] = h
        return h

    def boundary_couplings(self) -> Tuple[Array, Array]:
        """(left, right) couplings trimmed to the h columns they read:
        left: (S, nl, h) against neighbour s-1's *last* h rows; right:
        (S, nl, h) against neighbour s+1's *first* h rows."""
        h = self.halo
        nl = self.n_local
        return self.left[:, :, nl - h:], self.right[:, :, :h]


def _coupling_bandwidth(left: np.ndarray, right: np.ndarray) -> int:
    """Halo width h: how many boundary rows a neighbour actually reads.

    `left[s]` couples shard s to the trailing columns of shard s-1 and
    `right[s]` to the leading columns of shard s+1; h is the widest such
    band over all shards (at least 1 so the exchange shapes stay static).
    """
    nl = left.shape[1]
    h = 1
    lc = np.nonzero(np.any(left != 0, axis=(0, 1)))[0]
    if lc.size:
        h = max(h, nl - int(lc.min()))
    rc = np.nonzero(np.any(right != 0, axis=(0, 1)))[0]
    if rc.size:
        h = max(h, int(rc.max()) + 1)
    return min(h, nl)


def partition_banded(
    P_dense: np.ndarray, n_shards: int
) -> Tuple[BandedPartition, float]:
    """Split P into block-tridiagonal shard structure.

    Returns (partition, leak) where `leak` is the Frobenius norm of entries
    outside the block tridiagonal band (must be ~0 for the halo mode to be
    exact — use `spatial_sort` first for sensor graphs, or the 'allgather'
    backend).
    """
    P_dense = np.asarray(P_dense)
    n = P_dense.shape[0]
    nl = -(-n // n_shards)
    pad = n_shards * nl - n
    Pp = np.pad(P_dense, ((0, pad), (0, pad)))
    diag = np.zeros((n_shards, nl, nl), P_dense.dtype)
    left = np.zeros((n_shards, nl, nl), P_dense.dtype)
    right = np.zeros((n_shards, nl, nl), P_dense.dtype)
    covered = np.zeros_like(Pp, dtype=bool)
    for s in range(n_shards):
        r = slice(s * nl, (s + 1) * nl)
        diag[s] = Pp[r, r]
        covered[r, r] = True
        if s > 0:
            c = slice((s - 1) * nl, s * nl)
            left[s] = Pp[r, c]
            covered[r, c] = True
        if s < n_shards - 1:
            c = slice((s + 1) * nl, (s + 2) * nl)
            right[s] = Pp[r, c]
            covered[r, c] = True
    leak = float(np.linalg.norm(Pp[~covered]))
    return (
        BandedPartition(
            diag=jnp.asarray(diag),
            left=jnp.asarray(left),
            right=jnp.asarray(right),
            n=n,
        ),
        leak,
    )


def pad_signal(x: Union[np.ndarray, Array], parts: BandedPartition) -> Array:
    """Zero-pad the trailing (vertex) axis up to the partition's padded size;
    leading batch / eta axes pass through untouched."""
    return ops.pad_trailing(jnp.asarray(x), parts.n_padded)


# ---------------------------------------------------------------------------
# Local matvecs (run inside shard_map)
# ---------------------------------------------------------------------------
def _halo_matvec(diag, left, right, nl: int, h: int, axis: str,
                 exchange_dtype: str = "f32", error_feedback: bool = True,
                 fault_spec=None, degradation: str = "zero_fill"):
    """Interior/boundary-split matvec along the *last* axis of x.

    x: (..., nl) local block; left/right are the (nl, h) boundary
    couplings from :meth:`BandedPartition.boundary_couplings`.  Per call:

    1. **boundary tiles encoded and on the wire first** — the first/last
       h entries are compressed to `exchange_dtype` (identity for f32,
       truncating cast for bf16, per-tile-scale int8 with the scale
       bitcast-packed into the same buffer — see `repro.dist.quantize`)
       and ppermute to the ring neighbours (lines 6-7 of Algorithm 1);
    2. **interior compute while the exchange is in flight** — the
       diagonal-block product needs no remote data, so it overlaps the
       collective under an async-collective scheduler;
    3. **decode + boundary coupling on arrival** — the received tiles
       widen back to the compute dtype, then two (nl, h) products.

    Returns a `core.chebyshev.LocalMatvec`.  Under
    ``exchange_dtype="int8"`` with ``error_feedback=True`` (and a real
    multi-shard axis) it is *stateful-capable*: ``mv(x)`` stays the plain
    stateless signature (plain quantize), while ``mv(x, state) -> (y,
    state)`` threads the quantization residual of each boundary tile into
    the next round, and its ``init_state(x)`` builds the zero residuals;
    `core.chebyshev` / `kernels.ops` thread the state when the record has
    an `init_state`.

    With an *active* ``fault_spec`` (see `repro.dist.faults`) the matvec
    is stateful for a second reason: the state carries the int32 round
    counter and the last-delivered tile per incoming link, and every
    received tile passes through the injector's wire-noise / stale /
    drop channels AFTER the ppermute — the collective schedule (and the
    measured 2K|E| rounds) is bitwise identical to the clean plan's.

    The permute indices form a ring; the first/last shard's out-of-range
    contribution is killed by the zero left/right coupling blocks
    (partition_banded leaves left[0] = right[-1] = 0).
    """
    size = jax.lax.axis_size(axis)
    dt = quantize.validate_exchange_dtype(exchange_dtype)
    inj = faults.make_injector(fault_spec, degradation, axis, size > 1)
    use_ef = dt == "int8" and error_feedback and size > 1

    def _run(x, state):
        head = x[..., :h]
        tail = x[..., nl - h:nl]
        if inj is not None:
            k, carried, ef_state = state
        else:
            ef_state = state
        if size > 1:
            if ef_state is None:
                wire_tail = quantize.encode(tail, dt)
                wire_head = quantize.encode(head, dt)
                new_ef = None
            else:
                r_tail, r_head = ef_state
                wire_tail, r_tail = quantize.ef_encode(tail, r_tail, dt)
                wire_head, r_head = quantize.ef_encode(head, r_head, dt)
                new_ef = (r_tail, r_head)
            # (1) issue the boundary-tile exchange: shard s receives s-1's
            # tail (read by `left`) and s+1's head (read by `right`).
            # One ppermute per direction — the int8 scale rides inside the
            # wire buffer, so measured rounds stay the paper's 2K|E|.
            from_left = jax.lax.ppermute(
                wire_tail, axis,
                perm=[(i, (i + 1) % size) for i in range(size)]
            )
            from_right = jax.lax.ppermute(
                wire_head, axis,
                perm=[(i, (i - 1) % size) for i in range(size)]
            )
            # (2) interior: depends only on local data — overlaps the
            # exchange
            y = jnp.einsum("ij,...j->...i", diag, x)
            # (3) decode + boundary coupling, consumed after the interior
            # product; injected faults perturb only what the receiver
            # consumes — the wire traffic above is already committed
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
            y = jnp.einsum("ij,...j->...i", diag, x)
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
                # one-shot stateless call under faults: a fresh round-0
                # state, deterministic per seed, result state discarded
                return _run(x, init_state(x))[0]
            return _run(x, None)[0]
        return _run(x, state)

    return cheb.LocalMatvec(mv, init_state=init_state)


def halo_bytes_per_apply(parts: BandedPartition, K: int, eta: int = 1,
                         dtype_bytes: int = 4,
                         exchange_dtype: Optional[str] = None) -> int:
    """Collective-traffic model for one sharded application: per Chebyshev
    order each shard sends its h-row boundary tile left+right, K rounds,
    n_shards shards.  The TPU analog of the paper's 2K|E| message bound —
    the interior/boundary split shrank the payload from the full nl block
    to the h rows a neighbour actually reads, and the compressed exchange
    (`exchange_dtype=`) shrinks each row from 4h bytes (f32) to 2h (bf16)
    or h + 4 (int8 payload + packed scale; `quantize.tile_wire_bytes`),
    while the round count (what the paper-level accounting measures) is
    unchanged.  `dtype_bytes` is the legacy per-element width used when
    `exchange_dtype` is not given."""
    if exchange_dtype is not None:
        row = quantize.tile_wire_bytes(parts.halo, exchange_dtype)
    else:
        row = parts.halo * dtype_bytes
    return 2 * K * parts.n_shards * eta * row


# ---------------------------------------------------------------------------
# Plan builder
# ---------------------------------------------------------------------------
@register_backend("halo")
def build(op, *, mesh=None, partition=None, axis: Optional[str] = None,
          allow_leak: bool = False, exchange_dtype: str = "f32",
          error_feedback: bool = True, partition_method: str = "bfs",
          fault_spec=None, degradation: str = "zero_fill",
          **options):
    """Build an ExecutionPlan running every application inside a shard_map
    over `mesh` with ring halo exchange.

    Requires a dense P (or a precomputed `partition`).  ``partition=``
    accepts None / ``"banded"`` (the block-tridiagonal ring plan — the
    graph must be leak-free under the contiguous split unless
    ``allow_leak=True``), ``"general"`` (edge-cut sharding of *arbitrary*
    sparse graphs via `repro.dist.partition.partition_general`, exact for
    any sparsity; ``partition_method`` picks "bfs" or "spectral"), or a
    precomputed `BandedPartition` / `GeneralPartition` instance.  Without
    `mesh=`, a 1-D "graph" mesh over every visible device is built.

    ``exchange_dtype`` selects the wire precision of the boundary tiles
    ("f32" | "bf16" | "int8", see `repro.dist.quantize`);
    ``error_feedback`` (int8 only) threads the per-tile quantization
    residual across the K orders.
    """
    from ..operator import LocalForm, plan_from_form
    from ..partition import build_general_plan, resolve_partition_arg

    quantize.validate_exchange_dtype(exchange_dtype)
    faults.validate_degradation(degradation)
    fault_spec = faults.resolve_fault_spec(fault_spec)
    mesh = auto_mesh(mesh)
    axis = axis or mesh.axis_names[0]
    n_shards = int(mesh.shape[axis])
    general = resolve_partition_arg(op, partition, n_shards,
                                    method=partition_method)
    if general is not None:
        return build_general_plan(op, general, mesh, axis,
                                  interior="dense",
                                  exchange_dtype=exchange_dtype,
                                  error_feedback=error_feedback,
                                  fault_spec=fault_spec,
                                  degradation=degradation,
                                  backend_name="halo")
    if isinstance(partition, str):
        partition = None  # "banded": build from op.P below
    leak = 0.0
    if partition is None:
        if callable(op.P):
            raise ValueError("halo backend needs a dense P or partition=")
        partition, leak = partition_banded(np.asarray(op.P), n_shards)
        if leak > 1e-10 and not allow_leak:
            raise ValueError(
                f"P is not block-tridiagonal under {n_shards} shards "
                f"(leak={leak:.3e}); spatial_sort the graph first, pass "
                "allow_leak=True, or use backend='allgather'")
    parts = partition
    n = parts.n
    nl, h = parts.n_local, parts.halo

    def local(mine):
        diag, left, right = mine
        return _halo_matvec(diag, left, right, nl, h, axis, exchange_dtype,
                            error_feedback, fault_spec, degradation)

    # zero-padded tails stay zero: solver bodies use reciprocal-diagonal
    # updates
    form = LocalForm(local=local, recurrence=cheb.cheb_apply,
                     arrays=(parts.diag, *parts.boundary_couplings()),
                     glob_in=lambda x: pad_signal(x, parts),
                     glob_out=lambda y: ops.crop(y, n),
                     mesh=mesh, axis=axis)
    return plan_from_form(op, "halo", form, info={
        "mesh_axis": axis,
        "n_shards": n_shards,
        "n_local": nl,
        "halo_width": h,
        "partition": "banded",
        "partition_leak": leak,
        # one exchange round = the left+right ppermute pair (commstats
        # divides the measured ppermute tally by this)
        "exchange_collectives_per_round": 2,
        "exchange_dtype": exchange_dtype,
        "error_feedback": bool(error_feedback),
        "fault_spec": faults.spec_info(fault_spec),
        "degradation": degradation,
        "fault_key": faults.fault_key(fault_spec, degradation),
        # forward/gram ship an eta-independent (..., h) tile per order;
        # only the adjoint's iterate carries the eta streams
        "halo_bytes_per_apply": halo_bytes_per_apply(
            parts, op.K, 1, exchange_dtype=exchange_dtype),
        "halo_bytes_per_adjoint": halo_bytes_per_apply(
            parts, op.K, op.eta, exchange_dtype=exchange_dtype),
    })
