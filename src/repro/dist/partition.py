"""Pluggable graph partitions: edge-cut sharding for arbitrary sparse graphs.

The paper's headline systems claim — 2K|E| messages per filter application,
for *any* sparse graph (Section IV-B) — does not depend on P being banded.
What the banded `halo.BandedPartition` hard-codes is merely one *exchange
plan*: each shard ships one boundary tile left and one right per Chebyshev
order.  This module extracts the general contract and supplies a
dependency-free partitioner for graphs with no usable bandwidth (community,
k-NN, random-geometric):

* :class:`GeneralPartition` — per-shard Block-ELL structure for the
  intra-shard (interior) edges plus an explicit exchange plan for the cut
  edges.  The plan is a static tuple of ring **offsets**: round ``d`` has
  shard ``i`` send a gathered boundary tile to shard ``(i + d) % S`` via
  one ``ppermute`` whose permutation ``[(i, (i+d) % S)]`` is a complete
  bijection *by construction* — arbitrary neighbour sets are realized as a
  sequence of complete permutation rounds, so the `JX-PPERMUTE-BIJECTION`
  invariant of :mod:`repro.analysis` holds for free and no shard ever
  deadlocks waiting on a partner that isn't sending.  Shards with no cut
  edges at some offset ship a (zero-coupled, hence ignored) padded tile:
  uniform tile shapes keep the collective schedule static and
  batch-invariant.
* :func:`edge_cut_order` — greedy-BFS (default) or recursive spectral-
  bisection vertex ordering, chopped into S contiguous blocks of
  ``nl = ceil(n/S)``.  Pure numpy, no METIS/scipy dependency.
* :func:`partition_general` — builds the partition from a dense matrix or
  a :class:`CSRMatrix` (the million-vertex path: nothing dense is ever
  materialized).
* :func:`build_general_plan` — the shared ExecutionPlan builder both
  sharded backends delegate to (``halo`` with a dense per-shard interior,
  ``pallas_halo`` with the Block-ELL interior), preserving the
  encode→exchange→interior-compute overlap and the PR-8 ``exchange_dtype``
  codec on arbitrary boundary tiles.

Communication per application is exactly K exchange rounds (one per
Chebyshev order; each round = ``len(offsets)`` ppermutes), each moving only
the boundary rows that cross the cut — the general-graph form of the
paper's one-scalar-per-directed-edge-per-order accounting, measured (not
assumed) by :mod:`repro.dist.commstats` and property-tested in
``tests/test_property.py`` / ``tests/test_partition.py``.

A multi-shard plan takes and returns signals in vertex order, sharded over
the mesh.  Where S divides n it moves them into partition order and back
shard to shard (:class:`ShardReorder`, :func:`sharded_reorder`: only the
rows that change shard cross the mesh, under the ``repro.reorder`` scope,
never counted as exchange rounds); otherwise the whole signal is gathered
(``jnp.take``) on the way in and out.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..core import chebyshev as cheb
from ..core import graph as graphmod
from ..kernels import ops
from . import faults, quantize

Array = jax.Array


class OverfullSlotsError(ValueError):
    """A row block needs more column-block slots than the uniform budget.

    Raised instead of silently truncating: dropping blocks would produce a
    *wrong answer* (missing edges) with no error, the worst failure class.
    Raise the ``max_slots`` budget, use a smaller column block, or let the
    slot count float (``max_slots=None`` sizes slots to the actual max).
    """


# ---------------------------------------------------------------------------
# CSR container + synthetic community graphs (million-vertex scale)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CSRMatrix:
    """A square sparse matrix in CSR form (numpy, host-side).

    The partitioner's native input: at N = 1e6 a dense P would be 4 TB, so
    the whole partition pipeline (ordering, Block-ELL packing, exchange
    plan) is built from CSR without ever materializing a dense array.
    """

    indptr: np.ndarray   # (n + 1,) int64
    indices: np.ndarray  # (nnz,) column ids
    data: np.ndarray     # (nnz,) values

    @property
    def n(self) -> int:
        return len(self.indptr) - 1

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    @property
    def n_edges(self) -> int:
        """|E| — undirected off-diagonal edges (assumes symmetric support)."""
        rows = self.row_ids()
        return int(np.count_nonzero((rows < self.indices)
                                    & (self.data != 0)))

    def row_ids(self) -> np.ndarray:
        return np.repeat(np.arange(self.n, dtype=np.int64),
                         np.diff(self.indptr))

    def matvec(self, x: np.ndarray) -> np.ndarray:
        out = np.zeros(self.n, dtype=np.result_type(self.data, x))
        np.add.at(out, self.row_ids(), self.data * x[self.indices])
        return out

    def to_dense(self) -> np.ndarray:
        out = np.zeros((self.n, self.n), dtype=self.data.dtype)
        out[self.row_ids(), self.indices] = self.data
        return out

    @classmethod
    def from_coo(cls, n: int, rows, cols, vals) -> "CSRMatrix":
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals)
        order = np.lexsort((cols, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        indptr = np.searchsorted(rows, np.arange(n + 1))
        return cls(indptr=indptr, indices=cols, data=vals)

    @classmethod
    def from_dense(cls, M) -> "CSRMatrix":
        M = np.asarray(M)
        rows, cols = np.nonzero(M)
        return cls.from_coo(M.shape[0], rows, cols, M[rows, cols])


def as_csr(Pmat: Union[np.ndarray, Array, CSRMatrix]) -> CSRMatrix:
    if isinstance(Pmat, CSRMatrix):
        return Pmat
    return CSRMatrix.from_dense(np.asarray(Pmat))


def csr_matvec_fn(csr: CSRMatrix):
    """A jnp closure ``x -> L x`` over the (..., N) contract — the callable
    P for `GraphOperator` when the graph is too large to densify."""
    rows = jnp.asarray(csr.row_ids(), jnp.int32)
    cols = jnp.asarray(csr.indices, jnp.int32)
    vals = jnp.asarray(csr.data, jnp.float32)
    n = csr.n

    def mv(x):
        contrib = vals * jnp.take(x, cols, axis=-1)
        zero = jnp.zeros(x.shape[:-1] + (n,), x.dtype)
        return zero.at[..., rows].add(contrib.astype(x.dtype))

    return mv


def community_graph_csr(
    n: int,
    n_communities: Optional[int] = None,
    inter_per_comm: int = 2,
    seed: int = 0,
) -> Tuple[CSRMatrix, dict]:
    """Synthetic community graph, Laplacian in CSR, at any scale.

    Each community is a chain + a ring-closing wrap edge; communities are
    linked by a spanning chain of random-endpoint edges plus
    ``inter_per_comm`` extra edges to uniformly random other communities.
    Random endpoints make the inter-community edges *long-range* in any
    contiguous vertex order, so the graph is genuinely non-banded — the
    `GeneralPartition` workload — while the intra-community chains keep it
    connected and give the partitioner real structure to find.  Fully
    vectorized numpy: N = 1e6 builds in seconds.

    Returns ``(L, meta)`` with ``meta = {"n_edges", "lmax",
    "n_communities"}`` — ``lmax`` is the Anderson-Morley bound computed
    from local degrees only (Section IV-B), so no dense spectral work.
    """
    if n < 4:
        raise ValueError(f"community graph needs n >= 4, got {n}")
    if n_communities is None:
        n_communities = max(2, n // 250)
    n_communities = min(n_communities, n // 2)
    c = -(-n // n_communities)
    comm = np.arange(n) // c
    starts = np.arange(n_communities) * c
    ends = np.minimum(starts + c, n) - 1
    rng = np.random.default_rng(seed)

    # chain within each community
    i = np.arange(n - 1)
    keep = comm[i] == comm[i + 1]
    e_u = [i[keep]]
    e_v = [i[keep] + 1]
    # ring-closing wrap edge per community (size >= 3)
    big = (ends - starts) >= 2
    e_u.append(starts[big])
    e_v.append(ends[big])

    def _rand_in(comms):
        sizes = ends[comms] - starts[comms] + 1
        return starts[comms] + rng.integers(0, sizes)

    # spanning inter-community chain (random endpoints: long-range edges)
    k = np.arange(n_communities - 1)
    e_u.append(_rand_in(k))
    e_v.append(_rand_in(k + 1))
    # extra inter edges to random other communities
    if inter_per_comm > 0 and n_communities > 1:
        src = np.repeat(np.arange(n_communities), inter_per_comm)
        dst = rng.integers(0, n_communities - 1, src.size)
        dst = np.where(dst >= src, dst + 1, dst)
        e_u.append(_rand_in(src))
        e_v.append(_rand_in(dst))

    u = np.concatenate(e_u)
    v = np.concatenate(e_v)
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    keep = lo != hi
    lo, hi = lo[keep], hi[keep]
    key = lo * n + hi
    _, uniq_idx = np.unique(key, return_index=True)
    lo, hi = lo[uniq_idx], hi[uniq_idx]
    m = lo.size
    w = rng.uniform(0.5, 1.5, m).astype(np.float32)

    deg = np.zeros(n, np.float64)
    np.add.at(deg, lo, w)
    np.add.at(deg, hi, w)
    lmax = float((deg[lo] + deg[hi]).max())

    rows = np.concatenate([lo, hi, np.arange(n)])
    cols = np.concatenate([hi, lo, np.arange(n)])
    vals = np.concatenate([-w, -w, deg.astype(np.float32)]).astype(np.float32)
    L = CSRMatrix.from_coo(n, rows, cols, vals)
    return L, {"n_edges": int(m), "lmax": lmax,
               "n_communities": int(n_communities)}


# ---------------------------------------------------------------------------
# Edge-cut orderings (dependency-free: greedy BFS / spectral bisection)
# ---------------------------------------------------------------------------
def _ragged_gather(indptr: np.ndarray, indices: np.ndarray,
                   verts: np.ndarray) -> np.ndarray:
    """All CSR column ids of `verts`, concatenated (vectorized ragged
    gather — the partitioner's frontier-expansion primitive)."""
    starts = indptr[verts]
    lens = indptr[verts + 1] - starts
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, indices.dtype)
    offs = np.repeat(starts - np.concatenate(([0], np.cumsum(lens)[:-1])),
                     lens)
    return indices[offs + np.arange(total)]


def _bfs_order(csr: CSRMatrix) -> np.ndarray:
    """Global BFS ordering with min-degree restarts (handles disconnected
    graphs); chopping it into contiguous blocks is the greedy-BFS
    partition.  Each frontier expansion is one vectorized ragged gather."""
    n = csr.n
    deg = np.diff(csr.indptr)
    visited = np.zeros(n, bool)
    order = np.empty(n, np.int64)
    # restarts in (degree, id) order: the first unvisited one is the
    # least-degree unvisited vertex of least id, found by a pointer that
    # only moves forward (a scan of every vertex per restart is quadratic
    # in a graph with many isolated vertices)
    seeds = np.argsort(deg, kind="stable")
    nxt = pos = 0
    while pos < n:
        while True:
            free = np.flatnonzero(~visited[seeds[nxt:nxt + 4096]])
            if free.size:
                nxt += int(free[0])
                break
            nxt += 4096
        frontier = seeds[nxt:nxt + 1]
        visited[frontier] = True
        while frontier.size:
            order[pos:pos + frontier.size] = frontier
            pos += frontier.size
            nbr = _ragged_gather(csr.indptr, csr.indices, frontier)
            nbr = nbr[~visited[nbr]]
            frontier = np.unique(nbr)
            visited[frontier] = True
    return order


def _sub_csr(csr: CSRMatrix, idx: np.ndarray):
    """Extract the principal submatrix on `idx` with remapped local ids."""
    n = csr.n
    local = np.full(n, -1, np.int64)
    local[idx] = np.arange(idx.size)
    rows_l = np.repeat(np.arange(idx.size),
                       csr.indptr[idx + 1] - csr.indptr[idx])
    cols_g = _ragged_gather(csr.indptr, csr.indices, idx)
    starts = csr.indptr[idx]
    lens = csr.indptr[idx + 1] - starts
    offs = (np.repeat(starts - np.concatenate(([0], np.cumsum(lens)[:-1])),
                      lens) + np.arange(int(lens.sum())))
    vals = csr.data[offs]
    keep = local[cols_g] >= 0
    return rows_l[keep], local[cols_g[keep]], vals[keep]


def _fiedler_vector(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                    n: int, rng, iters: int = 80) -> np.ndarray:
    """Approximate Fiedler vector of the Laplacian submatrix by power
    iteration on sigma*I - L (constant mode deflated each step)."""
    diag = np.zeros(n)
    on_diag = rows == cols
    np.add.at(diag, rows[on_diag], vals[on_diag])
    absrow = np.zeros(n)
    np.add.at(absrow, rows, np.abs(vals))
    sigma = float(absrow.max()) + 1.0  # Gershgorin upper bound on lmax
    v = rng.standard_normal(n)
    for _ in range(iters):
        Lv = np.zeros(n)
        np.add.at(Lv, rows, vals * v[cols])
        v = sigma * v - Lv
        v = v - v.mean()
        nrm = np.linalg.norm(v)
        if nrm < 1e-12:
            v = rng.standard_normal(n)
            v = v - v.mean()
            nrm = np.linalg.norm(v)
        v = v / nrm
    return v


def _spectral_order(csr: CSRMatrix, n_shards: int, nl: int,
                    seed: int = 0) -> np.ndarray:
    """Recursive spectral bisection; split sizes are multiples of nl so the
    recursion's cut planes coincide with the final contiguous shard
    boundaries."""
    rng = np.random.default_rng(seed)

    def bisect(idx: np.ndarray, parts: int) -> list:
        if parts <= 1 or idx.size <= 2:
            return [idx]
        rows, cols, vals = _sub_csr(csr, idx)
        f = _fiedler_vector(rows, cols, vals, idx.size, rng)
        left_parts = parts // 2
        n_left = min(left_parts * nl, idx.size)
        sel = np.argsort(f, kind="stable")
        return (bisect(idx[sel[:n_left]], left_parts)
                + bisect(idx[sel[n_left:]], parts - left_parts))

    chunks = bisect(np.arange(csr.n, dtype=np.int64), n_shards)
    return np.concatenate(chunks)


def edge_cut_order(Pmat, n_shards: int, method: str = "bfs",
                   seed: int = 0) -> np.ndarray:
    """Vertex ordering whose contiguous nl-chunks form the edge-cut
    partition.  `method`: "bfs" (greedy BFS, vectorized frontier
    expansion — the million-vertex default) or "spectral" (recursive
    spectral bisection via power-iteration Fiedler vectors)."""
    csr = as_csr(Pmat)
    if method == "bfs":
        return _bfs_order(csr)
    if method == "spectral":
        nl = -(-csr.n // n_shards)
        return _spectral_order(csr, n_shards, nl, seed=seed)
    raise ValueError(f"unknown partition method {method!r}; "
                     "use 'bfs' or 'spectral'")


# ---------------------------------------------------------------------------
# Vectorized COO -> per-shard Block-ELL
# ---------------------------------------------------------------------------
def _block_ell_shards(shard: np.ndarray, rows: np.ndarray, cols: np.ndarray,
                      vals: np.ndarray, n_shards: int, nl: int,
                      block: Tuple[int, int],
                      max_slots: Optional[int] = None):
    """Pack per-shard COO triples (local rows/cols in [0, nl)) into a
    uniform-slot Block-ELL stack (S, nrb, slots, br, bc) — O(nnz log nnz),
    no python loop over blocks (to_block_ell's dense scan is quadratic in
    block count and unusable at N = 1e6)."""
    br, bc = block
    unit = int(np.lcm(br, bc))
    pnl = -(-nl // unit) * unit
    nrb, ncb = pnl // br, pnl // bc
    dtype = vals.dtype if vals.size else np.float32

    nz = vals != 0
    shard, rows, cols, vals = shard[nz], rows[nz], cols[nz], vals[nz]
    if rows.size == 0:
        slots = 1
        blocks = np.zeros((n_shards, nrb, slots, br, bc), dtype)
        indices = np.zeros((n_shards, nrb, slots), np.int32)
        mask = np.zeros((n_shards, nrb, slots), bool)
        return blocks, indices, mask, pnl

    rb, cb = rows // br, cols // bc
    gkey = (shard.astype(np.int64) * nrb + rb) * ncb + cb
    uniq, inv = np.unique(gkey, return_inverse=True)
    urow = uniq // ncb  # shard * nrb + rb, sorted non-decreasing
    firsts = np.flatnonzero(np.r_[True, urow[1:] != urow[:-1]])
    counts = np.diff(np.r_[firsts, uniq.size])
    slots = int(counts.max())
    if max_slots is not None and slots > max_slots:
        raise OverfullSlotsError(
            f"a row block couples {slots} column blocks but the uniform "
            f"slot budget is {max_slots} — refusing to truncate (silently "
            "dropped blocks = silently wrong matvecs); raise max_slots or "
            "shrink the column block")
    slot_of_uniq = np.arange(uniq.size) - np.repeat(firsts, counts)
    flat_blocks = np.zeros((n_shards * nrb * slots, br, bc), dtype)
    block_id = urow * slots + slot_of_uniq
    np.add.at(flat_blocks, (block_id[inv], rows % br, cols % bc), vals)
    flat_idx = np.zeros((n_shards * nrb, slots), np.int32)
    flat_mask = np.zeros((n_shards * nrb, slots), bool)
    flat_idx[urow, slot_of_uniq] = (uniq % ncb).astype(np.int32)
    flat_mask[urow, slot_of_uniq] = True
    return (flat_blocks.reshape(n_shards, nrb, slots, br, bc),
            flat_idx.reshape(n_shards, nrb, slots),
            flat_mask.reshape(n_shards, nrb, slots),
            pnl)


# ---------------------------------------------------------------------------
# Vertex order <-> partition order, shard to shard
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardReorder:
    """Static moves between a signal sharded in vertex order and the same
    signal in partition order, for n = S * nl (numpy, host-side).

    Shard q of the vertex-sharded signal holds vertices [q*nl, (q+1)*nl);
    shard r in partition order holds slots [r*nl, (r+1)*nl), slot p being
    vertex ``order[p]``.  A row on the same shard both ways stays; every
    other row moves at ring offset ``d = (r - q) % S``:

      offsets: ring offsets at which rows move, ascending.
      rows[k]: (S, h_k) int32 — vertex rows shard q sends to shard
          ``(q + offsets[k]) % S``, in the order of the slots they fill
          (padded with row 0).
      slots[k]: (S, h_k) int32 — the slots shard r fills from the tile it
          receives at ``offsets[k]``, in the same order (padded with slot
          0); on the way back shard r sends these slots to
          ``(r - offsets[k]) % S``.
      counts[k]: real rows each shard sends at ``offsets[k]``.
      to_partition: (S, nl) int32 — slot j of shard r is read from
          position ``to_partition[r, j]`` of its own vertex rows followed
          by the tiles received at ``offsets[0], offsets[1], ...``.
      to_vertex: (S, nl) int32 — the same for row i of shard q, from its
          own slots followed by the tiles received on the way back.
    """

    offsets: Tuple[int, ...]
    rows: Tuple[np.ndarray, ...]
    slots: Tuple[np.ndarray, ...]
    counts: Tuple[Tuple[int, ...], ...]
    to_partition: np.ndarray
    to_vertex: np.ndarray

    @property
    def tile_widths(self) -> Tuple[int, ...]:
        return tuple(int(r.shape[1]) for r in self.rows)

    @property
    def arrays(self) -> Tuple[np.ndarray, ...]:
        """Every per-shard array, leading axis S: what a plan shards."""
        return self.rows + self.slots + (self.to_partition, self.to_vertex)


def shard_reorder(order: np.ndarray, n_shards: int) -> ShardReorder:
    """The :class:`ShardReorder` of `order` over `n_shards` shards (n must
    be a multiple of the shard count)."""
    order = np.asarray(order, np.int64)
    n, S = order.size, int(n_shards)
    if n % S:
        raise ValueError(f"n = {n} is not a multiple of {S} shards")
    nl = n // S
    p = np.arange(n)
    r, j = p // nl, p % nl            # partition shard and slot
    q, i = order // nl, order % nl    # vertex shard and row
    d = (r - q) % S
    to_partition = np.empty(n, np.int32)   # indexed by r * nl + j
    to_vertex = np.empty(n, np.int32)      # indexed by q * nl + i
    stay = d == 0
    to_partition[stay] = i[stay]
    to_vertex[order[stay]] = j[stay]
    offsets, rows, slots, counts = [], [], [], []
    base = nl
    for dd in np.unique(d[~stay]).tolist():
        # ascending p: for one sender q the receiver (q + dd) % S is fixed,
        # so its rows come contiguous and in the receiver's slot order
        sel = np.flatnonzero(d == dd)
        snd = q[sel]
        cnt = np.bincount(snd, minlength=S)
        h = int(cnt.max())
        heads = np.flatnonzero(np.r_[True, snd[1:] != snd[:-1]])
        first = np.zeros(S, np.int64)
        first[snd[heads]] = heads
        rank = np.arange(sel.size) - first[snd]
        rk = np.zeros((S, h), np.int32)
        sk = np.zeros((S, h), np.int32)
        rk[snd, rank] = i[sel]
        sk[r[sel], rank] = j[sel]
        to_partition[sel] = base + rank
        to_vertex[order[sel]] = base + rank
        offsets.append(int(dd))
        rows.append(rk)
        slots.append(sk)
        counts.append(tuple(int(c) for c in cnt))
        base += h
    return ShardReorder(offsets=tuple(offsets), rows=tuple(rows),
                        slots=tuple(slots), counts=tuple(counts),
                        to_partition=to_partition.reshape(S, nl),
                        to_vertex=to_vertex.reshape(S, nl))


def sharded_reorder(x: Array, send: Sequence[Array], place: Array,
                    offsets: Sequence[int], axis: str, size: int,
                    back: bool = False) -> Array:
    """One shard's part of a :class:`ShardReorder` (inside ``shard_map``).

    `x` is this shard's (..., nl) block; `send[k]` its row of
    ``rows[k]`` (``slots[k]`` with ``back``) and `place` its row of
    ``to_partition`` (``to_vertex``).  Each offset's rows are packed and
    moved by one complete-bijection ``ppermute`` (``back`` reverses the
    ring); one gather then places every row.  Only rows that change shard
    cross the mesh, each tile padded to its offset's largest count.
    """
    def move(v):
        # scoped here too: a loop body's ops do not see the caller's scope
        with obs.scope("reorder"):
            tiles = [
                jax.lax.ppermute(
                    jnp.take(v, idx, axis=-1), axis,
                    perm=[(i, (i + (-d if back else d)) % size)
                          for i in range(size)])
                for idx, d in zip(send, offsets)]
            return jnp.take(jnp.concatenate([v, *tiles], axis=-1), place,
                            axis=-1)

    with obs.scope("reorder"):
        if x.ndim < 3:
            return move(x)
        # a (..., eta, nl) result moves one row of axis -2 at a time: the
        # tiles and the gather's copies are then a row's, not the whole
        # result's, and the loop's length does not depend on the batch
        ax = x.ndim - 2

        def row(out, j):
            v = jax.lax.dynamic_index_in_dim(x, j, ax, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(out, move(v), j,
                                                       ax), None

        return jax.lax.scan(row, jnp.zeros_like(x),
                            jnp.arange(x.shape[ax]))[0]


# ---------------------------------------------------------------------------
# The partition contract
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GeneralPartition:
    """Edge-cut partition of a sparse P over S shards + explicit exchange
    plan.

    Vertices are relabeled by `order` (original vertex id at partition slot
    i) and chopped into S contiguous blocks of nl rows.  Intra-shard
    entries live in the per-shard Block-ELL stack; every cut entry
    P[u, v] with u on shard r and v on shard o is realized as one exchange
    round at ring offset ``d = (r - o) % S`` plus one scatter coupling:

      blocks/indices/mask: (S, nrb, slots, br, bc) / (S, nrb, slots)
          per-shard Block-ELL of the interior (diagonal) block.
      offsets: static ring offsets, ascending.  Round k: every shard i
          gathers its boundary tile ``x[send_idx[k][i]]`` and ppermutes it
          with the complete bijection ``[(i, (i + offsets[k]) % S)]``.
      send_idx[k]: (S, h_k) int32 — local rows shard i ships at offset k
          (padded with row 0; receivers index only real positions).
      send_counts[k]: (S,) — how many of the h_k rows are real per shard.
      cpl_rows/cpl_cols/cpl_vals[k]: (S, m_k) — receiver-side scatter:
          shard i adds ``vals * tile[cols]`` into its rows, where `tile`
          arrived from shard ``(i - offsets[k]) % S`` (zero-val padding).
      order / n / n_local / edge_cut / method: bookkeeping.
      reorder: the :class:`ShardReorder` of `order` where S > 1 divides
          n, else None (signals are then gathered whole).

    A banded graph under the identity order reduces exactly to the ring
    plan: offsets (1, S-1) with the tail/head boundary tiles —
    property-tested in tests/test_property.py.
    """

    blocks: Array
    indices: Array
    mask: Array
    offsets: Tuple[int, ...]
    send_idx: Tuple[Array, ...]
    send_counts: Tuple[Tuple[int, ...], ...]
    cpl_rows: Tuple[Array, ...]
    cpl_cols: Tuple[Array, ...]
    cpl_vals: Tuple[Array, ...]
    order: np.ndarray
    n: int
    n_local: int
    edge_cut: int
    method: str
    reorder: Optional[ShardReorder] = None

    @property
    def n_shards(self) -> int:
        return self.blocks.shape[0]

    @property
    def n_padded(self) -> int:
        """Global padded signal size (S * nl); `halo.pad_signal` reads it."""
        return self.n_shards * self.n_local

    @property
    def n_local_padded(self) -> int:
        """Per-shard Block-ELL padded domain (nrb * br >= nl)."""
        return self.blocks.shape[1] * self.blocks.shape[3]

    @property
    def nnz_blocks(self) -> int:
        return int(np.asarray(self.mask).sum())

    @property
    def tile_widths(self) -> Tuple[int, ...]:
        return tuple(int(s.shape[1]) for s in self.send_idx)

    @property
    def halo(self) -> int:
        """Widest exchange tile (the banded plan's h analog; 0 = no cut)."""
        return max(self.tile_widths, default=0)

    @property
    def inv_order(self) -> np.ndarray:
        inv = self.__dict__.get("_inv_order")
        if inv is None:
            inv = np.empty_like(self.order)
            inv[self.order] = np.arange(self.order.size)
            self.__dict__["_inv_order"] = inv
        return inv

    @property
    def fingerprint(self) -> str:
        """Stable identity of the partition (order + exchange plan shape);
        joins plan memo keys and serving compat keys so plans built over
        different partitions never share a compiled entry."""
        fp = self.__dict__.get("_fingerprint")
        if fp is None:
            h = hashlib.sha1()
            h.update(np.ascontiguousarray(self.order).tobytes())
            h.update(repr((self.n, self.n_local, self.offsets,
                           self.tile_widths)).encode())
            fp = h.hexdigest()[:12]
            self.__dict__["_fingerprint"] = fp
        return fp

    def _order_jnp(self):
        cached = self.__dict__.get("_order_j")
        if cached is None:
            # concrete even when first asked for inside a jit trace: a
            # cached tracer would leak into every later trace
            with jax.ensure_compile_time_eval():
                cached = (jnp.asarray(self.order, jnp.int32),
                          jnp.asarray(self.inv_order, jnp.int32))
            self.__dict__["_order_j"] = cached
        return cached

    def to_partition_order(self, x: Array) -> Array:
        """Permute the trailing (vertex) axis into partition order."""
        with obs.scope("layout"):
            return jnp.take(x, self._order_jnp()[0], axis=-1)

    def from_partition_order(self, y: Array) -> Array:
        """Inverse of :meth:`to_partition_order` (trailing axis length n)."""
        with obs.scope("layout"):
            return jnp.take(y, self._order_jnp()[1], axis=-1)

    def dense_diag(self) -> np.ndarray:
        """(S, nl, nl) dense per-shard diagonal blocks — the `halo`
        backend's interior representation (small-n use only)."""
        S, nrb, slots, br, bc = self.blocks.shape
        pnl = self.n_local_padded
        blocks = np.asarray(self.blocks)
        indices = np.asarray(self.indices)
        mask = np.asarray(self.mask)
        out = np.zeros((S, pnl, pnl), blocks.dtype)
        for s in range(S):
            for rb in range(nrb):
                for k in range(slots):
                    if mask[s, rb, k]:
                        cb = int(indices[s, rb, k])
                        out[s, rb * br:(rb + 1) * br,
                            cb * bc:(cb + 1) * bc] += blocks[s, rb, k]
        return out[:, :self.n_local, :self.n_local]

    def wire_bytes_per_round(self, exchange_dtype: str = "f32") -> int:
        """Bytes ONE shard ships per exchange round (= per matvec): the sum
        of its per-offset tile wire sizes under the PR-8 codec."""
        return sum(quantize.tile_wire_bytes(h, exchange_dtype)
                   for h in self.tile_widths)


def general_bytes_per_apply(parts: GeneralPartition, K: int, eta: int = 1,
                            exchange_dtype: str = "f32") -> int:
    """Collective-traffic model for one application under a general
    partition: K rounds x S shards x the per-shard wire bytes of all
    offset tiles (eta-wide iterates for the adjoint) — the arbitrary-graph
    analog of `halo.halo_bytes_per_apply`."""
    return K * parts.n_shards * eta * parts.wire_bytes_per_round(
        exchange_dtype)


def partition_general(
    Pmat: Union[np.ndarray, Array, CSRMatrix],
    n_shards: int,
    *,
    method: str = "bfs",
    block: Tuple[int, int] = (8, 128),
    max_slots: Optional[int] = None,
    order: Optional[np.ndarray] = None,
    seed: int = 0,
) -> GeneralPartition:
    """Build a :class:`GeneralPartition` from a dense matrix or CSRMatrix.

    `order` overrides the partitioner (method becomes "precomputed") —
    pass ``np.arange(n)`` to shard an already-sorted graph in place.
    ``max_slots`` bounds the uniform Block-ELL slot count and *raises*
    :class:`OverfullSlotsError` when exceeded (never truncates).
    """
    csr = as_csr(Pmat)
    n = csr.n
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if order is None:
        order = edge_cut_order(csr, n_shards, method=method, seed=seed)
    else:
        order = np.asarray(order, np.int64)
        if sorted(order.tolist()) != list(range(n)):
            raise ValueError("order= must be a permutation of range(n)")
        method = "precomputed"
    nl = -(-n // n_shards)
    pos = np.empty(n, np.int64)
    pos[order] = np.arange(n)

    rows_g = csr.row_ids()
    nz = csr.data != 0
    pr = pos[rows_g[nz]]
    pc = pos[csr.indices[nz]]
    w = csr.data[nz].astype(np.float32)
    sr, sc = pr // nl, pc // nl

    intra = sr == sc
    blocks, indices, mask, _pnl = _block_ell_shards(
        sr[intra], pr[intra] - sr[intra] * nl, pc[intra] - sc[intra] * nl,
        w[intra], n_shards, nl, block, max_slots=max_slots)

    cut = ~intra
    d_all = (sr[cut] - sc[cut]) % n_shards
    offsets, send_idx, send_counts = [], [], []
    cpl_rows, cpl_cols, cpl_vals = [], [], []
    for d in np.unique(d_all).tolist():
        sel = d_all == d
        snd = sc[cut][sel]                  # sender shard per cut entry
        lv = pc[cut][sel] - snd * nl        # sender-local boundary row
        rcv = sr[cut][sel]                  # receiver shard
        lu = pr[cut][sel] - rcv * nl        # receiver-local target row
        wv = w[cut][sel]

        okey = snd * nl + lv
        u = np.unique(okey)
        uo, ulv = u // nl, u % nl
        counts = np.bincount(uo, minlength=n_shards)
        h = int(counts.max())
        first = np.concatenate(([0], np.cumsum(counts)))[:-1]
        rank_u = np.arange(u.size) - first[uo]
        sidx = np.zeros((n_shards, h), np.int32)
        sidx[uo, rank_u] = ulv.astype(np.int32)
        col_pos = rank_u[np.searchsorted(u, okey)]

        mcounts = np.bincount(rcv, minlength=n_shards)
        m = int(mcounts.max())
        firstm = np.concatenate(([0], np.cumsum(mcounts)))[:-1]
        eidx = np.argsort(rcv, kind="stable")
        rank_e = np.arange(eidx.size) - firstm[rcv[eidx]]
        crows = np.zeros((n_shards, m), np.int32)
        ccols = np.zeros((n_shards, m), np.int32)
        cvals = np.zeros((n_shards, m), np.float32)
        crows[rcv[eidx], rank_e] = lu[eidx].astype(np.int32)
        ccols[rcv[eidx], rank_e] = col_pos[eidx].astype(np.int32)
        cvals[rcv[eidx], rank_e] = wv[eidx]

        offsets.append(int(d))
        send_idx.append(jnp.asarray(sidx))
        send_counts.append(tuple(int(c) for c in counts))
        cpl_rows.append(jnp.asarray(crows))
        cpl_cols.append(jnp.asarray(ccols))
        cpl_vals.append(jnp.asarray(cvals))

    return GeneralPartition(
        blocks=jnp.asarray(blocks),
        indices=jnp.asarray(indices),
        mask=jnp.asarray(mask),
        offsets=tuple(offsets),
        send_idx=tuple(send_idx),
        send_counts=tuple(send_counts),
        cpl_rows=tuple(cpl_rows),
        cpl_cols=tuple(cpl_cols),
        cpl_vals=tuple(cpl_vals),
        order=order,
        n=n,
        n_local=nl,
        edge_cut=int(cut.sum()) // 2,
        method=method,
        reorder=(shard_reorder(order, n_shards)
                 if n_shards > 1 and n % n_shards == 0 else None),
    )


def partition_to_dense(parts: GeneralPartition) -> np.ndarray:
    """Reassemble the dense P from interior blocks + exchange plan, back in
    the ORIGINAL vertex order — the correctness oracle of the property
    suite: equality with the input P proves every edge is covered exactly
    once across intra-shard blocks and the exchange plan (a dropped edge
    shows as a zero, a double-covered one as a doubled weight)."""
    S, nl = parts.n_shards, parts.n_local
    np_tot = parts.n_padded
    A = np.zeros((np_tot, np_tot), np.float64)
    diag = parts.dense_diag()
    for s in range(S):
        A[s * nl:(s + 1) * nl, s * nl:(s + 1) * nl] += diag[s]
    for k, d in enumerate(parts.offsets):
        sidx = np.asarray(parts.send_idx[k])
        crows = np.asarray(parts.cpl_rows[k])
        ccols = np.asarray(parts.cpl_cols[k])
        cvals = np.asarray(parts.cpl_vals[k])
        for r in range(S):
            o = (r - d) % S
            nzc = cvals[r] != 0
            gr = r * nl + crows[r][nzc]
            gc = o * nl + sidx[o][ccols[r][nzc]]
            np.add.at(A, (gr, gc), cvals[r][nzc])
    A = A[:parts.n, :parts.n]
    inv = parts.inv_order
    return A[np.ix_(inv, inv)]


# ---------------------------------------------------------------------------
# The shared exchange matvec (runs inside shard_map)
# ---------------------------------------------------------------------------
def make_exchange_matvec(interior, sends, couplings, axis: str, size: int,
                         exchange_dtype: str = "f32",
                         error_feedback: bool = True,
                         fault_spec=None, degradation: str = "zero_fill"):
    """Interior/boundary-split matvec over an arbitrary exchange plan.

    `interior(x)` is the shard-local product (dense diag einsum or
    Block-ELL SpMV); `sends` is a tuple of ``(idx, offset)`` boundary-tile
    gathers and `couplings` the matching ``(rows, cols, vals)`` receiver
    scatters.  Per call, in the same order as the banded `_halo_matvec`:

    1. every boundary tile is gathered, encoded to `exchange_dtype`
       (`repro.dist.quantize` — the PR-8 codec works on arbitrary tiles)
       and put on the wire: one ppermute per offset, each a complete
       bijection ``[(i, (i + d) % size)]``;
    2. the interior product runs while the exchange is in flight;
    3. received tiles decode and scatter-add into the output rows
       (`y.at[rows].add(vals * tile[cols])` — duplicate rows accumulate).

    Returns a `core.chebyshev.LocalMatvec`.  Under
    ``exchange_dtype="int8"`` with error feedback it follows the
    dual-signature stateful protocol of `core.chebyshev` (``mv(x, state)
    -> (y, state)``, its ``init_state``), threading one quantization
    residual per offset tile across the K orders.

    With an *active* ``fault_spec`` (see `repro.dist.faults`) the state
    additionally carries the round counter and one last-delivered tile
    per offset; every received tile passes the injector's wire-noise /
    stale / drop channels AFTER its ppermute, so the traced collective
    schedule — and the measured 2K|E| rounds — is identical to the clean
    plan's.  The offset index is the injector's link id.
    """
    dt = quantize.validate_exchange_dtype(exchange_dtype)
    exchanging = size > 1 and len(sends) > 0
    inj = faults.make_injector(fault_spec, degradation, axis, exchanging)
    use_ef = dt == "int8" and error_feedback and exchanging

    def _run(x, state):
        if inj is not None:
            k, carried, ef_state = state
        else:
            ef_state = state
        if exchanging:
            with obs.scope("exchange"):
                tiles = [jnp.take(x, idx, axis=-1) for idx, _ in sends]
                if ef_state is None:
                    wires = [quantize.encode(t, dt) for t in tiles]
                    new_ef = None
                else:
                    wires, new_ef = [], []
                    for t, r in zip(tiles, ef_state):
                        wt, rt = quantize.ef_encode(t, r, dt)
                        wires.append(wt)
                        new_ef.append(rt)
                    new_ef = tuple(new_ef)
                # (1) one complete-bijection ppermute per ring offset —
                # the multi-peer generalization of the banded left/right
                # pair
                recvs = [
                    jax.lax.ppermute(
                        wt, axis,
                        perm=[(i, (i + off) % size) for i in range(size)])
                    for wt, (_, off) in zip(wires, sends)
                ]
            # (2) interior product overlaps the exchange
            y = interior(x)
            # (3) decode on arrival; injected faults perturb only what the
            # receiver consumes — the wire traffic is already committed
            with obs.scope("exchange"):
                if inj is not None:
                    recvs = [inj.wire(rv, k, j, dt)
                             for j, rv in enumerate(recvs)]
                recvs = [quantize.decode(rv, dt, x.dtype) for rv in recvs]
                if inj is not None:
                    new_carried = []
                    faulted = []
                    for j, (rv, c) in enumerate(zip(recvs, carried)):
                        rv, c = inj.recv(rv, c, k, j)
                        faulted.append(rv)
                        new_carried.append(c)
                    recvs = faulted
                    new_state = (k + 1, tuple(new_carried), new_ef)
                else:
                    new_state = new_ef
        else:
            with obs.scope("exchange"):
                recvs = [jnp.take(x, idx, axis=-1) for idx, _ in sends]
            new_state = state
            y = interior(x)
        with obs.scope("exchange"):
            for (rows, cols, vals), rv in zip(couplings, recvs):
                y = y.at[..., rows].add(
                    vals.astype(x.dtype) * jnp.take(rv, cols, axis=-1))
        return y, new_state

    if inj is not None:
        def init_state(x):
            tiles = tuple(jnp.take(x, idx, axis=-1) for idx, _ in sends)
            ef0 = (tuple(quantize.ef_init(t) for t in tiles)
                   if use_ef else None)
            return (inj.init_round(), inj.init_carried(tiles), ef0)
    elif use_ef:
        def init_state(x):
            return tuple(quantize.ef_init(jnp.take(x, idx, axis=-1))
                         for idx, _ in sends)
    else:
        init_state = None

    def mv(x, state=None):
        if state is None:
            if inj is not None:
                return _run(x, init_state(x))[0]
            return _run(x, None)[0]
        return _run(x, state)

    return cheb.LocalMatvec(mv, init_state=init_state)


# ---------------------------------------------------------------------------
# The shared ExecutionPlan builder (both sharded backends delegate here)
# ---------------------------------------------------------------------------
def resolve_partition_arg(op, partition, n_shards: int,
                          block: Tuple[int, int] = (8, 128),
                          method: str = "bfs"):
    """Normalize a backend's ``partition=`` argument.

    Returns a `GeneralPartition` when the general path should run (the
    instance itself, or one built from a dense P for ``"general"``), else
    None (banded family: None / "banded" / BandedPartition /
    ShardedBlockELL are handled by the calling backend)."""
    if isinstance(partition, GeneralPartition):
        if partition.n_shards != n_shards:
            raise ValueError(
                f"partition has {partition.n_shards} shards but the mesh "
                f"axis has {n_shards}")
        return partition
    if isinstance(partition, str):
        if partition == "banded":
            return None
        if partition == "general":
            if callable(op.P):
                raise ValueError(
                    "partition='general' needs a dense P (or pass a "
                    "precomputed GeneralPartition built from CSR)")
            return partition_general(np.asarray(op.P), n_shards,
                                     method=method, block=block)
        raise ValueError(f"unknown partition {partition!r}; use 'banded', "
                         "'general', or a partition instance")
    return None


def build_general_plan(op, parts: GeneralPartition, mesh, axis: str, *,
                       interior: str = "block_ell",
                       use_pallas: Optional[bool] = None,
                       vmem_budget: Optional[int] = None,
                       sweep_dtype: Optional[str] = None,
                       exchange_dtype: str = "f32",
                       error_feedback: bool = True,
                       fault_spec=None, degradation: str = "zero_fill",
                       backend_name: str = "pallas_halo"):
    """ExecutionPlan over a :class:`GeneralPartition`.

    `interior` selects the shard-local representation: "block_ell" (the
    `pallas_halo` hot loop — Pallas SpMV + fused Chebyshev step, padded
    Block-ELL domain) or "dense" (the `halo` backend's per-shard dense
    diagonal einsum, small-n only).  Everything else — signatures, the
    exchange codec, the Section-V `matvec_runner` substrate, the fused
    lasso — is the plan body's (`repro.dist.operator.plan_from_form`);
    signals are permuted into partition order on entry and back on exit,
    so callers never see the relabeling (solver state like Jacobi's
    1/diag travels as signals and is permuted consistently).

    On several shards, signals enter and leave in vertex order sharded
    over `axis`.  Where S divides n, :func:`sharded_reorder` moves them to
    partition order and back inside the shard_map (counted once per trace
    as ``reorder.sharded``); otherwise the whole signal is gathered
    (``reorder.gather``).  The structure is laid out on the mesh and is
    the plan's `structure`: compiled entries take it as arguments.  On
    one shard the structure is closed over and the matvec, with no
    exchange, carries its Block-ELL for the single-launch sweep.
    """
    from .operator import LocalForm, plan_from_form

    quantize.validate_exchange_dtype(exchange_dtype)
    faults.validate_degradation(degradation)
    fault_spec = faults.resolve_fault_spec(fault_spec)
    if interior not in ("block_ell", "dense"):
        raise ValueError(f"unknown interior {interior!r}")
    S, n, nl = parts.n_shards, parts.n, parts.n_local
    dl = parts.n_local_padded if interior == "block_ell" else nl
    offsets = parts.offsets
    n_off = len(offsets)

    if interior == "block_ell":
        base_mats: Tuple[Array, ...] = (parts.blocks, parts.indices,
                                        parts.mask)
        band = graphmod.block_ell_band(parts.indices, parts.mask,
                                       parts.blocks.shape[3:])
    else:
        base_mats = (jnp.asarray(parts.dense_diag()),)
    nbase = len(base_mats)
    ex_mats = []
    for k in range(n_off):
        ex_mats += [parts.send_idx[k], parts.cpl_rows[k],
                    parts.cpl_cols[k], parts.cpl_vals[k]]
    mats = base_mats + tuple(ex_mats)
    nmats = len(mats)

    def local(mine):
        """A shard's matvec from its structure (reorder arrays after)."""
        base, ex = mine[:nbase], mine[nbase:nmats]
        if interior == "block_ell":
            local_A = graphmod.BlockELL(blocks=base[0], indices=base[1],
                                        mask=base[2], n=nl, band=band)

            def interior_mv(x):
                return ops.spmv(local_A, x, use_pallas=use_pallas)
        else:
            dg = base[0]

            def interior_mv(x):
                return jnp.einsum("ij,...j->...i", dg, x)

        sends = tuple((ex[4 * k], offsets[k]) for k in range(n_off))
        coupl = tuple((ex[4 * k + 1], ex[4 * k + 2], ex[4 * k + 3])
                      for k in range(n_off))
        mv = make_exchange_matvec(interior_mv, sends, coupl, axis, S,
                                  exchange_dtype, error_feedback,
                                  fault_spec, degradation)
        if S == 1 and interior == "block_ell":
            # no exchange on a 1-shard mesh: the single-launch sweep
            # kernel may run, exactly like the banded 1-shard path
            mv = dataclasses.replace(mv, block_ell=local_A,
                                     vmem_budget=vmem_budget,
                                     sweep_dtype=sweep_dtype)
        return mv

    info = {
        "mesh_axis": axis,
        "n_shards": S,
        "n_local": nl,
        "halo_width": parts.halo,
        "partition": "general",
        "partition_method": parts.method,
        "partition_fingerprint": parts.fingerprint,
        "partition_offsets": parts.offsets,
        "partition_tile_widths": parts.tile_widths,
        "edge_cut": parts.edge_cut,
        "exchange_dtype": exchange_dtype,
        "error_feedback": bool(error_feedback),
        "fault_spec": faults.spec_info(fault_spec),
        "degradation": degradation,
        "fault_key": faults.fault_key(fault_spec, degradation),
        "exchange_collectives_per_round": n_off if S > 1 else 0,
        "halo_bytes_per_apply": general_bytes_per_apply(
            parts, op.K, 1, exchange_dtype) if S > 1 else 0,
        "halo_bytes_per_adjoint": general_bytes_per_apply(
            parts, op.K, op.eta, exchange_dtype) if S > 1 else 0,
    }
    if interior == "block_ell":
        info.update({
            "n_local_padded": dl,
            "block": (int(parts.blocks.shape[3]),
                      int(parts.blocks.shape[4])),
            "nnz_blocks": parts.nnz_blocks,
            "blockell_fill": graphmod.block_ell_fill(parts.blocks),
            "spmv_band": band,
            "sweep_dtype": sweep_dtype or "f32",
            "sweep_vmem_bytes": ops.cheb_sweep_vmem_bytes(
                parts.blocks.shape[1:], dl, op.eta,
                scratch_dtype=sweep_dtype),
        })

    def pad(x, mine):
        return ops.pad_trailing(x, dl)

    recurrence = functools.partial(ops.fused_cheb_recurrence,
                                   use_pallas=use_pallas)
    if S == 1:
        form = LocalForm(local=local, recurrence=recurrence, arrays=mats,
                         glob_in=parts.to_partition_order, enter=pad,
                         leave=lambda y, mine: ops.crop(y, n),
                         glob_out=parts.from_partition_order)
        return plan_from_form(op, backend_name, form, info)

    # the structure lives sharded on the mesh; every entry reads it as
    # its first argument, so compiled programs take it as arguments
    rd = parts.reorder
    sharding = NamedSharding(mesh, P(axis))
    structure = tuple(jax.device_put(m, sharding)
                      for m in mats + (rd.arrays if rd is not None else ()))
    if rd is not None:
        info.update({
            "reorder": "sharded",
            "reorder_tile_widths": rd.tile_widths,
            # one signal in, eta out, f32 on the wire
            "reorder_bytes_per_apply":
                4 * (1 + op.eta) * sum(rd.tile_widths),
        })
        r_off, nr = rd.offsets, len(rd.offsets)

        def enter(x, mine):
            """Vertex order -> partition order (padded to dl), on a shard."""
            rl = mine[nmats:]
            x = sharded_reorder(x, rl[:nr], rl[2 * nr], r_off, axis, S)
            return ops.pad_trailing(x, dl)

        def leave(y, mine):
            """Partition order -> vertex order (logical nl), on a shard."""
            rl = mine[nmats:]
            return sharded_reorder(ops.crop(y, nl), rl[nr:2 * nr],
                                   rl[2 * nr + 1], r_off, axis, S,
                                   back=True)

        places = dict(enter=enter, leave=leave, count="reorder.sharded")
    else:
        info["reorder"] = "gather"
        places = dict(
            glob_in=lambda x: ops.pad_trailing(parts.to_partition_order(x),
                                               S * nl),
            enter=pad, leave=lambda y, mine: ops.crop(y, nl),
            glob_out=lambda y: parts.from_partition_order(ops.crop(y, n)),
            count="reorder.gather")
    form = LocalForm(local=local, recurrence=recurrence, arrays=structure,
                     structure=True, mesh=mesh, axis=axis, **places)
    return plan_from_form(op, backend_name, form, info)
