"""Weighted graphs, Laplacians and spectral bounds.

Implements the communication-graph model of Section I-A / II-C of the paper:
undirected weighted graphs G = {V, E, W}, the combinatorial Laplacian
L = D - W, the normalized Laplacian L_norm = D^{-1/2} L D^{-1/2}, the
Anderson-Morley upper bound on lambda_max used by Algorithm 1, and the
random sensor-network generator of Section IV-D.

Dense (N, N) arrays are used for the paper-scale experiments (N = 500); a
static Block-ELL sparse format (`BlockELL`) backs the Pallas SpMV kernel and
the sharded distributed path for large N.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# Graph container
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Graph:
    """An undirected weighted graph held as a dense weight matrix.

    Attributes:
      W: (N, N) symmetric non-negative weight matrix, zero diagonal.
      coords: optional (N, d) vertex coordinates (sensor positions).
    """

    W: Array
    coords: Optional[Array] = None

    @property
    def n_vertices(self) -> int:
        return self.W.shape[0]

    @property
    def n_edges(self) -> int:
        """|E| — number of undirected edges with non-zero weight."""
        return int(jnp.count_nonzero(jnp.triu(self.W, k=1)))

    def degrees(self) -> Array:
        return jnp.sum(self.W, axis=1)

    def laplacian(self, kind: str = "combinatorial") -> Array:
        return laplacian(self.W, kind=kind)

    def lambda_max_bound(self, kind: str = "combinatorial") -> float:
        return lambda_max_bound(self.W, kind=kind)

    def is_connected(self) -> bool:
        return is_connected(np.asarray(self.W))


def laplacian(W: Array, kind: str = "combinatorial") -> Array:
    """Graph Laplacian of a weight matrix (Section II-C).

    kind:
      'combinatorial' : L = D - W
      'normalized'    : L_norm = D^{-1/2} L D^{-1/2}  (conventional 0/0 -> 0)
    """
    d = jnp.sum(W, axis=1)
    L = jnp.diag(d) - W
    if kind == "combinatorial":
        return L
    if kind == "normalized":
        inv_sqrt = jnp.where(d > 0, 1.0 / jnp.sqrt(jnp.where(d > 0, d, 1.0)), 0.0)
        return inv_sqrt[:, None] * L * inv_sqrt[None, :]
    raise ValueError(f"unknown Laplacian kind: {kind!r}")


def lambda_max_bound(W: Array, kind: str = "combinatorial") -> float:
    """Upper bound on lambda_max(L), computable from local degrees only.

    For the combinatorial Laplacian this is the Anderson-Morley bound
    lambda_max <= max{ d(m) + d(n) : m ~ n }  ([46], [47, Cor. 3.2]),
    exactly the bound suggested in Section IV-B. For the normalized
    Laplacian the spectrum is contained in [0, 2].
    """
    if kind == "normalized":
        return 2.0
    d = jnp.sum(W, axis=1)
    pair = d[:, None] + d[None, :]
    bound = jnp.max(jnp.where(W > 0, pair, 0.0))
    # Fall back to 2*max degree for edgeless graphs.
    bound = jnp.maximum(bound, jnp.max(d))
    return float(bound)


def k_scaling_matrix(W: Array, gamma: float) -> Array:
    """Ando & Zhang's K-scaling kernel matrix (Section III-D):

       S = (gamma I + D)^{-1/2} (gamma I + L) (gamma I + D)^{-1/2}

    Has the sparsity pattern of L; reduces to L_norm at gamma = 0.
    """
    n = W.shape[0]
    d = jnp.sum(W, axis=1)
    L = jnp.diag(d) - W
    scale = 1.0 / jnp.sqrt(gamma + d)
    return scale[:, None] * (gamma * jnp.eye(n) + L) * scale[None, :]


def is_connected(W: np.ndarray) -> bool:
    """BFS connectivity check (numpy; used by experiment drivers, as the paper
    discards disconnected random graph realizations — footnote 5)."""
    n = W.shape[0]
    adj = W > 0
    seen = np.zeros(n, dtype=bool)
    stack = [0]
    seen[0] = True
    while stack:
        u = stack.pop()
        nbrs = np.nonzero(adj[u] & ~seen)[0]
        seen[nbrs] = True
        stack.extend(nbrs.tolist())
    return bool(seen.all())


# ---------------------------------------------------------------------------
# Random sensor network of Section IV-D
# ---------------------------------------------------------------------------
def sensor_graph(
    key: Array,
    n: int = 500,
    theta: float = 0.074,
    kappa: float = 0.075,
) -> Graph:
    """Random sensor network of Section IV-D.

    n sensors placed uniformly in [0,1]^2; thresholded Gaussian kernel
    weights  w(e) = exp(-d(i,j)^2 / (2 theta^2)) if d(i,j) <= kappa else 0.
    """
    coords = jax.random.uniform(key, (n, 2))
    diff = coords[:, None, :] - coords[None, :, :]
    dist2 = jnp.sum(diff * diff, axis=-1)
    w = jnp.exp(-dist2 / (2.0 * theta * theta))
    w = jnp.where(dist2 <= kappa * kappa, w, 0.0)
    w = w - jnp.diag(jnp.diag(w))
    return Graph(W=w, coords=coords)


def connected_sensor_graph(
    key: Array, n: int = 500, theta: float = 0.074, kappa: float = 0.075,
    max_tries: int = 50,
) -> Tuple[Graph, Array]:
    """Draw sensor graphs until a connected one appears (paper footnote 5)."""
    for _ in range(max_tries):
        key, sub = jax.random.split(key)
        g = sensor_graph(sub, n=n, theta=theta, kappa=kappa)
        if g.is_connected():
            return g, key
    raise RuntimeError("could not draw a connected sensor graph")


def ring_graph(n: int, weight: float = 1.0) -> Graph:
    """Ring graph — the device-communication graph used by Chebyshev gossip."""
    W = np.zeros((n, n), dtype=np.float32)
    for i in range(n):
        W[i, (i + 1) % n] = weight
        W[(i + 1) % n, i] = weight
    return Graph(W=jnp.asarray(W))


def torus_graph(rows: int, cols: int, weight: float = 1.0) -> Graph:
    """2-D torus graph (device mesh topology analog: ICI torus)."""
    n = rows * cols
    W = np.zeros((n, n), dtype=np.float32)

    def idx(r, c):
        return (r % rows) * cols + (c % cols)

    for r in range(rows):
        for c in range(cols):
            u = idx(r, c)
            for v in (idx(r + 1, c), idx(r, c + 1)):
                W[u, v] = weight
                W[v, u] = weight
    return Graph(W=jnp.asarray(W))


def path_graph(n: int, weight: float = 1.0) -> Graph:
    W = np.zeros((n, n), dtype=np.float32)
    for i in range(n - 1):
        W[i, i + 1] = weight
        W[i + 1, i] = weight
    return Graph(W=jnp.asarray(W))


def two_cluster_graph(
    key: Array, n_per: int = 20, p_in: float = 0.9, p_out: float = 0.05
) -> Tuple[Graph, Array]:
    """Stochastic two-block graph + ground-truth labels, for SSL tests."""
    n = 2 * n_per
    labels = jnp.concatenate([jnp.zeros(n_per, jnp.int32), jnp.ones(n_per, jnp.int32)])
    u = jax.random.uniform(key, (n, n))
    u = jnp.triu(u, k=1)
    same = labels[:, None] == labels[None, :]
    p = jnp.where(same, p_in, p_out)
    upper = (u < p) & (jnp.triu(jnp.ones((n, n), bool), k=1))
    W = jnp.where(upper | upper.T, 1.0, 0.0)
    return Graph(W=W), labels


# ---------------------------------------------------------------------------
# Block-ELL static sparse format (TPU adaptation — DESIGN.md §3)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BlockELL:
    """Static block-sparse matrix: fixed number of column-block slots per row
    block. Shapes are static, making the format compatible with XLA/Pallas.

      blocks:  (n_row_blocks, max_slots, bs_r, bs_c) block values
      indices: (n_row_blocks, max_slots) int32 column-block index per slot
      mask:    (n_row_blocks, max_slots) bool slot validity
      n:       logical (unpadded) dimension
      panels:  (n_row_blocks, bs_r, max_slots * bs_c) the same values as
               row panels (`block_panels`), the layout the Pallas SpMV
               streams; laid out from `blocks` when the structure is
               made (once per plan on one device; once per application,
               outside the order loop, for a shard's structure made
               inside shard_map), unless given
      band:    static bound on how far a valid slot's column block lies
               from its row block's own (`block_ell_band`), or None when
               unknown; a known band lets the SpMV read each group of
               row blocks' column tiles from one VMEM window
    """

    blocks: Array
    indices: Array
    mask: Array
    n: int
    panels: Optional[Array] = None
    band: Optional[int] = None

    def __post_init__(self):
        if self.panels is None:
            object.__setattr__(self, "panels", block_panels(self.blocks))

    @property
    def block_shape(self) -> Tuple[int, int]:
        return (self.blocks.shape[2], self.blocks.shape[3])

    @property
    def n_row_blocks(self) -> int:
        return self.blocks.shape[0]

    @property
    def padded_n(self) -> int:
        return self.n_row_blocks * self.blocks.shape[2]

    def todense(self) -> Array:
        bs_r, bs_c = self.block_shape
        nb = self.n_row_blocks
        pn = self.padded_n
        out = jnp.zeros((pn, pn), self.blocks.dtype)
        for rb in range(nb):
            for s in range(self.blocks.shape[1]):
                cb = int(self.indices[rb, s])
                valid = bool(self.mask[rb, s])
                if valid:
                    out = out.at[
                        rb * bs_r : (rb + 1) * bs_r, cb * bs_c : (cb + 1) * bs_c
                    ].add(self.blocks[rb, s])
        return out[: self.n, : self.n]


def block_panels(blocks):
    """(..., nrb, slots, br, bc) Block-ELL blocks -> (..., nrb, br,
    slots * bc) row panels: a row block's slots side by side, so a narrow
    bc does not pad every block to 128 lanes in HBM and one row block is
    one MXU product.  Works on numpy and jax arrays."""
    *lead, nrb, slots, br, bc = blocks.shape
    return blocks.swapaxes(-3, -2).reshape(*lead, nrb, br, slots * bc)


def block_ell_fill(blocks) -> float:
    """Stored non-zeros over the entries the Block-ELL layout holds
    (nrb * slots * br * bc, summed over shards): the share of the bytes
    an SpMV streams that carry a matrix entry."""
    count = np.count_nonzero if isinstance(blocks, np.ndarray) \
        else jnp.count_nonzero
    return int(count(blocks)) / blocks.size


def block_ell_band(indices, mask, block: Tuple[int, int]) -> int:
    """The band of a Block-ELL structure: the largest distance, in column
    blocks, from a valid slot's column block to the column blocks that
    its row block's own rows span (row block rb spans column blocks
    rb * br // bc to ((rb + 1) * br - 1) // bc; for square blocks, rb).
    Padded slots do not count.  indices/mask: (..., nrb, slots) numpy
    arrays, leading shard dims included (the max over shards); 0 for a
    structure with no valid slot."""
    br, bc = block
    indices = np.asarray(indices, np.int64)
    mask = np.asarray(mask, bool)
    rb = np.arange(indices.shape[-2], dtype=np.int64)[:, None]
    lo, hi = rb * br // bc, ((rb + 1) * br - 1) // bc
    dist = np.maximum(lo - indices, indices - hi)
    return int(np.max(dist, initial=0, where=mask))


def to_block_ell(
    M: np.ndarray, block_shape: Tuple[int, int] = (8, 128)
) -> BlockELL:
    """Convert a dense (sparse-in-content) matrix to Block-ELL.

    Blocks that are entirely zero are dropped; every row block gets the same
    (max over row blocks) number of slots, padded with masked zero blocks.
    Block shape defaults to the TPU-native (8, 128) tile.
    """
    M = np.asarray(M)
    n = M.shape[0]
    bs_r, bs_c = block_shape
    # Pad the (square) matrix to a multiple of lcm(bs_r, bs_c) in both dims so
    # the SpMV output vector can feed straight back in (Chebyshev recurrence).
    unit = int(np.lcm(bs_r, bs_c))
    n_pad = -(-n // unit) * unit
    nrb = n_pad // bs_r
    ncb = n_pad // bs_c
    Mp = np.pad(M, ((0, n_pad - n), (0, n_pad - n)))
    # Find nonzero blocks per row block.
    per_row: list[list[tuple[int, np.ndarray]]] = []
    for rb in range(nrb):
        row = []
        for cb in range(ncb):
            blk = Mp[rb * bs_r : (rb + 1) * bs_r, cb * bs_c : (cb + 1) * bs_c]
            if np.any(blk != 0):
                row.append((cb, blk))
        per_row.append(row)
    max_slots = max(1, max(len(r) for r in per_row))
    blocks = np.zeros((nrb, max_slots, bs_r, bs_c), dtype=M.dtype)
    indices = np.zeros((nrb, max_slots), dtype=np.int32)
    mask = np.zeros((nrb, max_slots), dtype=bool)
    for rb, row in enumerate(per_row):
        for s, (cb, blk) in enumerate(row):
            blocks[rb, s] = blk
            indices[rb, s] = cb
            mask[rb, s] = True
    return BlockELL(
        blocks=jnp.asarray(blocks),
        indices=jnp.asarray(indices),
        mask=jnp.asarray(mask),
        n=n,
        panels=jnp.asarray(block_panels(blocks)),
        band=block_ell_band(indices, mask, block_shape),
    )


def block_ell_matvec_ref(A: BlockELL, x: Array) -> Array:
    """Reference Block-ELL matvec (pure jnp, vectorized over slots)."""
    bs_r, bs_c = A.block_shape
    pn = A.padded_n
    xp = jnp.pad(x, (0, pn - x.shape[0]))
    xb = xp.reshape(-1, bs_c)  # (n_col_blocks, bs_c)
    gathered = xb[A.indices]  # (nrb, slots, bs_c)
    prod = jnp.einsum("rsij,rsj->rsi", A.blocks, gathered)
    prod = jnp.where(A.mask[:, :, None], prod, 0.0)
    y = jnp.sum(prod, axis=1).reshape(pn)
    return y[: A.n]


def spatial_sort(graph: Graph) -> Tuple[Graph, np.ndarray]:
    """Reorder vertices by their y coordinate (strip order).

    With a thresholded-kernel sensor graph (connection radius kappa), two
    adjacent vertices differ in y-rank by at most the population of a
    kappa-height strip, so equal contiguous index blocks of size
    nl >> n*kappa couple only with adjacent blocks: W becomes block-
    tridiagonal and the sharded banded `halo` plan is exact
    (`partition_banded` reports the residual `leak` so callers can verify).
    """
    assert graph.coords is not None, "spatial_sort needs coordinates"
    coords = np.asarray(graph.coords)
    order = np.argsort(coords[:, 1], kind="stable")
    W = np.asarray(graph.W)[np.ix_(order, order)]
    return Graph(W=jnp.asarray(W), coords=jnp.asarray(coords[order])), order
