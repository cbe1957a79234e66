"""Graph generators, one module per ``graph.kind`` of a configuration.

``load(spec)`` imports ``bench.graphs.<kind>`` and calls its
``build(spec)``, which returns a :class:`Graph`: the combinatorial
Laplacian L = D - W in CSR (numpy, on the host), its Anderson-Morley
bound on lambda_max and |E|.  A new kind of graph is a new module here.
"""
import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    n: int
    indptr: np.ndarray    # (n + 1,) int64
    indices: np.ndarray   # (nnz,) int64 column ids, sorted within a row
    data: np.ndarray      # (nnz,) float32 values of L
    lmax: float           # max over edges u~v of d(u) + d(v)
    n_edges: int

    @classmethod
    def from_edges(cls, n, lo, hi, w):
        """L from the undirected edges lo[i] -- hi[i] of weight w[i]
        (lo != hi, no repeats)."""
        deg = np.zeros(n, np.float64)
        np.add.at(deg, lo, w)
        np.add.at(deg, hi, w)
        lmax = float((deg[lo] + deg[hi]).max())
        diag = np.arange(n)
        rows = np.concatenate([lo, hi, diag]).astype(np.int64)
        cols = np.concatenate([hi, lo, diag]).astype(np.int64)
        vals = np.concatenate([-w, -w, deg.astype(np.float32)]).astype(
            np.float32)
        order = np.lexsort((cols, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        indptr = np.searchsorted(rows, np.arange(n + 1))
        return cls(n=n, indptr=indptr, indices=cols, data=vals, lmax=lmax,
                   n_edges=int(lo.size))

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    def row_ids(self) -> np.ndarray:
        return np.repeat(np.arange(self.n, dtype=np.int64),
                         np.diff(self.indptr))

    def dense(self) -> np.ndarray:
        out = np.zeros((self.n, self.n), np.float32)
        out[self.row_ids(), self.indices] = self.data
        return out


def load(spec) -> Graph:
    return importlib.import_module(f"bench.graphs.{spec['kind']}").build(
        spec)
