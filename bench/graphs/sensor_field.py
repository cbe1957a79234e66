"""The paper's sensor network (Section IV-D) over a larger field.

``n`` sensors uniform in a square at the paper's density (``density``
sensors per unit area: its 500 sensors in the unit square), so the side
is sqrt(n / density); an edge joins two sensors at distance d <= kappa,
with weight exp(-d^2 / (2 theta^2)), theta and kappa as in the paper.
The mean degree is then the paper's at any n.

Built in CSR without an n x n distance matrix: sensors are binned into
kappa-wide cells, and each is paired with the sensors of its own cell
and of the four cells after it (right, and the row above), which covers
every pair closer than kappa once.  Sensors are numbered cell by cell,
row after row of cells, as a survey of the field would number them.

At a million sensors some have no neighbour within kappa; they are kept,
with zero rows of L (the paper's network of 500 is drawn again until it
is connected, which at this size the density never gives).
"""
import numpy as np

from bench.graphs import Graph

#: Forward neighbour cells (row offset, column offset): the own cell,
#: the next to the right, and three in the row above.
FORWARD = ((0, 0), (0, 1), (1, -1), (1, 0), (1, 1))


def positions(n, side, kappa, rng):
    """(n, 2) positions, numbered cell by cell (cells of side kappa)."""
    xy = rng.uniform(0.0, side, size=(n, 2))
    cells = int(np.ceil(side / kappa))
    c = np.minimum((xy / kappa).astype(np.int64), cells - 1)
    return xy[np.lexsort((c[:, 0], c[:, 1]))], cells


def near_pairs(xy, kappa, cells):
    """The pairs lo < hi of sensors at distance <= kappa, by cells."""
    c = np.minimum((xy / kappa).astype(np.int64), cells - 1)
    cell = c[:, 1] * cells + c[:, 0]   # positions() left these sorted
    start = np.searchsorted(cell, np.arange(cells * cells + 1))
    count = np.diff(start)
    lo, hi = [], []
    for dy, dx in FORWARD:
        ny, nx = c[:, 1] + dy, c[:, 0] + dx
        inside = (ny < cells) & (nx >= 0) & (nx < cells)
        nb = np.where(inside, ny * cells + nx, 0)
        for k in range(int(count.max())):
            i = np.flatnonzero(inside & (k < count[nb]))
            j = start[nb[i]] + k
            if (dy, dx) == (0, 0):
                i, j = i[j > i], j[j > i]
            near = ((xy[i] - xy[j]) ** 2).sum(1) <= kappa * kappa
            lo.append(i[near])
            hi.append(j[near])
    lo, hi = np.concatenate(lo), np.concatenate(hi)
    order = np.lexsort((hi, lo))
    return lo[order], hi[order]


def build(spec):
    n = int(spec["n"])
    theta, kappa = float(spec["theta"]), float(spec["kappa"])
    side = float(np.sqrt(n / float(spec["density"])))
    rng = np.random.default_rng(int(spec["graph_seed"]))
    xy, cells = positions(n, side, kappa, rng)
    lo, hi = near_pairs(xy, kappa, cells)
    d2 = ((xy[lo] - xy[hi]) ** 2).sum(1)
    w = np.exp(-d2 / (2.0 * theta * theta)).astype(np.float32)
    return Graph.from_edges(n, lo, hi, w)
