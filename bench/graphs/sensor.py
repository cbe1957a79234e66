"""Random sensor network (the paper's Section IV-D).

``n`` sensors uniform in the unit square; an edge joins two sensors at
distance d <= kappa, with weight exp(-d^2 / (2 theta^2)).  Draws repeat
from one generator until the graph is connected, as the paper discards
disconnected realizations.
"""
import numpy as np

from bench.graphs import Graph


def _connected(n, lo, hi):
    adj = [[] for _ in range(n)]
    for a, b in zip(lo.tolist(), hi.tolist()):
        adj[a].append(b)
        adj[b].append(a)
    seen = np.zeros(n, bool)
    seen[0] = True
    stack = [0]
    while stack:
        for b in adj[stack.pop()]:
            if not seen[b]:
                seen[b] = True
                stack.append(b)
    return bool(seen.all())


def build(spec):
    n = int(spec["n"])
    theta, kappa = float(spec["theta"]), float(spec["kappa"])
    rng = np.random.default_rng(int(spec["graph_seed"]))
    for _ in range(int(spec["max_draws"])):
        xy = rng.uniform(size=(n, 2))
        d2 = ((xy[:, None, :] - xy[None, :, :]) ** 2).sum(-1)
        lo, hi = np.nonzero(np.triu(d2 <= kappa * kappa, k=1))
        if _connected(n, lo, hi):
            w = np.exp(-d2[lo, hi] / (2.0 * theta * theta)).astype(np.float32)
            return Graph.from_edges(n, lo, hi, w)
    raise RuntimeError(f"no connected sensor graph in {spec['max_draws']} "
                       "draws")
