"""Closed-form checks of the pluggable edge-cut partition layer
(`repro.dist.partition`).

The property suite in tests/test_property.py covers the randomized
invariants (every edge covered exactly once, exchange symmetry, perm
bijections); this module pins the closed forms of ISSUE 9:

* partitioner edge-cut <= a sanity bound on path / grid / community
  fixtures (a contiguous chop of a good ordering cannot cut more than the
  boundary structure allows);
* `commstats.verify_message_scaling` == 2K|E| EXACTLY on a non-banded
  8-shard payload (the paper's Section IV-B count, measured from the
  jaxpr — max_rel_dev must be 0.0, not "within 10%");
* bytes-per-round == boundary-size x dtype wire width for each of
  f32 / bf16 / int8 (the PR-8 codec on arbitrary boundary tiles);
* the overfull-slot hazard raises instead of truncating (silently
  dropped blocks are silently wrong matvecs).
"""
import numpy as np
import pytest

from repro.core import graph as graphmod
from repro.dist import partition as pm
from repro.dist.backends.pallas_halo import partition_block_ell

from _subproc import run_payload


def _roundtrip_err(P, parts):
    return float(np.abs(pm.partition_to_dense(parts) - np.asarray(P)).max())


# ---------------------------------------------------------------------------
# Edge-cut sanity bounds
# ---------------------------------------------------------------------------
def test_path_graph_cut_is_minimal():
    # A path chopped into S contiguous runs cuts exactly S-1 edges; BFS
    # from a degree-1 endpoint recovers the natural order, so the
    # partitioner must land on the optimum.
    g = graphmod.path_graph(64)
    parts = pm.partition_general(g.laplacian(), 8, block=(8, 8))
    assert parts.edge_cut == 7
    assert _roundtrip_err(g.laplacian(), parts) < 1e-6


def test_torus_graph_cut_bound():
    # 8x16 torus at 4 shards: any contiguous chop of a row-major-ish BFS
    # order cuts O(rows) edges per shard boundary; gate at the loose
    # closed form 4 * rows * shards (a random order would cut ~ |E|/2
    # = 256, far above it).
    g = graphmod.torus_graph(8, 16)
    parts = pm.partition_general(g.laplacian(), 4, block=(8, 8))
    assert parts.edge_cut <= 4 * 8 * 4
    assert _roundtrip_err(g.laplacian(), parts) < 1e-6


@pytest.mark.parametrize("method", ["bfs", "spectral"])
def test_community_graph_cut_bound(method):
    # 8 communities of 32 vertices, ~2 inter-community edges per
    # community: intra-community edges dominate, so a partitioner that
    # respects community structure cuts a small fraction of |E|.
    csr, meta = pm.community_graph_csr(256, n_communities=8, seed=1)
    parts = pm.partition_general(csr, 8, method=method, block=(8, 8))
    assert parts.edge_cut <= csr.n_edges // 2, (
        f"{method} cut {parts.edge_cut} of {csr.n_edges} edges")
    assert _roundtrip_err(csr.to_dense(), parts) < 1e-6


def test_spectral_beats_random_on_communities():
    csr, _ = pm.community_graph_csr(256, n_communities=8, seed=1)
    rng = np.random.default_rng(0)
    random_parts = pm.partition_general(
        csr, 8, order=rng.permutation(256), block=(8, 8))
    spectral_parts = pm.partition_general(
        csr, 8, method="spectral", block=(8, 8))
    assert spectral_parts.edge_cut < random_parts.edge_cut


# ---------------------------------------------------------------------------
# Overfull-slot hazard: raise, never truncate
# ---------------------------------------------------------------------------
def test_partition_general_overfull_raises():
    # A star graph couples the hub row block to every column block; with
    # max_slots=1 the packer must refuse rather than drop blocks.
    n = 64
    W = np.zeros((n, n), np.float32)
    W[0, 1:] = 1.0
    W[1:, 0] = 1.0
    L = np.asarray(graphmod.laplacian(W))
    with pytest.raises(pm.OverfullSlotsError):
        pm.partition_general(L, 1, block=(8, 8), max_slots=1,
                             order=np.arange(n))
    # generous budget: packs fine and stays exact
    parts = pm.partition_general(L, 1, block=(8, 8), max_slots=8,
                                 order=np.arange(n))
    assert _roundtrip_err(L, parts) < 1e-6


def test_partition_block_ell_overfull_raises():
    import jax

    # a seed whose sorted graph stays inside the 4-shard band, so the
    # default packing is lossless and max_slots=1 is genuinely overfull
    g = graphmod.sensor_graph(jax.random.PRNGKey(1), n=64, kappa=0.3)
    gs, _ = graphmod.spatial_sort(g)
    with pytest.raises(pm.OverfullSlotsError):
        partition_block_ell(np.asarray(gs.laplacian()), 4, block=(8, 8),
                            max_slots=1)
    # and the default (max_slots=None) still packs losslessly
    parts, leak = partition_block_ell(np.asarray(gs.laplacian()), 4,
                                      block=(8, 8))
    assert leak < 1e-8


# ---------------------------------------------------------------------------
# Measured 2K|E| == closed form, exactly, on a non-banded 8-shard mesh
# ---------------------------------------------------------------------------
PAYLOAD = r"""
import numpy as np, jax
from repro.core.wavelets import sgwt_multipliers
from repro.dist import GraphOperator, verify_message_scaling
from repro.dist import partition as pm
from repro.dist.quantize import tile_wire_bytes

csr, meta = pm.community_graph_csr(256, n_communities=8, seed=5)
E = csr.n_edges
op = GraphOperator(P=csr.to_dense(),
                   multipliers=sgwt_multipliers(meta["lmax"], 3),
                   lmax=meta["lmax"], K=9)
mesh = jax.make_mesh((8,), ("graph",))
parts = pm.partition_general(csr, 8, block=(8, 8))
assert len(parts.offsets) > 2, (
    "fixture is effectively banded — offsets %r" % (parts.offsets,))

for backend in ("halo", "pallas_halo"):
    plan = op.plan(backend, mesh=mesh, partition=parts)
    v = verify_message_scaling(plan, E, n=256, batch=64)
    assert v["max_rel_dev"] == 0.0, (backend, v["measured"], v["predicted"])
    assert v["measured"]["apply"] == 2 * op.K * E
    assert v["measured"]["apply_gram"] == 4 * op.K * E
    assert v["per_signal_messages"]["apply"] == 2 * op.K * E / 64

# bytes per round == boundary size x dtype wire width, per exchange dtype
for dt in ("f32", "bf16", "int8"):
    plan = op.plan("pallas_halo", mesh=mesh, partition=parts,
                   exchange_dtype=dt)
    v = verify_message_scaling(plan, E, n=256)
    s = v["stats"]["apply"]
    got = s["bytes_per_shard"] / s["exchange_rounds"]
    want = sum(tile_wire_bytes(h, dt) for h in parts.tile_widths)
    assert got == want, (dt, got, want)
    assert want == parts.wire_bytes_per_round(dt)
print("OK")
"""


def test_message_scaling_exact_8_shards():
    assert "OK" in run_payload(PAYLOAD, n_devices=8)


def _bfs_order_by_scans(csr):
    """The BFS order written as a scan of every vertex per restart: the
    reference for the partitioner's forward-moving restart pointer."""
    n = csr.n
    deg = np.diff(csr.indptr)
    visited = np.zeros(n, bool)
    order = []
    while len(order) < n:
        unv = np.flatnonzero(~visited)
        frontier = np.array([unv[np.argmin(deg[unv])]])
        visited[frontier] = True
        while frontier.size:
            order.extend(frontier.tolist())
            nbr = pm._ragged_gather(csr.indptr, csr.indices, frontier)
            frontier = np.unique(nbr[~visited[nbr]])
            visited[frontier] = True
    return np.array(order)


@pytest.mark.parametrize("n, seed", [(300, 0), (5000, 1), (9000, 2)])
def test_bfs_restarts_take_least_degree_then_least_id(n, seed):
    # components of many sizes and isolated vertices, so the BFS
    # restarts thousands of times
    rng = np.random.default_rng(seed)
    lo = rng.integers(0, n, n // 2)
    hi = rng.integers(0, n, n // 2)
    keep = lo != hi
    rows = np.concatenate([lo[keep], hi[keep], np.arange(n)])
    cols = np.concatenate([hi[keep], lo[keep], np.arange(n)])
    csr = pm.CSRMatrix.from_coo(n, rows, cols, np.ones(rows.size, np.float32))
    assert np.array_equal(pm.edge_cut_order(csr, 4), _bfs_order_by_scans(csr))
