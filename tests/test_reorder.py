"""A multi-shard general plan keeps its signals in vertex order, sharded
over the mesh: the shard-to-shard move between vertex and partition
order (`partition.ShardReorder`, `partition.sharded_reorder`) against
plain gathers, the plans built on it against the `dense` backend, and
what it costs and counts.
"""
import numpy as np
import pytest

from repro.dist import partition as pm

from _subproc import run_payload


def _emulate(x, rd, back=False):
    """The moves of `rd` done with numpy on an (S, ..., nl) stack of
    shards: each offset's rows packed, rolled round the ring, placed."""
    S = x.shape[0]
    send = rd.slots if back else rd.rows
    place = rd.to_vertex if back else rd.to_partition
    out = np.empty_like(x)
    for me in range(S):
        parts = [x[me]]
        for d, idx in zip(rd.offsets, send):
            src = (me + d) % S if back else (me - d) % S
            parts.append(x[src][..., idx[src]])
        out[me] = np.concatenate(parts, axis=-1)[..., place[me]]
    return out


@pytest.mark.parametrize("n, S", [(12, 3), (64, 4), (1000, 8)])
def test_shard_reorder_is_the_partition_order(n, S):
    order = np.random.default_rng(n).permutation(n)
    rd = pm.shard_reorder(order, S)
    x = np.random.default_rng(1).standard_normal((2, 3, n))
    shards = np.moveaxis(x.reshape(2, 3, S, n // S), 2, 0)
    moved = _emulate(shards, rd)
    want = x[..., order]
    assert np.array_equal(np.moveaxis(moved, 0, 2).reshape(2, 3, n), want)
    assert np.array_equal(_emulate(moved, rd, back=True), shards)
    # only rows that change shard are sent, each offset's tile as wide
    # as its largest sender
    q, r = order // (n // S), np.arange(n) // (n // S)
    assert sum(map(sum, rd.counts)) == int(np.count_nonzero(q != r))
    assert rd.tile_widths == tuple(max(c) for c in rd.counts)


def test_shard_reorder_needs_even_shards():
    with pytest.raises(ValueError, match="multiple"):
        pm.shard_reorder(np.arange(10), 4)
    csr, _ = pm.community_graph_csr(250, n_communities=5, seed=1)
    assert pm.partition_general(csr, 4, block=(8, 8)).reorder is None
    assert pm.partition_general(csr, 1, block=(8, 8)).reorder is None
    assert pm.partition_general(csr, 5, block=(8, 8)).reorder is not None


# ---------------------------------------------------------------------------
# On four devices: the collective against jnp.take, and the round trip
# ---------------------------------------------------------------------------
ROUND_TRIP = r"""
import numpy as np, jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.wavelets import sgwt_multipliers
from repro.dist import GraphOperator
from repro.dist import partition as pm

mesh = jax.make_mesh((4,), ("graph",))
for n in (256, 254):
    csr, meta = pm.community_graph_csr(n, n_communities=8, seed=3)
    parts = pm.partition_general(csr, 4, block=(8, 8))
    op = GraphOperator(P=csr.to_dense(),
                       multipliers=sgwt_multipliers(meta["lmax"], 2),
                       lmax=meta["lmax"], K=3)
    plan = op.plan("pallas_halo", mesh=mesh, partition=parts)
    # a solver body that returns its signals moves them in and out again
    for shape in ((1, n), (3, n), (64, n), (3, op.eta, n)):
        x = jax.random.normal(jax.random.PRNGKey(len(shape)), shape)
        back = plan.matvec_runner(lambda mv, s: s, (x,))
        assert np.array_equal(np.asarray(back), np.asarray(x)), (n, shape)
    rd = parts.reorder
    if n % 4:
        assert rd is None and plan.info["reorder"] == "gather"
        continue
    assert plan.info["reorder"] == "sharded"
    spec = P("graph")
    arrays = [jax.device_put(a, NamedSharding(mesh, spec))
              for a in rd.arrays]
    k = len(rd.offsets)

    def move(x, *arrays, back=False):
        def run(xl, *al):
            mine = [a[0] for a in al]
            send = mine[k:2 * k] if back else mine[:k]
            place = mine[2 * k + 1] if back else mine[2 * k]
            return pm.sharded_reorder(xl, send, place, rd.offsets, "graph",
                                      4, back=back)

        sig = P(*([None] * (x.ndim - 1)), "graph")
        return jax.shard_map(run, mesh=mesh,
                             in_specs=(sig,) + (spec,) * len(arrays),
                             out_specs=sig, check_vma=False)(x, *arrays)

    for shape in ((1, n), (3, n), (64, n), (3, op.eta, n), (n,)):
        x = jax.random.normal(jax.random.PRNGKey(7), shape)
        there = jax.jit(move)(x, *arrays)
        assert np.array_equal(np.asarray(there),
                              np.asarray(x)[..., parts.order])
        again = jax.jit(lambda y, *a: move(y, *a, back=True))(there, *arrays)
        assert np.array_equal(np.asarray(again), np.asarray(x))
        assert np.array_equal(np.asarray(there)[..., parts.inv_order],
                              np.asarray(x))
print("OK")
"""


def test_sharded_reorder_matches_take_and_round_trips():
    assert "OK" in run_payload(ROUND_TRIP, n_devices=4)


# ---------------------------------------------------------------------------
# A four-shard plan on a small sensor field against the dense backend
# ---------------------------------------------------------------------------
SENSOR_FIELD = r"""
import sys
sys.path.insert(0, %(root)r)
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from bench.graphs import sensor_field
from repro import obs
from repro.core.wavelets import sgwt_multipliers
from repro.dist import GraphOperator
from repro.dist import partition as pm
from repro.dist.commstats import measure

g = sensor_field.build({"n": 4000, "theta": 0.074, "kappa": 0.075,
                        "density": 500, "graph_seed": 0})
csr = pm.CSRMatrix(indptr=g.indptr, indices=g.indices, data=g.data)
op = GraphOperator(P=csr.to_dense(), multipliers=sgwt_multipliers(g.lmax, 6),
                   lmax=g.lmax, K=6)
mesh = jax.make_mesh((4,), ("graph",))
parts = pm.partition_general(csr, 4, block=(8, 8))
plan = op.plan("pallas_halo", mesh=mesh, partition=parts)
dense = op.plan("dense")
B = 3
f = jax.random.normal(jax.random.PRNGKey(0), (B, g.n))
a = jax.random.normal(jax.random.PRNGKey(1), (B, op.eta, g.n))
got, want = plan.compiled("apply")(f), dense.apply(f)
scale = float(jnp.max(jnp.abs(want)))
assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale
assert got.sharding.spec == P(None, None, "graph"), got.sharding
got_a, want_a = plan.compiled("apply_adjoint")(a), dense.apply_adjoint(a)
scale = float(jnp.max(jnp.abs(want_a)))
assert float(jnp.max(jnp.abs(got_a - want_a))) <= 1e-5 * scale
assert got_a.sharding.spec == P(None, "graph"), got_a.sharding
# the compiled apply gathers nothing whole
text = plan.compiled("apply").lower(f).compile().as_text()
assert "all-gather" not in text and "collective-permute" in text
# 2K|E| messages exactly; the reorder's bytes are its own, as counted
st = measure(plan.apply, jax.ShapeDtypeStruct((B, g.n), jnp.float32),
             n_shards=4, batch=B,
             ppermutes_per_round=plan.info["exchange_collectives_per_round"])
assert st.exchange_rounds == op.K
assert st.paper_messages(g.n_edges) == 2 * op.K * g.n_edges
assert st.reorder_bytes_per_shard == B * plan.info["reorder_bytes_per_apply"]
# counted once per trace: a plan whose vertices split evenly moves them
# shard to shard, one that does not gathers them
obs.reset()
jax.make_jaxpr(plan.apply)(f[:2])
jax.make_jaxpr(plan.apply_adjoint)(a[:2])
assert obs.snapshot().get("reorder.sharded") == 2, obs.snapshot()
odd = op.plan("pallas_halo", mesh=mesh, partition=pm.partition_general(
    pm.CSRMatrix.from_dense(csr.to_dense()[:3998, :3998]), 4, block=(8, 8)))
obs.reset()
jax.make_jaxpr(odd.apply)(f[:, :3998])
assert obs.snapshot().get("reorder.gather") == 1, obs.snapshot()
assert "reorder.sharded" not in obs.snapshot()
print("OK")
"""


def test_four_shard_sensor_field_matches_dense():
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert "OK" in run_payload(SENSOR_FIELD % {"root": root}, n_devices=4)


# ---------------------------------------------------------------------------
# Compiled entries take the structure as arguments, solves included
# ---------------------------------------------------------------------------
STRUCTURE = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import graph
from repro.core.wavelets import sgwt_multipliers
from repro.dist import GraphOperator
from repro.dist import partition as pm

g, _ = graph.connected_sensor_graph(jax.random.PRNGKey(0), n=64, theta=0.3,
                                    kappa=0.35)
L = np.asarray(g.laplacian(), np.float32)
lmax = float(g.lambda_max_bound())
op = GraphOperator(P=L, multipliers=sgwt_multipliers(lmax, 2), lmax=lmax,
                   K=6)
plan = op.plan("pallas_halo", mesh=jax.make_mesh((4,), ("graph",)),
               partition=pm.partition_general(L, 4, block=(8, 8)))
dense = op.plan("dense")
assert plan.info["reorder"] == "sharded" and plan.structure
y = jax.random.normal(jax.random.PRNGKey(1), (3, 64))


def held(consts):
    return sum(any(c is s for s in plan.structure) for c in consts)


# the check bites: a solve jitted around the plan compiles its structure in
closed = jax.jit(lambda v: plan.solve(v, "jacobi", tau=0.5).x).trace(y)
assert held(closed.jaxpr.consts) == len(plan.structure)
for name, entry, ref in [
        ("apply", plan.compiled("apply"), dense.apply),
        ("apply_gram", plan.compiled("apply_gram"), dense.apply_gram),
        ("jacobi", plan.compiled_solve("jacobi", tau=0.5),
         dense.compiled_solve("jacobi", tau=0.5)),
        ("chebyshev", plan.compiled_solve("chebyshev", tau=0.5),
         dense.compiled_solve("chebyshev", tau=0.5))]:
    assert held(entry.fn.trace(entry.structure, y).jaxpr.consts) == 0, name
    got, want = entry(y), ref(y)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale, name
    assert got.sharding.spec[-1] == "graph", (name, got.sharding)
print("OK")
"""


def test_compiled_entries_take_the_structure_as_arguments():
    assert "OK" in run_payload(STRUCTURE, n_devices=4)


# ---------------------------------------------------------------------------
# Bytes by hand on three shards
# ---------------------------------------------------------------------------
HAND = r"""
import numpy as np, jax
from repro.core.wavelets import sgwt_multipliers
from repro.dist import GraphOperator
from repro.dist import partition as pm
from repro.dist.commstats import measure

# the path 0-1-2-3-4-5, vertex shards {0,1} {2,3} {4,5}, partition shards
# {0,3} {1,4} {2,5}: 0 and 5 stay; at offset 1 shard 0 sends 1 and shard 1
# sends 2; at offset 2 shard 1 sends 3 and shard 2 sends 4.  One row a
# tile at each offset: 2 rows in, eta * 2 rows out, 4 bytes each.
L = (np.diag([1.0, 2, 2, 2, 2, 1]) - np.eye(6, k=1) - np.eye(6, k=-1))
parts = pm.partition_general(L.astype(np.float32), 3, block=(1, 1),
                             order=np.array([0, 3, 1, 4, 2, 5]))
rd = parts.reorder
assert rd.offsets == (1, 2) and rd.tile_widths == (1, 1)
assert rd.counts == ((1, 1, 0), (0, 1, 1))
op = GraphOperator(P=L, multipliers=sgwt_multipliers(4.0, 2), lmax=4.0, K=3)
assert op.eta == 3
plan = op.plan("halo", mesh=jax.make_mesh((3,), ("graph",)), partition=parts)
assert plan.info["reorder_bytes_per_apply"] == 4 * (1 + 3) * 2 == 32
st = measure(plan.apply, jax.ShapeDtypeStruct((1, 6), np.float32),
             n_shards=3, ppermutes_per_round=plan.info[
                 "exchange_collectives_per_round"])
assert st.reorder_bytes_per_shard == 32
assert st.exchange_rounds == op.K
x = np.arange(6, dtype=np.float32)[None]
assert np.allclose(plan.apply(x), op.plan("dense").apply(x), atol=1e-5)
print("OK")
"""


def test_reorder_bytes_per_apply_is_a_hand_count():
    assert "OK" in run_payload(HAND, n_devices=3)
