"""Sharded Algorithm 1/2/3 (shard_map) equals the centralized reference —
runs in a subprocess with 8 forced host devices."""
import pytest

from _subproc import run_payload

PAYLOAD = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import graph, multiplier, wavelets, lasso
from repro.dist import GraphOperator

key = jax.random.PRNGKey(1)
g, key = graph.connected_sensor_graph(key, n=600, theta=0.07, kappa=0.07)
gs, _ = graph.spatial_sort(g)
L = gs.laplacian()
lmax = gs.lambda_max_bound()
mesh = jax.make_mesh((8,), ("graph",),
                     axis_types=(jax.sharding.AxisType.Auto,))
y = jax.random.normal(key, (g.n_vertices,))
mults = wavelets.sgwt_multipliers(lmax, J=3)
uop = multiplier.UnionMultiplier(P=L, multipliers=mults, lmax=lmax, K=15)
plan = GraphOperator(P=L, multipliers=mults, lmax=lmax,
                     K=15).plan("halo", mesh=mesh)
assert plan.info["partition_leak"] == 0.0, plan.info["partition_leak"]
n = g.n_vertices

out_d = plan.apply(y)
out_c = uop.apply(y)
assert float(jnp.abs(out_d[:, :n] - out_c).max()) < 1e-4

a = out_c
adj_d = plan.apply_adjoint(a)
assert float(jnp.abs(adj_d[:n] - uop.apply_adjoint(a)).max()) < 1e-4

gram_d = plan.apply_gram(y)
assert float(jnp.abs(gram_d[:n] - uop.apply_gram(y)).max()) < 1e-4

mu = jnp.array([0.01, 0.75, 0.75, 0.75])
gamma = lasso.ista_step_size(uop)
res_d = plan.solve_lasso(y, mu, gamma=gamma, n_iters=25)
assert res_d.fused
res_c = lasso.distributed_lasso(uop, y, mu=mu, gamma=gamma, n_iters=25)
assert float(jnp.abs(res_d.signal[:n] - res_c.signal).max()) < 1e-4

# allgather fallback on an unsorted (non-banded) graph
L2 = g.laplacian()
uop2 = multiplier.UnionMultiplier(P=L2, multipliers=mults, lmax=lmax, K=15)
plan2 = GraphOperator(P=L2, multipliers=mults, lmax=lmax,
                      K=15).plan("allgather", mesh=mesh)
out_ag = plan2.apply(y)
assert float(jnp.abs(out_ag[:, :n] - uop2.apply(y)).max()) < 1e-4
print("DIST OK")
"""


def test_sharded_equals_centralized():
    out = run_payload(PAYLOAD, n_devices=8)
    assert "DIST OK" in out
