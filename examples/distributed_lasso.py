"""Distributed wavelet-lasso denoising (paper Section VI, Algorithm 3).

Piecewise-smooth field on the 500-sensor network, SGWT with 6 wavelet
scales, iterative soft thresholding over the Chebyshev-approximate frame.
With --sharded (and forced host devices) the whole ISTA loop runs inside a
shard_map over 8 graph shards with ring halo exchanges — the TPU analog of
the sensors' neighbour messages.  --backend pallas_halo runs the fused
Pallas Block-ELL recurrence per shard and exchanges only the boundary rows
each neighbour actually reads; the measured collective traffic
(repro.dist.commstats) is printed next to the paper's 2K|E| model.

    PYTHONPATH=src python examples/distributed_lasso.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_lasso.py --sharded
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_lasso.py --sharded \
        --backend pallas_halo
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import SENSOR500
from repro.core import filters, graph, wavelets
from repro.core.multiplier import graph_multiplier
from repro.data.pipeline import graph_signal_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sharded", action="store_true")
    ap.add_argument("--backend", default=None,
                    help="explicit execution backend (default: dense, or "
                    "halo with --sharded)")
    ap.add_argument("--iters", type=int, default=150)
    args = ap.parse_args()
    enable_compile_cache()

    p = SENSOR500
    key = jax.random.PRNGKey(11)
    g, key = graph.connected_sensor_graph(key, n=p.n_vertices,
                                          theta=p.theta, kappa=p.kappa)
    f0 = graph_signal_batch(key, g.coords, "piecewise")
    key, sub = jax.random.split(key)
    y = f0 + p.noise_sigma * jax.random.normal(sub, f0.shape)
    lmax = g.lambda_max_bound()
    mu = jnp.array([p.lasso_mu_scaling]
                   + [p.lasso_mu_wavelet] * p.n_wavelet_scales)
    op = wavelets.sgwt_operator(g.laplacian(), lmax,
                                J=p.n_wavelet_scales, K=p.lasso_K)

    tik = graph_multiplier(g.laplacian(), filters.tikhonov(p.tau, p.r),
                           lmax, K=p.K).apply(y)

    backend = args.backend or ("halo" if args.sharded else "dense")
    if backend in ("halo", "pallas_halo", "allgather"):
        n_dev = len(jax.devices())
        assert n_dev >= 8, "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
        gs, order = graph.spatial_sort(g)
        mesh = jax.make_mesh((8,), ("graph",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        lmax_s = gs.lambda_max_bound()
        op_s = wavelets.sgwt_operator(gs.laplacian(), lmax_s,
                                      J=p.n_wavelet_scales, K=p.lasso_K)
        plan = op_s.plan(backend, mesh=mesh)
        print(f"backend={backend} over 8 devices; "
              f"plan info: {plan.info}")
        from repro.dist import plan_comm_stats
        st = plan_comm_stats(plan)["apply"]
        print(f"measured per apply: {st.exchange_rounds} exchange rounds, "
              f"{st.total_bytes} bytes over the mesh "
              f"(paper model: {op.message_counts(g.n_edges)['apply_messages']}"
              f" scalar messages)")
        res = plan.solve_lasso(y[jnp.asarray(order)], mu,
                               gamma=p.lasso_gamma, n_iters=args.iters)
        signal = jnp.zeros_like(y).at[np.asarray(order)].set(res.signal)
    else:
        plan = op.plan(backend)
        print(f"backend={backend}; plan info: {plan.info}")
        res = plan.solve_lasso(y, mu, gamma=p.lasso_gamma,
                               n_iters=args.iters)
        signal = res.signal

    print(f"MSE noisy    : {float(jnp.mean((y - f0) ** 2)):.4f}  (paper 0.250)")
    print(f"MSE tikhonov : {float(jnp.mean((tik - f0) ** 2)):.4f}  (paper 0.098)")
    print(f"MSE lasso    : {float(jnp.mean((signal - f0) ** 2)):.4f}  (paper 0.079)")
    mc = op.message_counts(g.n_edges)
    per_iter = mc["gram_messages"] + mc["adjoint_messages"] * op.eta
    print(f"communication per ISTA iteration ~ {per_iter} scalar messages "
          f"(scales with |E|={g.n_edges}, independent of N beyond that)")


if __name__ == "__main__":
    main()
