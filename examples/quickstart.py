"""Quickstart: the paper's Section IV-D experiment end to end.

Builds the 500-sensor random network, observes a noisy smooth field, and
denoises it with the distributed-ready Chebyshev approximation of the
Tikhonov multiplier g(lambda) = tau / (tau + 2 lambda^r).

    PYTHONPATH=src python examples/quickstart.py

Pass ``--drop-prob 0.1 --backend halo`` to run the same experiment with
seeded link faults injected into the halo exchange (repro.dist.faults):
the script prints the degradation policy, the fault identity key, and
the achieved MSE so you can see graceful degradation directly.  Solver
methods additionally run with the divergence guard (``check_every``) and
report the measured residual.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import SENSOR500
from repro.core import filters, graph
from repro.data.pipeline import graph_signal_batch
from repro.dist import GraphOperator, available_backends


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="dense",
                    choices=available_backends(),
                    help="execution backend for the multiplier application")
    ap.add_argument("--method", default="chebyshev",
                    choices=["chebyshev", "jacobi", "cheb_jacobi", "arma"],
                    help="Section-V solver for the Tikhonov application: "
                    "the Chebyshev approximation (Section IV) or an exact "
                    "iterative solve of (tau I + 2 L^r) f = tau y via "
                    "plan.solve (Eqs. (24)/(25)/(29)-(30))")
    ap.add_argument("--drop-prob", type=float, default=0.0,
                    help="per-(round, link) probability of dropping a "
                    "halo tile (seeded fault injection; needs a sharded "
                    "backend: halo or pallas_halo)")
    ap.add_argument("--degradation", default="zero_fill",
                    choices=["zero_fill", "hold_last"],
                    help="receiver-side substitute for dropped tiles")
    args = ap.parse_args()
    enable_compile_cache()

    if args.drop_prob > 0:
        if args.backend not in ("halo", "pallas_halo"):
            ap.error("--drop-prob needs a halo-exchange backend "
                     "(--backend halo or pallas_halo); link faults are "
                     "meaningless without links")
        if len(jax.devices()) == 1:
            # one device = one shard = no links to drop; re-exec with
            # forced host devices so the exchange (and its faults) exist;
            # the new image runs on the CPU, off this process's chip
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=8 "
                + os.environ.get("XLA_FLAGS", ""))
            os.execv(sys.executable, [sys.executable] + sys.argv)

    p = SENSOR500
    key = jax.random.PRNGKey(0)
    g, key = graph.connected_sensor_graph(key, n=p.n_vertices,
                                          theta=p.theta, kappa=p.kappa)
    print(f"sensor network: N={g.n_vertices}, |E|={g.n_edges}")

    f0 = graph_signal_batch(key, g.coords, "smooth")   # h_n = nx^2+ny^2-1
    key, sub = jax.random.split(key)
    y = f0 + p.noise_sigma * jax.random.normal(sub, f0.shape)

    order = None
    if args.backend in ("halo", "pallas_halo"):
        # the halo-exchange backends need a banded (spatially sorted) order
        g, order = graph.spatial_sort(g)
        y = y[jnp.asarray(order)]

    lmax = g.lambda_max_bound()
    print(f"lambda_max bound (Anderson-Morley): {lmax:.2f}")
    R = GraphOperator(P=g.laplacian(),
                      multipliers=[filters.tikhonov(p.tau, p.r)],
                      lmax=lmax, K=p.K)
    plan_opts = {}
    if args.drop_prob > 0:
        from repro.dist import FaultSpec
        plan_opts = dict(fault_spec=FaultSpec(drop_prob=args.drop_prob,
                                              seed=0),
                         degradation=args.degradation)
    plan = R.plan(args.backend, **plan_opts)  # sharded backends build
    if args.drop_prob > 0:                    # their own mesh
        print(f"fault injection: drop_prob={args.drop_prob:g}, "
              f"degradation={args.degradation}, "
              f"fault_key={plan.info['fault_key']}")
    if args.method == "chebyshev":
        denoised = plan.apply(y)[0]
    else:
        # the same multiplier served by the Section-V exact solvers: the
        # Prop. 2 filter tau/(tau + 2 lambda^r) is the rational problem
        # den(L) f = tau y with den = tau + 2 lambda^r; check_every arms
        # the divergence guard so a fault-degraded solve reports an
        # honest residual instead of silently returning garbage
        res = plan.solve(y, args.method, tau=p.tau, r=p.r, h_scale=2.0,
                         n_iters=p.K, check_every=max(1, p.K // 2))
        denoised = res.x
        print(f"plan.solve[{args.method}]: {res.n_iters} iterations x "
              f"{res.info['matvecs_per_round']} matvec(s)/round = "
              f"{res.info['exchange_rounds']} exchange rounds")
        print(f"plan.solve[{args.method}]: residual "
              f"{float(res.info['residual']):.3e}, "
              f"diverged={bool(res.info['diverged'])}")

    if order is not None:  # undo the sort so the MSE lines up with f0
        import numpy as np
        inv = np.argsort(order)
        denoised, y = denoised[inv], y[inv]

    mse_noisy = float(jnp.mean((y - f0) ** 2))
    mse_den = float(jnp.mean((denoised - f0) ** 2))
    print(f"Chebyshev order K={p.K}; backend={plan.backend}; "
          f"error bound B(K)*sqrt(eta) = {R.error_bound():.2e}")
    print(f"MSE noisy    : {mse_noisy:.4f}   (paper avg: 0.250)")
    print(f"MSE denoised : {mse_den:.4f}   (paper avg: 0.013)")
    mc = plan.message_counts(g.n_edges)
    print(f"communication: {mc['apply_messages']} length-1 messages "
          f"(= 2K|E|)")


if __name__ == "__main__":
    main()
