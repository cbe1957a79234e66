"""Distributed semi-supervised classification (paper Section III-D).

Two-cluster graph, 4 labeled nodes, labels propagated by applying the
optimal multiplier g(lambda) = tau/(tau + h(lambda)) to each class
indicator column — all classes share the same K communication rounds.

    PYTHONPATH=src python examples/semi_supervised.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import filters, graph, ssl
from repro.dist import available_backends


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="dense",
                    choices=available_backends(),
                    help="execution backend for the label propagation")
    args = ap.parse_args()
    enable_compile_cache()
    key = jax.random.PRNGKey(3)
    g, labels = graph.two_cluster_graph(key, n_per=25, p_in=0.85, p_out=0.06)
    mask = jnp.zeros(50, bool).at[jnp.array([0, 1, 25, 26])].set(True)
    print(f"two-cluster graph: N={g.n_vertices}, labeled={int(mask.sum())}")

    kernels = {
        "tikhonov L_norm  (S = L_norm)": filters.power_kernel(1),
        "tikhonov L_norm^2": filters.power_kernel(2),
        "diffusion (Smola-Kondor)": filters.diffusion_kernel(1.0),
        "2-step random walk": filters.random_walk_kernel(2.0, 2),
    }
    Ln = g.laplacian("normalized")
    for name, h in kernels.items():
        res = ssl.semi_supervised_classify(Ln, labels, mask, 2, h=h,
                                           tau=0.5, lmax=2.0, K=20,
                                           backend=args.backend)
        acc = ssl.accuracy(res, labels, mask)
        print(f"  {name:34s} accuracy on unlabeled: {acc:.3f}")


if __name__ == "__main__":
    main()
