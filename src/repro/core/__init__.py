"""Core library: the paper's contribution (Chebyshev graph multipliers).

The sharded forms live in :mod:`repro.dist` (``GraphOperator.plan``).
"""
from . import arma, chebyshev, filters, graph, jacobi, lasso, ssl, wavelets
from .chebyshev import (
    cheb_apply,
    cheb_apply_adjoint,
    cheb_apply_gram,
    cheb_coeffs,
    cheb_coeffs_stack,
    cheb_eval,
    gram_coeffs,
)
from .graph import Graph, laplacian, lambda_max_bound, sensor_graph
from .multiplier import ScalarMultiplier, UnionMultiplier, graph_multiplier
from .wavelets import sgwt_multipliers, sgwt_operator

__all__ = [
    "arma", "chebyshev", "filters", "graph", "jacobi",
    "lasso", "ssl", "wavelets",
    "cheb_apply", "cheb_apply_adjoint", "cheb_apply_gram", "cheb_coeffs",
    "cheb_coeffs_stack", "cheb_eval", "gram_coeffs",
    "Graph", "laplacian", "lambda_max_bound", "sensor_graph",
    "ScalarMultiplier", "UnionMultiplier", "graph_multiplier",
    "sgwt_multipliers", "sgwt_operator",
]
