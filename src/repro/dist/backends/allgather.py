"""'allgather' execution backend: row-block sharded P with one all_gather of
the iterate per Chebyshev order (general, non-banded graphs).

Exact for any sparsity pattern — the trade is bandwidth: each order moves
the whole iterate instead of the 2-block halo, so prefer 'halo' whenever
the graph is (or can be sorted to be) banded.
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ...core import chebyshev as cheb
from ...kernels.ops import pad_trailing
from ..sharding import auto_mesh
from . import register_backend
from .halo import _sharded, _vspec

Array = jax.Array


def _allgather_matvec(rows, axis: str):
    """rows: (nl, N_padded) local row block; x gathered each application.

    x: (..., nl) — one gather moves every leading batch / eta stream in the
    same round (the vertex axis stays last, so `axis=x.ndim - 1` is the
    gather axis for any batch rank)."""

    def mv(x: Array) -> Array:
        x_full = jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)
        return jnp.einsum("ij,...j->...i", rows, x_full)

    return mv


def dist_cheb_apply_allgather(
    mesh: Mesh,
    P_dense: Array,
    x: Array,
    coeffs: Union[Array, np.ndarray],
    lmax: float,
    axis: str = "graph",
) -> Array:
    """Sharded Phi_tilde x for general (non-banded) P: row-block sharding of
    P, one all_gather of the iterate per Chebyshev order.  x: (..., n_padded)
    -> (..., eta, n_padded) ((..., n_padded) for 1-D coeffs)."""
    single = getattr(coeffs, "ndim", None) == 1 or (
        not hasattr(coeffs, "ndim") and np.asarray(coeffs).ndim == 1)
    c = jnp.atleast_2d(jnp.asarray(coeffs, dtype=x.dtype))

    def run(rows, xl, c):
        mv = _allgather_matvec(rows, axis)
        return cheb.cheb_apply(mv, xl, c, lmax)

    out = _sharded(
        run, mesh, (P(axis, None), _vspec(x.ndim, axis), P()),
        _vspec(x.ndim + 1, axis)
    )(P_dense, x, c)
    return out[..., 0, :] if single else out


def dist_cheb_apply_adjoint_allgather(
    mesh: Mesh,
    P_dense: Array,
    a: Array,
    coeffs: Union[Array, np.ndarray],
    lmax: float,
    axis: str = "graph",
) -> Array:
    """Sharded Phi_tilde^* a (Algorithm 2) with all-gather matvecs.
    a: (..., eta, n_padded) -> (..., n_padded); one gather moves all eta
    streams (and all batch signals) per order."""
    c = jnp.asarray(coeffs, dtype=a.dtype)

    def run(rows, al, c):
        mv = _allgather_matvec(rows, axis)
        return cheb.cheb_apply_adjoint(mv, al, c, lmax)

    return _sharded(
        run, mesh, (P(axis, None), _vspec(a.ndim, axis), P()),
        _vspec(a.ndim - 1, axis)
    )(P_dense, a, c)


def dist_cheb_apply_gram_allgather(
    mesh: Mesh,
    P_dense: Array,
    x: Array,
    coeffs: np.ndarray,
    lmax: float,
    axis: str = "graph",
) -> Array:
    """Sharded Phi~*Phi~ x via product coefficients (Section IV-C).
    x: (..., n_padded) -> (..., n_padded)."""
    d = jnp.asarray(cheb.gram_coeffs(coeffs), dtype=x.dtype)

    def run(rows, xl, d):
        mv = _allgather_matvec(rows, axis)
        return cheb.cheb_apply(mv, xl, d, lmax)

    return _sharded(
        run, mesh, (P(axis, None), _vspec(x.ndim, axis), P()),
        _vspec(x.ndim, axis)
    )(P_dense, x, d)


@register_backend("allgather")
def build(op, *, mesh=None, partition=None, axis: Optional[str] = None,
          **options):
    """ExecutionPlan for arbitrary graphs: shard P by row blocks over `mesh`
    and all_gather the iterate once per Chebyshev order.  Without `mesh=`, a
    1-D "graph" mesh over every visible device is built."""
    from ..operator import ExecutionPlan

    del partition  # allgather shards rows directly from the dense P
    mesh = auto_mesh(mesh)
    if callable(op.P):
        raise ValueError("allgather backend needs a dense P")
    axis = axis or mesh.axis_names[0]
    n_shards = int(mesh.shape[axis])
    Pm = np.asarray(op.P)
    n = Pm.shape[0]
    total = n_shards * (-(-n // n_shards))
    Pp = jnp.asarray(np.pad(Pm, ((0, total - n), (0, total - n))))
    coeffs = op.coeffs
    lmax = op.lmax

    def _pad(x: Array) -> Array:
        return pad_trailing(x, total)

    def apply(f: Array) -> Array:
        c2 = jnp.atleast_2d(jnp.asarray(coeffs, f.dtype))
        return dist_cheb_apply_allgather(mesh, Pp, _pad(f), c2, lmax,
                                         axis)[..., :n]

    def apply_adjoint(a: Array) -> Array:
        return dist_cheb_apply_adjoint_allgather(mesh, Pp, _pad(a), coeffs,
                                                 lmax, axis)[..., :n]

    def apply_gram(f: Array) -> Array:
        return dist_cheb_apply_gram_allgather(mesh, Pp, _pad(f), coeffs,
                                              lmax, axis)[..., :n]

    def matvec_runner(fn, signals, consts=()):
        # Section-V solver substrate for general graphs: `fn` runs inside
        # one shard_map with the row-block matvec (one all_gather of the
        # iterate per solver matvec); vertex-last signals shard, consts
        # replicate, outputs crop back to the logical n.
        padded = tuple(_pad(jnp.asarray(s)) for s in signals)
        nl = total // n_shards
        local = tuple(
            jax.ShapeDtypeStruct(s.shape[:-1] + (nl,), s.dtype)
            for s in padded)
        out_sds = jax.eval_shape(lambda *a: fn(lambda v: v, *a),
                                 *local, *consts)
        in_specs = ((P(axis, None),)
                    + tuple(_vspec(s.ndim, axis) for s in padded)
                    + tuple(P() for _ in consts))
        out_specs = jax.tree.map(lambda sd: _vspec(len(sd.shape), axis),
                                 out_sds)

        def run(rows, *rest):
            mv = _allgather_matvec(rows, axis)
            return fn(mv, *rest)

        outs = _sharded(run, mesh, in_specs, out_specs)(Pp, *padded, *consts)
        return jax.tree.map(lambda o: o[..., :n], outs)

    return ExecutionPlan(
        op=op, backend="allgather",
        apply=apply, apply_adjoint=apply_adjoint, apply_gram=apply_gram,
        matvec_runner=matvec_runner,
        info={
            "mesh_axis": axis,
            "n_shards": n_shards,
            "gather_bytes_per_apply": 2 * op.K * total * 4,
        },
    )
