"""'allgather' execution backend: row-block sharded P with one all_gather of
the iterate per Chebyshev order (general, non-banded graphs).

Exact for any sparsity pattern — the trade is bandwidth: each order moves
the whole iterate instead of the 2-block halo, so prefer 'halo' whenever
the graph is (or can be sorted to be) banded.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core import chebyshev as cheb
from ...kernels import ops
from ..sharding import auto_mesh
from . import register_backend

Array = jax.Array


def _allgather_matvec(rows, axis: str):
    """rows: (nl, N_padded) local row block; x gathered each application.

    x: (..., nl) — one gather moves every leading batch / eta stream in the
    same round (the vertex axis stays last, so `axis=x.ndim - 1` is the
    gather axis for any batch rank)."""

    def mv(x: Array) -> Array:
        x_full = jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)
        return jnp.einsum("ij,...j->...i", rows, x_full)

    return cheb.LocalMatvec(mv)


@register_backend("allgather")
def build(op, *, mesh=None, partition=None, axis: Optional[str] = None,
          **options):
    """ExecutionPlan for arbitrary graphs: shard P by row blocks over `mesh`
    and all_gather the iterate once per Chebyshev order.  Without `mesh=`, a
    1-D "graph" mesh over every visible device is built."""
    from ..operator import LocalForm, plan_from_form

    del partition  # allgather shards rows directly from the dense P
    mesh = auto_mesh(mesh)
    if callable(op.P):
        raise ValueError("allgather backend needs a dense P")
    axis = axis or mesh.axis_names[0]
    n_shards = int(mesh.shape[axis])
    Pm = np.asarray(op.P)
    n = Pm.shape[0]
    total = n_shards * (-(-n // n_shards))
    # shard s holds rows [s * nl, (s + 1) * nl) of the padded P
    rows = jnp.asarray(np.pad(Pm, ((0, total - n), (0, total - n))).reshape(
        n_shards, total // n_shards, total))
    form = LocalForm(local=lambda mine: _allgather_matvec(mine[0], axis),
                     recurrence=cheb.cheb_apply, arrays=(rows,),
                     glob_in=lambda x: ops.pad_trailing(x, total),
                     glob_out=lambda y: ops.crop(y, n),
                     mesh=mesh, axis=axis, lasso=False)
    return plan_from_form(op, "allgather", form, info={
        "mesh_axis": axis,
        "n_shards": n_shards,
        "gather_bytes_per_apply": 2 * op.K * total * 4,
    })
