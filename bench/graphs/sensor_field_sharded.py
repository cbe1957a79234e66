"""The sensor field of ``sensor_field``, for a deployment sharded over
several chips that keeps its signals sharded in vertex order.

The graph is ``sensor_field``'s, sensor for sensor.  What this kind adds
is a check of the program before minutes of set-up: at four million
sensors the (B, eta, N) result of one call (7.17 GB at B = 64) fits the
chips only split among them, so a multi-shard general plan must hand its
result back sharded over the mesh, as API.md documents.  The check plans
a path graph of 16 vertices a device on the same devices and the same
backend, applies it, and refuses a program whose result is not split
over them: one that gathers the result whole spends minutes compiling
this deployment (past 240 s on four TPU v5 lite chips) before it can
fail.  On a single device there is nothing to check.
"""
from bench.graphs import sensor_field


def build(spec):
    _results_stay_sharded()
    return sensor_field.build(spec)


def _results_stay_sharded():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.wavelets import sgwt_multipliers
    from repro.dist import GraphOperator
    from repro.dist.partition import partition_general

    devices = jax.devices()
    S, n = len(devices), 16 * len(devices)
    if S < 2:
        return
    L = (np.diag(np.r_[1.0, np.full(n - 2, 2.0), 1.0]) - np.eye(n, k=1)
         - np.eye(n, k=-1)).astype(np.float32)
    mesh = jax.make_mesh((S,), ("graph",), devices=devices)
    op = GraphOperator(P=L, multipliers=sgwt_multipliers(4.0, 1), lmax=4.0,
                       K=2)
    plan = op.plan("pallas_halo", mesh=mesh, use_pallas=False,
                   partition=partition_general(L, S, block=(8, 8)))
    out = plan.compiled("apply")(jnp.ones((1, n), jnp.float32))
    if out.sharding.shard_shape(out.shape)[-1] * S != n:
        raise RuntimeError(
            "this deployment needs a program whose multi-shard plans hand "
            "back results sharded over the mesh; this one returns "
            f"{out.sharding} on {S} devices")
