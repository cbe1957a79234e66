"""Backend equivalence: one `GraphOperator.plan()` path dispatches to every
registered backend with matching outputs (the unified execution API)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_payload
from repro.core import filters, graph, wavelets
from repro.dist import (GraphOperator, available_backends, get_backend,
                        register_backend)
from repro.dist.backends import _REGISTRY

BACKENDS = ["dense", "pallas", "halo", "pallas_halo", "allgather"]


@pytest.fixture(scope="module")
def small_op():
    """Small sensor graph + eta=3 SGWT union (N=120: not a 128 multiple, so
    the pallas path exercises its auto-padding)."""
    g, _ = graph.connected_sensor_graph(
        jax.random.PRNGKey(0), n=120, theta=0.2, kappa=0.25)
    lmax = g.lambda_max_bound()
    op = GraphOperator(P=g.laplacian(),
                       multipliers=wavelets.sgwt_multipliers(lmax, J=2),
                       lmax=lmax, K=12)
    return g, op


def _plan(op, backend):
    if backend in ("halo", "pallas_halo", "allgather"):
        mesh = jax.make_mesh((1,), ("graph",))
        return op.plan(backend, mesh=mesh)
    return op.plan(backend)


def test_registry_lists_builtin_backends():
    assert set(BACKENDS) <= set(available_backends())


def test_unknown_backend_raises(small_op):
    _, op = small_op
    with pytest.raises(KeyError, match="available"):
        op.plan("no-such-backend")


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_matches_dense(small_op, backend):
    """plan.apply / apply_adjoint / apply_gram agree across backends."""
    g, op = small_op
    ref = op.plan("dense")
    plan = _plan(op, backend)
    f = jax.random.normal(jax.random.PRNGKey(1), (g.n_vertices,))
    a = jax.random.normal(jax.random.PRNGKey(2), (op.eta, g.n_vertices))

    out = plan.apply(f)
    assert out.shape == (op.eta, g.n_vertices)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.apply(f)),
                               atol=1e-4)
    adj = plan.apply_adjoint(a)
    assert adj.shape == (g.n_vertices,)
    np.testing.assert_allclose(np.asarray(adj),
                               np.asarray(ref.apply_adjoint(a)), atol=1e-4)
    gram = plan.apply_gram(f)
    assert gram.shape == (g.n_vertices,)
    np.testing.assert_allclose(np.asarray(gram),
                               np.asarray(ref.apply_gram(f)), atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_adjoint_consistency(small_op, backend):
    """<Phi f, a> == <f, Phi* a> per backend (true adjoint pairs)."""
    g, op = small_op
    plan = _plan(op, backend)
    f = jax.random.normal(jax.random.PRNGKey(3), (g.n_vertices,))
    a = jax.random.normal(jax.random.PRNGKey(4), (op.eta, g.n_vertices))
    lhs = float(jnp.sum(plan.apply(f) * a))
    rhs = float(jnp.sum(f * plan.apply_adjoint(a)))
    assert abs(lhs - rhs) < 1e-2 * max(1.0, abs(lhs))


@pytest.mark.parametrize("backend", BACKENDS)
def test_plans_are_jittable(small_op, backend):
    g, op = small_op
    plan = _plan(op, backend)
    f = jax.random.normal(jax.random.PRNGKey(5), (g.n_vertices,))
    np.testing.assert_allclose(np.asarray(jax.jit(plan.apply)(f)),
                               np.asarray(plan.apply(f)), atol=1e-5)


@pytest.mark.parametrize("backend", ["halo", "pallas_halo"])
def test_solve_lasso_backend_equivalence(small_op, backend):
    """Algorithm 3 through the plan API: the fused shard_map ISTA loops
    (halo and pallas_halo) match the dense ISTA loop."""
    g, op = small_op
    y = jax.random.normal(jax.random.PRNGKey(6), (g.n_vertices,))
    mu = jnp.array([0.01, 0.75, 0.75])
    res_d = op.plan("dense").solve_lasso(y, mu, gamma=0.1, n_iters=15)
    mesh = jax.make_mesh((1,), ("graph",))
    res_h = op.plan(backend, mesh=mesh).solve_lasso(y, mu, gamma=0.1,
                                                    n_iters=15)
    np.testing.assert_allclose(np.asarray(res_h.signal),
                               np.asarray(res_d.signal), atol=1e-4)
    np.testing.assert_allclose(np.asarray(res_h.coeffs),
                               np.asarray(res_d.coeffs), atol=1e-4)


def test_pallas_halo_partition_roundtrip():
    """partition_block_ell: per-shard Block-ELL + boundary couplings
    reassemble to the original banded matrix, and the halo width matches
    the true coupling bandwidth (1 on a path graph)."""
    from repro.core.graph import path_graph
    from repro.dist.backends.pallas_halo import (partition_block_ell,
                                                 _banded_to_dense)
    from repro.dist.backends.halo import partition_banded

    L = np.asarray(path_graph(32).laplacian())
    parts, leak = partition_block_ell(L, 4)
    assert leak == 0.0
    assert parts.halo == 1 and parts.n_local == 8
    # reassemble: diag blocks from Block-ELL + the boundary columns
    banded, _ = partition_banded(L, 4)
    dense = _banded_to_dense(banded)
    np.testing.assert_allclose(dense, L, atol=0)
    # Block-ELL diagonal blocks match the banded diagonal blocks
    from repro.core.graph import BlockELL
    for s in range(4):
        A = BlockELL(blocks=parts.blocks[s], indices=parts.indices[s],
                     mask=parts.mask[s], n=parts.n_local)
        np.testing.assert_allclose(
            np.asarray(A.todense())[:8, :8],
            np.asarray(banded.diag[s]), atol=0)


def test_register_backend_extensibility(small_op):
    """New strategies plug in without touching callers (registry contract)."""
    g, op = small_op

    @register_backend("_test_echo")
    def build(op, *, mesh=None, partition=None, **options):
        plan = get_backend("dense")(op)
        import dataclasses
        return dataclasses.replace(plan, backend="_test_echo",
                                   info={"echo": True})

    try:
        plan = op.plan("_test_echo")
        assert plan.backend == "_test_echo" and plan.info == {"echo": True}
        f = jax.random.normal(jax.random.PRNGKey(7), (g.n_vertices,))
        np.testing.assert_allclose(np.asarray(plan.apply(f)),
                                   np.asarray(op.plan("dense").apply(f)),
                                   atol=1e-6)
    finally:
        _REGISTRY.pop("_test_echo", None)


def test_cheb_step_autopads_non_128_sizes():
    """Satellite: cheb_step no longer raises on N % 128 != 0."""
    from repro.kernels import ref
    from repro.kernels.cheb_step import cheb_step

    n, eta = 500, 3  # 500 % 128 != 0
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    pt, t1, t2 = (jax.random.normal(k, (n,)) for k in ks[:3])
    acc = jax.random.normal(ks[3], (eta, n))
    coef = jax.random.normal(ks[4], (eta,))
    tk_k, acc_k = cheb_step(pt, t1, t2, acc, coef, alpha=1.7, interpret=True)
    tk_r, acc_r = ref.cheb_step_ref(pt, t1, t2, acc, coef, alpha=1.7)
    assert tk_k.shape == (n,) and acc_k.shape == (eta, n)
    np.testing.assert_allclose(np.asarray(tk_k), np.asarray(tk_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(acc_k), np.asarray(acc_r),
                               atol=1e-5)


PAYLOAD = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import graph, wavelets
from repro.dist import GraphOperator, verify_message_scaling

key = jax.random.PRNGKey(1)
g, key = graph.connected_sensor_graph(key, n=600, theta=0.07, kappa=0.07)
gs, _ = graph.spatial_sort(g)
L = gs.laplacian()
lmax = gs.lambda_max_bound()
op = GraphOperator(P=L, multipliers=wavelets.sgwt_multipliers(lmax, J=3),
                   lmax=lmax, K=15)
mesh = jax.make_mesh((8,), ("graph",),
                     axis_types=(jax.sharding.AxisType.Auto,))
f = jax.random.normal(key, (g.n_vertices,))
a = jax.random.normal(jax.random.PRNGKey(2), (op.eta, g.n_vertices))
B = 64
F = jax.random.normal(jax.random.PRNGKey(3), (B, g.n_vertices))

ref = op.plan("dense")
out_ref, adj_ref, gram_ref = ref.apply(f), ref.apply_adjoint(a), ref.apply_gram(f)
Fout_ref = ref.apply(F)
for backend in ("pallas", "halo", "pallas_halo", "allgather"):
    plan = (op.plan(backend, mesh=mesh) if backend != "pallas"
            else op.plan(backend))
    assert float(jnp.abs(plan.apply(f) - out_ref).max()) < 1e-4, backend
    assert float(jnp.abs(plan.apply_adjoint(a) - adj_ref).max()) < 1e-4, backend
    assert float(jnp.abs(plan.apply_gram(f) - gram_ref).max()) < 1e-4, backend
    lhs = float(jnp.sum(plan.apply(f) * a))
    rhs = float(jnp.sum(f * plan.apply_adjoint(a)))
    assert abs(lhs - rhs) < 1e-2 * abs(lhs), (backend, lhs, rhs)
    # batched (..., N) contract under genuine sharding: B=64 signals match
    # the dense reference, and the exchange-round count is batch-invariant
    # (per-signal messages = 2K|E|/B)
    Fout = plan.apply(F)
    assert Fout.shape == (B, op.eta, g.n_vertices), (backend, Fout.shape)
    assert float(jnp.abs(Fout - Fout_ref).max()) < 1e-4, backend
    if backend != "pallas":
        v = verify_message_scaling(plan, g.n_edges, batch=B)
        assert v["max_rel_dev"] == 0.0, (backend, v["rel_dev"])
        assert v["per_signal_messages"]["apply"] == (
            2 * op.K * g.n_edges / B), backend
    print(f"{backend} OK", plan.info)
print("BACKENDS OK")
"""


def test_backends_match_dense_8shards():
    """Genuinely sharded (8 forced host devices) backend plans match the
    dense reference (single and B=64 batched signals), stay true adjoint
    pairs, and keep batch-invariant exchange rounds."""
    out = run_payload(PAYLOAD, n_devices=8)
    assert "BACKENDS OK" in out


# ---------------------------------------------------------------------------
# GeneralPartition golden matrix: non-banded community graph, edge-cut
# sharding (ISSUE 9) — dense reference vs halo / pallas_halo at 1 and 8
# devices, incl. B=64 batched and bf16-exchange paths.
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def community_op():
    from repro.dist import partition as pm

    csr, meta = pm.community_graph_csr(192, n_communities=6, seed=7)
    op = GraphOperator(
        P=csr.to_dense(),
        multipliers=wavelets.sgwt_multipliers(meta["lmax"], J=2),
        lmax=meta["lmax"], K=10)
    return csr, op


@pytest.mark.parametrize("backend", ["halo", "pallas_halo"])
def test_general_partition_matches_dense_1dev(community_op, backend):
    """partition="general" on a 1-shard mesh: apply/adjoint/gram/solve all
    match the dense plan (the S=1 degenerate skips collectives but must
    still run the permuted Block-ELL interior)."""
    csr, op = community_op
    n = csr.n
    dense = op.plan("dense")
    mesh = jax.make_mesh((1,), ("graph",))
    plan = op.plan(backend, mesh=mesh, partition="general")
    assert plan.info["partition"] == "general"
    f = jax.random.normal(jax.random.PRNGKey(0), (n,))
    a = jax.random.normal(jax.random.PRNGKey(1), (op.eta, n))
    assert float(jnp.abs(plan.apply(f) - dense.apply(f)).max()) < 1e-4
    assert float(jnp.abs(plan.apply_adjoint(a)
                         - dense.apply_adjoint(a)).max()) < 1e-4
    assert float(jnp.abs(plan.apply_gram(f)
                         - dense.apply_gram(f)).max()) < 1e-4
    xs = plan.solve(f, "jacobi", tau=0.5).x
    xd = dense.solve(f, "jacobi", tau=0.5).x
    assert float(jnp.abs(xs - xd).max()) < 1e-4


GENERAL_PAYLOAD = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import wavelets
from repro.dist import GraphOperator, verify_message_scaling
from repro.dist import partition as pm

csr, meta = pm.community_graph_csr(256, n_communities=8, seed=5)
n, E = csr.n, csr.n_edges
op = GraphOperator(P=csr.to_dense(),
                   multipliers=wavelets.sgwt_multipliers(meta["lmax"], J=3),
                   lmax=meta["lmax"], K=12)
mesh = jax.make_mesh((8,), ("graph",))
parts = pm.partition_general(csr, 8, block=(8, 8))
assert len(parts.offsets) > 2, parts.offsets  # genuinely non-banded

ref = op.plan("dense")
f = jax.random.normal(jax.random.PRNGKey(0), (n,))
a = jax.random.normal(jax.random.PRNGKey(1), (op.eta, n))
B = 64
F = jax.random.normal(jax.random.PRNGKey(2), (B, n))
out_ref, adj_ref = ref.apply(f), ref.apply_adjoint(a)
gram_ref, Fout_ref = ref.apply_gram(f), ref.apply(F)

for backend in ("halo", "pallas_halo"):
    plan = op.plan(backend, mesh=mesh, partition=parts)
    assert plan.info["partition"] == "general", backend
    assert float(jnp.abs(plan.apply(f) - out_ref).max()) < 1e-4, backend
    assert float(jnp.abs(plan.apply_adjoint(a) - adj_ref).max()) < 1e-4, backend
    assert float(jnp.abs(plan.apply_gram(f) - gram_ref).max()) < 1e-4, backend
    Fout = plan.apply(F)
    assert Fout.shape == (B, op.eta, n), (backend, Fout.shape)
    assert float(jnp.abs(Fout - Fout_ref).max()) < 1e-4, backend
    xs = plan.solve(f, "jacobi", tau=0.5).x
    xd = ref.solve(f, "jacobi", tau=0.5).x
    assert float(jnp.abs(xs - xd).max()) < 1e-4, backend
    # measured rounds exactly 2K|E| and batch-invariant
    v = verify_message_scaling(plan, E, n=n, batch=B)
    assert v["max_rel_dev"] == 0.0, (backend, v["rel_dev"])
    assert v["per_signal_messages"]["apply"] == 2 * op.K * E / B, backend
    # bf16 wire path: same rounds, half the f32 bytes, looser accuracy
    p16 = op.plan(backend, mesh=mesh, partition=parts,
                  exchange_dtype="bf16")
    assert float(jnp.abs(p16.apply(f) - out_ref).max()) < 5e-2, backend
    v16 = verify_message_scaling(p16, E, n=n)
    assert v16["max_rel_dev"] == 0.0, backend
    s32 = v["stats"]["apply"]; s16 = v16["stats"]["apply"]
    assert s16["bytes_per_shard"] * 2 == s32["bytes_per_shard"], backend
    print(backend, "OK")
print("GENERAL OK")
"""


def test_general_partition_matches_dense_8shards():
    """Genuinely sharded GeneralPartition plans (8 forced host devices) on
    a non-banded community graph match dense for apply/adjoint/gram/solve,
    keep B=64 batched equivalence, measure exactly 2K|E| with
    batch-invariant rounds, and halve wire bytes under bf16 exchange."""
    out = run_payload(GENERAL_PAYLOAD, n_devices=8)
    assert "GENERAL OK" in out


# ---------------------------------------------------------------------------
# The matvec a runner hands its body, and where the sweep may run
# ---------------------------------------------------------------------------
RUNNER_PAYLOAD = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core.chebyshev import LocalMatvec
from repro.core.wavelets import sgwt_multipliers
from repro.dist import GraphOperator, available_backends

n = 64
L = (np.diag(np.r_[1.0, np.full(n - 2, 2.0), 1.0]) - np.eye(n, k=1)
     - np.eye(n, k=-1)).astype(np.float32)
op = GraphOperator(P=L, multipliers=sgwt_multipliers(4.0, 1), lmax=4.0, K=3)
y = jnp.asarray(np.random.RandomState(0).randn(2, n), jnp.float32)
out = {}
for backend in available_backends():
    for shards in (1, 2):
        mesh = jax.make_mesh((shards,), ("graph",),
                             devices=jax.devices()[:shards],
                             axis_types=(AxisType.Auto,))
        for part in (("banded", "general") if "halo" in backend else ("-",)):
            kw = {"mesh": mesh}
            if part != "-":
                kw["partition"] = part
            if "pallas" in backend:
                kw.update(block=(8, 8), use_pallas=False)
            plan = op.plan(backend, **kw)
            seen = []

            def body(mv, v):
                seen.append(mv)
                return mv(v)

            got = plan.matvec_runner(body, (y,))
            err = float(jnp.abs(got - y @ L.T).max())
            out[f"{backend}/{shards}/{part}"] = {
                "record": all(isinstance(mv, LocalMatvec) for mv in seen),
                "block_ell": [mv.block_ell is not None for mv in seen],
                "err": err}
print("RUNNERS " + json.dumps(out))
"""

RUNNER_CASES = [(b, s, p) for b in available_backends() for s in (1, 2)
                for p in (("banded", "general") if "halo" in b else ("-",))]


@pytest.fixture(scope="module")
def runner_records():
    out = run_payload(RUNNER_PAYLOAD, n_devices=2)
    line = [ln for ln in out.splitlines() if ln.startswith("RUNNERS ")][-1]
    import json

    return json.loads(line[len("RUNNERS "):])


@pytest.mark.parametrize("backend,shards,partition", RUNNER_CASES)
def test_runner_hands_a_record_with_the_sweep_where_local(
        runner_records, backend, shards, partition):
    """Every plan's `matvec_runner` hands its body a `LocalMatvec` that
    applies P, and the record carries its Block-ELL — the licence for the
    single-launch sweep kernels — exactly where the product is purely
    local: `pallas` always, `pallas_halo` on one shard (banded and
    general); never where the matvec exchanges or gathers."""
    rec = runner_records[f"{backend}/{shards}/{partition}"]
    assert rec["record"]
    assert rec["err"] < 1e-5
    local = backend == "pallas" or (backend == "pallas_halo" and shards == 1)
    assert rec["block_ell"] and all(b == local for b in rec["block_ell"])
