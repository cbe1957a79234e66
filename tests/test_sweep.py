"""Single-launch sweep kernels (`kernels/cheb_sweep.py`) + the
interior/boundary split: sweep == per-order on every backend, the VMEM
guard falls back (and says so), solvers ride the one-launch path, and the
split leaves measured messages at exactly 2K|E|."""
import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_payload
from repro.core import filters, graph, jacobi, wavelets
from repro.core import chebyshev as cheb
from repro.dist import GraphOperator
from repro.kernels import ops, ref
from repro.kernels.cheb_sweep import cheb_sweep, jacobi_sweep

BACKENDS = ["dense", "pallas", "halo", "pallas_halo", "allgather"]


@pytest.fixture(scope="module")
def op120():
    """n=120 (not a 128 multiple) sensor graph + eta=3 SGWT union."""
    g, _ = graph.connected_sensor_graph(
        jax.random.PRNGKey(0), n=120, theta=0.2, kappa=0.25)
    lmax = g.lambda_max_bound()
    op = GraphOperator(P=g.laplacian(),
                       multipliers=wavelets.sgwt_multipliers(lmax, J=2),
                       lmax=lmax, K=12)
    return g, op


@pytest.fixture(scope="module")
def block_ell_500():
    """Multi-row-block, multi-slot Block-ELL structure (n=500)."""
    g, _ = graph.connected_sensor_graph(
        jax.random.PRNGKey(1), n=500, theta=0.075, kappa=0.075)
    A = graph.to_block_ell(np.asarray(g.laplacian()), (8, 128))
    return g, A


# ---------------------------------------------------------------------------
# Kernel vs reference vs per-order
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("batch_shape", [(), (5,), (64,), (2, 3)])
def test_cheb_sweep_kernel_matches_per_order(block_ell_500, batch_shape):
    """One `cheb_sweep` launch == K per-order SpMV+cheb_step launches ==
    the unrolled jnp oracle, across batch ranks (incl. B=64)."""
    g, A = block_ell_500
    lmax = g.lambda_max_bound()
    K, eta = 9, 3
    coeffs = jnp.asarray(
        np.random.RandomState(0).randn(eta, K + 1), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2),
                          batch_shape + (A.padded_n,))
    per_order = ops.fused_cheb_apply(A, x, coeffs, lmax, use_pallas=False,
                                     sweep=False)
    oracle = ref.cheb_sweep_ref(A.blocks, A.indices, x, coeffs,
                                alpha=lmax / 2)
    kern = cheb_sweep(A.blocks, A.indices, x, coeffs, alpha=lmax / 2,
                      interpret=True)
    assert kern.shape == batch_shape + (eta, A.padded_n)
    np.testing.assert_allclose(np.asarray(oracle), np.asarray(per_order),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(per_order),
                               atol=2e-5)


def test_sweep_dispatch_auto_and_forced(block_ell_500):
    """`fused_cheb_apply` default routes to the sweep; sweep=False keeps
    the per-order path; both agree."""
    g, A = block_ell_500
    lmax = g.lambda_max_bound()
    coeffs = jnp.asarray(np.random.RandomState(1).randn(2, 8), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, A.padded_n))
    auto = ops.fused_cheb_apply(A, x, coeffs, lmax, use_pallas=False)
    step = ops.fused_cheb_apply(A, x, coeffs, lmax, use_pallas=False,
                                sweep=False)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(step), atol=2e-5)


def test_vmem_guard_falls_back_and_logs(block_ell_500, caplog):
    """An over-budget sweep takes the per-order fallback — logged, same
    numbers."""
    g, A = block_ell_500
    lmax = g.lambda_max_bound()
    coeffs = jnp.asarray(np.random.RandomState(2).randn(2, 8), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (A.padded_n,))
    with caplog.at_level(logging.INFO, logger="repro.kernels.ops"):
        out = ops.fused_cheb_sweep(A, x, coeffs, lmax, use_pallas=True,
                                   vmem_budget=64)
    assert any("falling back to the per-order" in r.message
               for r in caplog.records)
    step = ops.fused_cheb_apply(A, x, coeffs, lmax, use_pallas=True,
                                sweep=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(step), atol=2e-5)
    # within budget: no fallback log
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="repro.kernels.ops"):
        ops.fused_cheb_sweep(A, x, coeffs, lmax, use_pallas=True)
    assert not any("falling back" in r.message for r in caplog.records)


def test_vmem_footprint_model(block_ell_500):
    """The guard formula counts the x operand, the t_k ping-pong pair and
    the eta accumulator planes — (n, B) with the batch on 128 lanes —
    plus the resident Block-ELL blocks, all under the (8, 128) tiling."""
    g, A = block_ell_500
    n, eta, B = A.padded_n, 3, 4
    got = ops.cheb_sweep_vmem_bytes(A.blocks.shape, n, eta, B)
    iterates = (3 + eta) * 128 * n * 4
    structure = A.blocks.size * 4          # (8, 128) blocks: no tile padding
    assert got == iterates + structure
    # the batch rides the lanes: B = 1 and B = 128 cost the same, B = 129
    # takes a second vreg column
    assert ops.cheb_sweep_vmem_bytes(A.blocks.shape, n, eta, 1) == got
    assert ops.cheb_sweep_vmem_bytes(A.blocks.shape, n, eta, 128) == got
    assert (ops.cheb_sweep_vmem_bytes(A.blocks.shape, n, eta, 129)
            == 2 * iterates + structure)


# ---------------------------------------------------------------------------
# Mixed-precision (bf16-scratch) sweep
# ---------------------------------------------------------------------------
def test_cheb_sweep_bf16_scratch_matches_ref(block_ell_500):
    """scratch_dtype='bf16': iterates/blocks/operand in bf16, f32 coef
    table + f32 accumulator — matches the f32 reference to bf16 tolerance
    and returns f32 output."""
    g, A = block_ell_500
    K, eta = 9, 3
    coeffs = jnp.asarray(
        np.random.RandomState(0).randn(eta, K + 1), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, A.padded_n))
    alpha = g.lambda_max_bound() / 2
    ref_out = ref.cheb_sweep_ref(A.blocks, A.indices, x, coeffs, alpha=alpha)
    got = cheb_sweep(A.blocks, A.indices, x, coeffs, alpha=alpha,
                     interpret=True, scratch_dtype="bf16")
    assert got.dtype == x.dtype
    scale = float(jnp.abs(ref_out).max())
    assert float(jnp.abs(got - ref_out).max()) / scale < 3e-2
    with pytest.raises(ValueError):
        cheb_sweep(A.blocks, A.indices, x, coeffs, alpha=alpha,
                   interpret=True, scratch_dtype="f16")


def test_jacobi_sweep_bf16_scratch_matches_ref(block_ell_500):
    g, A = block_ell_500
    L = np.asarray(g.laplacian())
    tau = 0.5
    den = (tau, 1.0)
    inv_d = ops.pad_trailing(
        jnp.asarray(tau / (tau + np.diag(L)), jnp.float32), A.padded_n)
    b = jax.random.normal(jax.random.PRNGKey(5), (4, A.padded_n))
    ws = jacobi.jacobi_weights(10)
    oracle = ref.jacobi_sweep_ref(A.blocks, A.indices, b, inv_d / tau,
                                  ws, jnp.zeros_like(b), den=den)
    kern = jacobi_sweep(A.blocks, A.indices, b, inv_d / tau, ws,
                        jnp.zeros_like(b), den=den, interpret=True,
                        scratch_dtype="bf16")
    scale = float(jnp.abs(oracle).max())
    assert float(jnp.abs(kern - oracle).max()) / scale < 3e-2


def test_vmem_footprint_model_bf16_and_measured_ratio(block_ell_500):
    """bf16 scratch halves exactly the scratch-width terms — the x
    operand, the t_k pair and the resident blocks (the eta f32
    accumulator planes stay) — and the TRACED pallas_call footprint
    (analysis.pallas_footprint, recovered from BlockSpecs + scratch avals)
    equals the model in both modes, so the guard admits what is staged."""
    from repro import analysis as A_
    g, A = block_ell_500
    n, eta, K, B = A.padded_n, 3, 10, 4
    got16 = ops.cheb_sweep_vmem_bytes(A.blocks.shape, n, eta, B,
                                      scratch_dtype="bf16")
    iterates = 3 * 128 * n * 2 + eta * 128 * n * 4  # acc stays f32
    structure = A.blocks.size * 2
    assert got16 == iterates + structure
    got32 = ops.cheb_sweep_vmem_bytes(A.blocks.shape, n, eta, B)
    assert got32 - got16 == (3 * 128 * n + A.blocks.size) * 2
    # jacobi model: b, D^-1 and the x pair stay wide; the Horner pair
    # (deg(den) >= 2) and the blocks halve
    j32 = ops.jacobi_sweep_vmem_bytes(A.blocks.shape, n, 2, batch=B)
    j16 = ops.jacobi_sweep_vmem_bytes(A.blocks.shape, n, 2, batch=B,
                                      scratch_dtype="bf16")
    assert j32 - j16 == A.blocks.size * 2
    j32 = ops.jacobi_sweep_vmem_bytes(A.blocks.shape, n, 4, batch=B)
    j16 = ops.jacobi_sweep_vmem_bytes(A.blocks.shape, n, 4, batch=B,
                                      scratch_dtype="bf16")
    assert j32 - j16 == (2 * 128 * n + A.blocks.size) * 2

    coeffs = jnp.ones((eta, K + 1), jnp.float32)
    x = jax.ShapeDtypeStruct((B, n), np.float32)

    def traced_bytes(sdt):
        def fn(v):
            return cheb_sweep(A.blocks, A.indices, v, coeffs, alpha=2.0,
                              interpret=True, scratch_dtype=sdt)
        closed = jax.make_jaxpr(fn)(x)
        eqns = [e for e, _ in A_.collect_eqns(closed, {"pallas_call"})]
        assert len(eqns) == 1
        return A_.pallas_footprint(eqns[0])["total_bytes"]

    assert traced_bytes("f32") == got32
    assert traced_bytes("bf16") == got16


def test_sweep_dtype_tag_survives_with_budget(op120):
    """A per-solve VMEM budget (`dataclasses.replace` on the runner's
    matvec record, as `plan.solve(vmem_budget=...)` does) keeps the
    record's sweep_dtype and structure, and both single-device builds
    put the sweep_dtype on their record."""
    g, op = op120
    plan = op.plan("pallas_halo", sweep_dtype="bf16")
    assert plan.info["sweep_dtype"] == "bf16"
    assert plan.info["sweep_vmem_bytes"] < op.plan(
        "pallas_halo").info["sweep_vmem_bytes"]

    # the single-device pallas backend takes the same knob
    pplan = op.plan("pallas", use_pallas=False, sweep_dtype="bf16")
    assert pplan.info["sweep_dtype"] == "bf16"
    tag = pplan.matvec_runner(
        lambda mv, v: v + (mv.sweep_dtype == "bf16"), (jnp.zeros(3),))
    assert float(tag[0]) == 1.0  # the solve path sees the bf16 tag
    assert pplan.info["sweep_vmem_bytes"] < op.plan(
        "pallas", use_pallas=False).info["sweep_vmem_bytes"]

    for p in (plan, pplan):
        seen = []
        p.matvec_runner(lambda mv, v: seen.append(mv) or v, (jnp.zeros(3),))
        mv = seen[-1]
        assert mv.sweep_dtype == "bf16" and mv.block_ell is not None
        budgeted = dataclasses.replace(mv, vmem_budget=123456)
        assert budgeted.vmem_budget == 123456
        assert budgeted.sweep_dtype == "bf16"
        assert budgeted.block_ell is mv.block_ell


# ---------------------------------------------------------------------------
# Jacobi sweep
# ---------------------------------------------------------------------------
def test_jacobi_sweep_kernel_matches_per_round(block_ell_500):
    """One `jacobi_sweep` launch == the per-round jacobi_solve loop, plain
    and Chebyshev-accelerated."""
    g, A = block_ell_500
    L = np.asarray(g.laplacian())
    tau = 0.5
    den = (tau, 1.0)   # den(P) = tau I + P   (Tikhonov split)
    inv_d = ops.pad_trailing(
        jnp.asarray(tau / (tau + np.diag(L)), jnp.float32), A.padded_n)
    b = jax.random.normal(jax.random.PRNGKey(5), (4, A.padded_n))

    def mv(v):
        return ops.spmv(A, v, use_pallas=False)

    def a_mv(v):
        return (tau * v + mv(v))

    for method, ws in (("jacobi", jacobi.jacobi_weights(10)),
                       ("cheb_jacobi", jacobi.cheb_jacobi_weights(0.8, 10))):
        kern = jacobi_sweep(A.blocks, A.indices, b, inv_d / tau, ws,
                            jnp.zeros_like(b), den=den, interpret=True)
        oracle = ref.jacobi_sweep_ref(A.blocks, A.indices, b, inv_d / tau,
                                      ws, jnp.zeros_like(b), den=den)
        if method == "jacobi":
            loop = jacobi.jacobi_solve(a_mv, None, b, 10,
                                       inv_diag=inv_d / tau,
                                       use_pallas=False)
        else:
            loop = jacobi.jacobi_chebyshev_solve(a_mv, None, b, 0.8, 10,
                                                 inv_diag=inv_d / tau,
                                                 use_pallas=False)
        np.testing.assert_allclose(np.asarray(oracle), np.asarray(loop),
                                   atol=2e-5, err_msg=method)
        np.testing.assert_allclose(np.asarray(kern), np.asarray(loop),
                                   atol=2e-5, err_msg=method)


def test_solve_one_launch_matches_dense(op120):
    """plan.solve on the sweep-tagged backends == dense, for the methods
    the one-launch jacobi_sweep serves (and history still works)."""
    g, op = op120
    y = jax.random.normal(jax.random.PRNGKey(6), (g.n_vertices,))
    Y = jax.random.normal(jax.random.PRNGKey(7), (8, g.n_vertices))
    dense = op.plan("dense")
    mesh = jax.make_mesh((1,), ("graph",))
    for backend in ("pallas", "pallas_halo"):
        plan = (op.plan(backend) if backend == "pallas"
                else op.plan(backend, mesh=mesh))
        for method in ("jacobi", "cheb_jacobi"):
            r = plan.solve(y, method, tau=0.5, n_iters=12)
            r0 = dense.solve(y, method, tau=0.5, n_iters=12)
            np.testing.assert_allclose(np.asarray(r.x), np.asarray(r0.x),
                                       atol=5e-4, err_msg=(backend, method))
            rb = plan.solve(Y, method, tau=0.5, n_iters=12)
            r0b = dense.solve(Y, method, tau=0.5, n_iters=12)
            np.testing.assert_allclose(np.asarray(rb.x), np.asarray(r0b.x),
                                       atol=5e-4, err_msg=(backend, method))
            rh = plan.solve(y, method, tau=0.5, n_iters=12, history=True)
            assert rh.history is not None and rh.history.shape[0] == 12
            np.testing.assert_allclose(np.asarray(rh.x), np.asarray(r0.x),
                                       atol=5e-4, err_msg=(backend, method))


# ---------------------------------------------------------------------------
# Backend equivalence with the sweep engaged
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_apply_matches_dense_with_sweep(op120, backend):
    """All five backends agree on B=64 batched apply with the sweep
    dispatch live (n=120: every kernel path exercises its padding)."""
    g, op = op120
    dense = op.plan("dense")
    if backend in ("halo", "pallas_halo", "allgather"):
        plan = op.plan(backend, mesh=jax.make_mesh((1,), ("graph",)))
    else:
        plan = op.plan(backend)
    F = jax.random.normal(jax.random.PRNGKey(8), (64, g.n_vertices))
    np.testing.assert_allclose(np.asarray(plan.apply(F)),
                               np.asarray(dense.apply(F)), atol=1e-4)


def test_pallas_plan_sweep_off_matches(op120):
    """plan("pallas", sweep=False) keeps the per-order path and agrees."""
    g, op = op120
    f = jax.random.normal(jax.random.PRNGKey(9), (g.n_vertices,))
    on = op.plan("pallas").apply(f)
    off = op.plan("pallas", sweep=False).apply(f)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off), atol=1e-4)


# ---------------------------------------------------------------------------
# ExecutionPlan compiled-callable memoization
# ---------------------------------------------------------------------------
def test_compiled_apply_skips_retrace(op120):
    """plan.compiled("apply") returns one jit wrapper: repeated same-shape
    calls trace once; a new shape traces once more."""
    g, op = op120
    plan = op.plan("dense")
    f = jax.random.normal(jax.random.PRNGKey(10), (g.n_vertices,))
    traces = []
    orig = plan.apply

    def counting_apply(x):
        traces.append(1)          # runs at trace time only
        return orig(x)

    plan2 = dataclasses.replace(plan, apply=counting_apply)
    compiled = plan2.compiled("apply")
    assert plan2.compiled("apply") is compiled
    compiled(f)
    compiled(f)
    compiled(f)
    assert len(traces) == 1
    compiled(jnp.stack([f, f]))   # new shape -> exactly one more trace
    assert len(traces) == 2
    with pytest.raises(KeyError, match="unknown kind"):
        plan2.compiled("nope")


def test_compiled_solve_memoizes(op120):
    """compiled_solve returns the same jitted solver per (method, kwargs)
    and matches plan.solve."""
    g, op = op120
    plan = op.plan("dense")
    y = jax.random.normal(jax.random.PRNGKey(11), (g.n_vertices,))
    s1 = plan.compiled_solve("jacobi", tau=0.5, n_iters=10)
    s2 = plan.compiled_solve("jacobi", tau=0.5, n_iters=10)
    assert s1 is s2
    s3 = plan.compiled_solve("jacobi", tau=0.7, n_iters=10)
    assert s3 is not s1
    np.testing.assert_allclose(
        np.asarray(s1(y)),
        np.asarray(plan.solve(y, "jacobi", tau=0.5, n_iters=10).x),
        atol=1e-5)


# ---------------------------------------------------------------------------
# Sharded: interior/boundary split keeps the 2K|E| accounting exact
# ---------------------------------------------------------------------------
PAYLOAD = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import graph, wavelets
from repro.dist import GraphOperator, plan_comm_stats, verify_message_scaling

key = jax.random.PRNGKey(1)
g, key = graph.connected_sensor_graph(key, n=600, theta=0.07, kappa=0.07)
gs, _ = graph.spatial_sort(g)
lmax = gs.lambda_max_bound()
K = 15
op = GraphOperator(P=gs.laplacian(),
                   multipliers=wavelets.sgwt_multipliers(lmax, J=3),
                   lmax=lmax, K=K)
mesh = jax.make_mesh((8,), ("graph",),
                     axis_types=(jax.sharding.AxisType.Auto,))
f = jax.random.normal(key, (g.n_vertices,))
F = jax.random.normal(jax.random.PRNGKey(3), (64, g.n_vertices))
dense = op.plan("dense")
for backend in ("halo", "pallas_halo"):
    plan = op.plan(backend, mesh=mesh)
    # numbers unchanged by the split, single and B=64 batched
    assert float(jnp.abs(plan.apply(f) - dense.apply(f)).max()) < 1e-4
    assert float(jnp.abs(plan.apply(F) - dense.apply(F)).max()) < 1e-4
    # paper-level: measured messages == 2K|E| exactly, batch-invariant
    v = verify_message_scaling(plan, g.n_edges, batch=64)
    assert v["max_rel_dev"] == 0.0, (backend, v["rel_dev"])
    assert v["measured"]["apply"] == 2 * K * g.n_edges, backend
    # device-level: the wire carries ONLY the h-row boundary tile per
    # direction per round (the split's payload claim), every round
    h = plan.info["halo_width"]
    st = plan_comm_stats(plan)["apply"]
    assert st.exchange_rounds == K, backend
    assert st.bytes_per_shard == 2 * K * h * 4, backend
    assert st.bytes_per_round == 2 * h * 4, backend
    assert st.bytes_per_shard * 8 == plan.info["halo_bytes_per_apply"], backend
    # batched payload grows with B, round count does not
    stB = plan_comm_stats(plan, batch=64)["apply"]
    assert stB.exchange_rounds == K, backend
    assert stB.bytes_per_shard == 64 * st.bytes_per_shard, backend
    # solver rounds through the same split matvec: deg(den)=1 Tikhonov
    # Jacobi costs exactly n_iters exchange rounds + deg(num)=0 for b
    from repro.dist.commstats import solve_comm_stats
    sj = solve_comm_stats(plan, "jacobi", tau=0.5, n_iters=10)
    assert sj.exchange_rounds == 10, backend
    print(backend, "OK")
print("SWEEP SPLIT OK")
"""


def test_interior_boundary_split_8shards():
    """8 genuinely sharded devices: the interior/boundary split leaves the
    measured message count at exactly 2K|E| (batch-invariant) while the
    per-round payload is the 2h boundary tile."""
    out = run_payload(PAYLOAD, n_devices=8)
    assert "SWEEP SPLIT OK" in out
