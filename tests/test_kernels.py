"""Pallas kernel sweeps (interpret mode) vs the pure-jnp oracles in ref.py."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import chebyshev as cheb
from repro.core import filters, graph
from repro.kernels import ops, ref
from repro.kernels import bcsr_spmv
from repro.kernels.bcsr_spmv import (block_ell_spmv_batched,
                                     block_ell_spmv_window, window_starts)
from repro.kernels.cheb_step import cheb_step
from repro.kernels.flash_attention import flash_attention
from repro.kernels.soft_threshold import ista_shrink


@pytest.mark.parametrize("n,block", [(300, (8, 128)), (513, (8, 128)),
                                     (1024, (16, 128)), (200, (8, 256)),
                                     (300, (8, 8))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_ell_spmv_sweep(n, block, dtype):
    """A 1-D signal rides the batched kernel as a batch of one."""
    g, _ = graph.connected_sensor_graph(jax.random.PRNGKey(n), n=n,
                                        theta=0.15, kappa=0.15)
    L = np.asarray(g.laplacian(), dtype=np.float32)
    A = graph.to_block_ell(L, block)
    blocks = A.blocks.astype(dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (A.padded_n,), dtype)
    y_k = block_ell_spmv_batched(graph.block_panels(blocks), A.indices, x,
                                 interpret=True)
    assert y_k.shape == x.shape
    y_r = ref.block_ell_spmv_ref(blocks, A.indices, x)
    tol = 1e-4 if dtype == jnp.float32 else 2e-1
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("rows_per_launch", [1, 5])
def test_block_ell_spmv_smem_chunks(monkeypatch, rows_per_launch):
    """Structures whose column indices overflow one SMEM prefetch are
    swept in row-block chunks that write one aliased output."""
    g, _ = graph.connected_sensor_graph(jax.random.PRNGKey(3), n=300,
                                        theta=0.15, kappa=0.15)
    A = graph.to_block_ell(np.asarray(g.laplacian(), np.float32), (8, 128))
    slots = A.indices.shape[1]
    monkeypatch.setattr(bcsr_spmv, "SMEM_INDEX_WORDS",
                        rows_per_launch * slots)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, A.padded_n))
    y_k = bcsr_spmv.block_ell_spmv_batched.__wrapped__(
        A.panels, A.indices, x, interpret=True)
    y_r = ref.block_ell_spmv_ref(A.blocks, A.indices, x)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-4)


def banded_block_ell(n, block, seed=0):
    """A spatially sorted sensor graph's Laplacian in Block-ELL: a known,
    narrow band, and row blocks with fewer valid slots than the widest
    (so padded slots)."""
    g, _ = graph.connected_sensor_graph(jax.random.PRNGKey(seed), n=n,
                                        theta=0.15, kappa=0.15)
    gs, _ = graph.spatial_sort(g)
    return graph.to_block_ell(np.asarray(gs.laplacian(), np.float32), block)


@pytest.mark.parametrize("n,block,rows", [(300, (8, 8), 5),
                                          (513, (8, 128), 7),
                                          (1024, (8, 128), 24)])
@pytest.mark.parametrize("batch", [(), (3,), (64,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_ell_spmv_window(n, block, rows, batch, dtype):
    """The band-windowed grid against the oracle: groups that do not
    divide the row blocks (a partial last group), windows clamped at
    both ends of the matrix, padded slots, and B = 1, 3, 64."""
    A = banded_block_ell(n, block)
    nrb, br, bc = A.n_row_blocks, *block
    starts, span = window_starts(nrb, br, bc, A.band, rows)
    assert nrb % rows and span < nrb * br // bc
    assert starts[0] == 0 and starts[-1] == nrb * br // bc - span
    assert not np.asarray(A.mask).all()
    blocks = A.blocks.astype(dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), batch + (A.padded_n,),
                          dtype)
    y_k = block_ell_spmv_window(graph.block_panels(blocks), A.indices, x,
                                band=A.band, rows=rows, interpret=True)
    assert y_k.shape == x.shape
    y_r = ref.block_ell_spmv_ref(blocks, A.indices, x)
    tol = 1e-4 if dtype == jnp.float32 else 2e-1
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), atol=tol, rtol=tol)


def test_block_ell_spmv_window_needs_the_band():
    """The window holds only what the band promises: told a band of 0,
    a row block reads its own column block and drops its neighbours'."""
    A = banded_block_ell(300, (8, 8))
    x = jax.random.normal(jax.random.PRNGKey(2), (3, A.padded_n))
    y_r = ref.block_ell_spmv_ref(A.blocks, A.indices, x)
    y_k = block_ell_spmv_window(A.panels, A.indices, x, band=0, rows=1,
                                interpret=True)
    assert float(jnp.abs(y_k - y_r).max()) > 1e-2


@pytest.mark.parametrize("case,counted", [("banded", "spmv.window"),
                                          ("no_band", "spmv.gather"),
                                          ("over_budget", "spmv.gather")])
def test_spmv_dispatch_follows_structure(monkeypatch, case, counted):
    """`ops.spmv` takes the window where the band is known and the
    window fits the VMEM budget, else the gather path; both match the
    oracle, and each choice is counted."""
    A = banded_block_ell(300, (8, 8))
    if case == "no_band":
        A = dataclasses.replace(A, band=None)
    if case == "over_budget":
        monkeypatch.setattr(ops, "DEFAULT_SPMV_WINDOW_VMEM_BUDGET", 64 * 1024)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, A.padded_n))
    obs.reset()
    y_k = ops.spmv(A, x, use_pallas=True)
    other = ({"spmv.window", "spmv.gather"} - {counted}).pop()
    assert obs.snapshot().get(counted) == 1 and other not in obs.snapshot()
    y_r = ref.block_ell_spmv_ref(A.blocks, A.indices, x)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-4)


def _chip_ell(band, slots=23, block=(8, 8), n=1_000_000, dtype=jnp.float32):
    br, bc = block
    return types.SimpleNamespace(
        panels=jax.ShapeDtypeStruct((n // br, br, slots * bc), dtype),
        indices=jax.ShapeDtypeStruct((n // br, slots), jnp.int32), band=band)


@pytest.mark.parametrize("band,batch,rows", [
    (265, 64, 128),    # sensor1m: a 658-block window of 4 KiB tiles
    (265, 129, 64),    # two lane tiles a column tile: smaller groups
    (700, 64, 32),     # a wider band: smaller groups
    (1500, 64, None),  # even 16 rows: two 3,016-block windows, 24 MiB
    (None, 64, None),  # no band known
])
def test_spmv_window_rows_follow_the_footprint(band, batch, rows):
    """The group size comes from the structure, the batch's lanes and
    the budget; a window that cannot fit takes the gather path."""
    x = jax.ShapeDtypeStruct((batch, 1_000_000), jnp.float32)
    assert ops.spmv_window_rows(_chip_ell(band), x) == rows


@pytest.mark.parametrize("n,eta", [(1024, 1), (2048, 3), (896, 7)])
def test_cheb_step_sweep(n, eta):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    pt, t1, t2 = (jax.random.normal(k, (n,)) for k in ks[:3])
    acc = jax.random.normal(ks[3], (eta, n))
    coef = jax.random.normal(ks[4], (eta,))
    tk_k, acc_k = cheb_step(pt, t1, t2, acc, coef, alpha=1.3, interpret=True)
    tk_r, acc_r = ref.cheb_step_ref(pt, t1, t2, acc, coef, alpha=1.3)
    np.testing.assert_allclose(np.asarray(tk_k), np.asarray(tk_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(acc_k), np.asarray(acc_r), atol=1e-5)


@pytest.mark.parametrize("batch,n,eta", [((64,), 1000, 4), ((70,), 300, 2),
                                         ((2, 3), 120, 3)])
def test_cheb_step_batched_ragged(batch, n, eta):
    """Batched iterates on a cdiv grid: ragged lane and batch edge tiles
    (n not a 128 multiple, B > one 64-row tile) and the multiplier-major
    (eta, ..., n) accumulator match the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    pt, t1, t2 = (jax.random.normal(k, batch + (n,)) for k in ks[:3])
    acc = jax.random.normal(ks[3], (eta,) + batch + (n,))
    coef = jax.random.normal(ks[4], (eta,))
    tk_k, acc_k = cheb_step(pt, t1, t2, acc, coef, alpha=0.9, interpret=True)
    tk_r, acc_r = ref.cheb_step_ref(pt, t1, t2, acc, coef, alpha=0.9)
    np.testing.assert_allclose(np.asarray(tk_k), np.asarray(tk_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(acc_k), np.asarray(acc_r), atol=1e-5)


def one_term_per_order(matvec, x, c, lmax):
    """The schedule before the deferred fold, written out: every order
    adds its one term c_{j,k} T_k into the whole accumulator."""
    mv2, st = cheb._stateful_matvec(matvec, x)
    alpha = lmax / 2.0
    acc = 0.5 * c[:, 0:1] * x[..., None, :]
    px, st = mv2(x, st)
    t_km2, t_km1 = x, px / alpha - x
    acc = acc + c[:, 1:2] * t_km1[..., None, :]
    for k in range(2, c.shape[1]):
        pt, st = mv2(t_km1, st)
        tk = (2.0 / alpha) * pt - 2.0 * t_km1 - t_km2
        acc = acc + c[:, k:k + 1] * tk[..., None, :]
        t_km2, t_km1 = t_km1, tk
    return acc


def dense_matvec(n, seed=0):
    """t -> t @ A for a symmetric A with its spectrum inside [0, 4]."""
    a = jax.random.normal(jax.random.PRNGKey(seed), (n, n)) / np.sqrt(n)
    A = 0.5 * (a + a.T) + 2.0 * jnp.eye(n)
    return lambda t: jnp.dot(t, A, precision="highest")


def error_feedback_matvec(n):
    """A stateful matvec (the int8 exchange's protocol): each order sends
    its iterate rounded to 1/64 plus the rounding left over before."""
    mv = dense_matvec(n)

    def stateful(t, r):
        v = t + r
        q = jnp.round(v * 64.0) / 64.0
        return mv(q), v - q

    return cheb.LocalMatvec(stateful, init_state=jnp.zeros_like)


def assert_same_sum(got, want):
    """Within 1e-6 of the largest entry (`max_rel_err`'s measure): the
    fold adds the same f32 products in another order."""
    want = np.asarray(want)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel", "reference"])
@pytest.mark.parametrize("batch", [(), (3,), (2, 5)])
@pytest.mark.parametrize("K", [2, 3, 8, 9, 20])
@pytest.mark.parametrize("eta", [1, 4, 7])
def test_deferred_fold_matches_one_term_per_order(eta, K, batch, use_pallas):
    """s = eta + 1 terms a fold: K = 2 and 3 end inside the first chunk
    or just past it, 8 and 9 give exact and partial chunks at eta = 7,
    20 gives 8 + 8 + 5 there and 3 + 9 x 2 at eta = 1."""
    n = 200
    mv = dense_matvec(n)
    x = jax.random.normal(jax.random.PRNGKey(1), batch + (n,))
    c = jax.random.normal(jax.random.PRNGKey(2), (eta, K + 1))
    got = ops._cheb_recurrence_loop(mv, x, c, 4.0, use_pallas=use_pallas)
    assert_same_sum(got, one_term_per_order(mv, x, c, 4.0))


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel", "reference"])
def test_deferred_fold_threads_matvec_state(use_pallas):
    """A stateful matvec's state rides the unrolled chunks and the scan
    carry in the order of the matvecs, as it did one order at a time."""
    n, eta, K = 200, 3, 13
    mv = error_feedback_matvec(n)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, n))
    c = jax.random.normal(jax.random.PRNGKey(4), (eta, K + 1))
    got = ops._cheb_recurrence_loop(mv, x, c, 4.0, use_pallas=use_pallas)
    want = one_term_per_order(mv, x, c, 4.0)
    assert_same_sum(got, want)
    plain = one_term_per_order(dense_matvec(n), x, c, 4.0)
    assert float(jnp.abs(want - plain).max()) > 1e-3


def test_cheb_step_fold_reads_each_iterate_once():
    """On the order that closes a chunk the iterates held for the fold
    that are also t_{k-1} and t_{k-2} are one operand each, and the fold
    matches its oracle with and without an accumulator to read."""
    ks = jax.random.split(jax.random.PRNGKey(6), 7)
    held = tuple(jax.random.normal(k, (3, 300)) for k in ks[:4])
    pt = jax.random.normal(ks[4], (3, 300))
    acc = jax.random.normal(ks[5], (2, 3, 300))
    coef = jax.random.normal(ks[6], (2, 5))
    for a in (None, acc):
        got = cheb_step(pt, held[-1], held[-2], a, coef, alpha=1.1,
                        held=held, interpret=True)
        want = ref.cheb_step_ref(pt, held[-1], held[-2], a, coef, alpha=1.1,
                                 held=held)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=1e-6)
    jaxpr = jax.make_jaxpr(lambda *h: cheb_step(
        pt, h[-1], h[-2], None, coef, alpha=1.1, held=h, interpret=True))(
        *held)
    (call,) = [e for e in jaxpr.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    assert len(call.invars) == 1 + 1 + len(held)     # coef, pt, held


@pytest.mark.parametrize("eta,n", [(2, 1024), (5, 1280)])
def test_ista_shrink_sweep(eta, n):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    a, phi_y, gram = (jax.random.normal(k, (eta, n)) for k in ks[:3])
    th = jnp.abs(jax.random.normal(ks[3], (eta, 1))) * 0.3
    out_k = ista_shrink(a, phi_y, gram, th, gamma=0.2, interpret=True)
    out_r = ref.ista_shrink_ref(a, phi_y, gram, th, gamma=0.2)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-6)


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 2, 2, 128, 64),
    (2, 4, 2, 256, 64),    # GQA
    (1, 8, 1, 256, 128),   # MQA
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, s, d, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    o_k = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    o_r = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol, rtol=tol)


def test_fused_cheb_apply_matches_core(sensor120):
    L = np.asarray(sensor120.laplacian())
    A = graph.to_block_ell(L, (8, 128))
    lmax = sensor120.lambda_max_bound()
    coeffs = cheb.cheb_coeffs_stack(
        [filters.tikhonov(1.0), filters.heat(0.5)], 12, lmax)
    x = jax.random.normal(jax.random.PRNGKey(3), (A.padded_n,))
    Lp = jnp.asarray(np.pad(L, ((0, A.padded_n - L.shape[0]),) * 2))
    fused = ops.fused_cheb_apply(A, x, coeffs, lmax, use_pallas=True)
    core = cheb.cheb_apply(lambda t: Lp @ t, x,
                           jnp.asarray(coeffs, x.dtype), lmax)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(core), atol=1e-4)
