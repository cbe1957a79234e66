"""Pallas kernel sweeps (interpret mode) vs the pure-jnp oracles in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import chebyshev as cheb
from repro.core import filters, graph
from repro.kernels import ops, ref
from repro.kernels import bcsr_spmv
from repro.kernels.bcsr_spmv import block_ell_spmv_batched
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
    (n not a 128 multiple, B > one 64-row tile) match the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    pt, t1, t2 = (jax.random.normal(k, batch + (n,)) for k in ks[:3])
    acc = jax.random.normal(ks[3], batch + (eta, n))
    coef = jax.random.normal(ks[4], (eta,))
    tk_k, acc_k = cheb_step(pt, t1, t2, acc, coef, alpha=0.9, interpret=True)
    tk_r, acc_r = ref.cheb_step_ref(pt, t1, t2, acc, coef, alpha=0.9)
    np.testing.assert_allclose(np.asarray(tk_k), np.asarray(tk_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(acc_k), np.asarray(acc_r), atol=1e-5)


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
