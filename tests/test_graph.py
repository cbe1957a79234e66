import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph


def test_laplacian_psd_and_rowsum(sensor120):
    L = np.asarray(sensor120.laplacian())
    assert np.allclose(L, L.T, atol=1e-6)
    lam = np.linalg.eigvalsh(L)
    assert lam[0] > -1e-4                       # PSD
    assert np.abs(L.sum(axis=1)).max() < 1e-3   # zero row sums


def test_lambda_max_bound_dominates(sensor120):
    L = np.asarray(sensor120.laplacian())
    lam_max = np.linalg.eigvalsh(L)[-1]
    assert sensor120.lambda_max_bound() >= lam_max - 1e-4


def test_normalized_laplacian_spectrum(sensor120):
    Ln = np.asarray(sensor120.laplacian("normalized"))
    lam = np.linalg.eigvalsh(Ln)
    assert lam[0] > -1e-5 and lam[-1] < 2.0 + 1e-5


def test_sensor_graph_matches_paper_construction():
    g = graph.sensor_graph(jax.random.PRNGKey(0), n=500)
    W = np.asarray(g.W)
    assert W.shape == (500, 500)
    assert np.allclose(W, W.T)
    assert np.all(np.diag(W) == 0)
    # weights only inside the kappa radius, Gaussian kernel values in (0, 1]
    nz = W[W > 0]
    assert nz.min() > 0 and nz.max() <= 1.0
    coords = np.asarray(g.coords)
    d2 = ((coords[:, None] - coords[None, :]) ** 2).sum(-1)
    assert np.all(d2[W > 0] <= 0.075**2 + 1e-9)


def test_k_scaling_matrix_reduces_to_lnorm(sensor120):
    S0 = np.asarray(graph.k_scaling_matrix(sensor120.W, gamma=0.0))
    Ln = np.asarray(sensor120.laplacian("normalized"))
    assert np.allclose(S0, Ln, atol=1e-5)


def test_block_ell_roundtrip_and_matvec(sensor120):
    L = np.asarray(sensor120.laplacian())
    A = graph.to_block_ell(L, (8, 128))
    x = np.random.RandomState(0).randn(A.padded_n).astype(np.float32)
    y = graph.block_ell_matvec_ref(A, jnp.asarray(x))
    y_ref = np.pad(L, ((0, A.padded_n - L.shape[0]),) * 2) @ x
    np.testing.assert_allclose(np.asarray(y)[: L.shape[0]],
                               y_ref[: L.shape[0]], atol=1e-4)


def test_spatial_sort_banded_partition(sensor_banded):
    from repro.dist.backends.halo import partition_banded

    L = np.asarray(sensor_banded.laplacian())
    parts, leak = partition_banded(L, 8)
    assert leak == 0.0
    dense = np.zeros((parts.n_shards * parts.n_local,) * 2, np.float32)
    nl = parts.n_local
    for s in range(parts.n_shards):
        r = slice(s * nl, (s + 1) * nl)
        dense[r, r] = np.asarray(parts.diag[s])
        if s > 0:
            dense[r, slice((s - 1) * nl, s * nl)] = np.asarray(parts.left[s])
        if s < parts.n_shards - 1:
            dense[r, slice((s + 1) * nl, (s + 2) * nl)] = np.asarray(parts.right[s])
    np.testing.assert_allclose(dense[: L.shape[0], : L.shape[0]], L, atol=1e-6)


def test_ring_and_torus_graphs():
    r = graph.ring_graph(8)
    assert r.degrees().min() == r.degrees().max() == 2.0
    t = graph.torus_graph(4, 4)
    assert t.degrees().min() == t.degrees().max() == 4.0
    assert r.is_connected() and t.is_connected()
