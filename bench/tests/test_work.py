"""The graphs and the work count of one apply against the hand count in
PERF.md."""
import numpy as np
import pytest

from bench import work
from bench.graphs import load, sensor_field

SENSOR1M = {"kind": "sensor_field", "n": 1000000, "theta": 0.074,
            "kappa": 0.075, "density": 500, "graph_seed": 0}
SENSOR500 = {"kind": "sensor", "n": 500, "theta": 0.074, "kappa": 0.075,
             "graph_seed": 0, "max_draws": 50}


@pytest.mark.parametrize("spec, n, nnz, edges", [
    (SENSOR500, 500, 4434, 1967),
    (SENSOR1M, 1000000, 9824716, 4412358),
])
def test_graph_sizes(spec, n, nnz, edges):
    g = load(spec)
    assert (g.n, g.nnz, g.n_edges) == (n, nnz, edges)


@pytest.mark.parametrize("n", [700, 3000])
def test_sensor_field_pairs_are_all_pairs_within_kappa(n):
    """The cell search finds exactly the pairs a full distance matrix
    finds, and the sensors are numbered cell by cell."""
    kappa, side = 0.075, np.sqrt(n / 500)
    xy, cells = sensor_field.positions(n, side, kappa,
                                       np.random.default_rng(3))
    c = np.minimum((xy / kappa).astype(np.int64), cells - 1)
    assert np.all(np.diff(c[:, 1] * cells + c[:, 0]) >= 0)
    lo, hi = sensor_field.near_pairs(xy, kappa, cells)
    d2 = ((xy[:, None] - xy[None]) ** 2).sum(-1)
    want_lo, want_hi = np.nonzero(np.triu(d2 <= kappa * kappa, k=1))
    np.testing.assert_array_equal(lo, want_lo)
    np.testing.assert_array_equal(hi, want_hi)


@pytest.mark.parametrize("n, nnz, batch, chips, nbytes, flops, least", [
    # sensor500.apply_b64
    (500, 4434, 64, 1, 1059472, 20759040, 1059472 / 819e9),
    # sensor1m.apply_b64
    (1000000, 9824716, 64, 1, 2126597728, 43967272960, 2126597728 / 819e9),
    # sensor1m.apply_b64's graph and batch over four chips
    (1000000, 9824716, 64, 4, 2126597728, 43967272960,
     2126597728 / (4 * 819e9)),
])
def test_apply_work(n, nnz, batch, chips, nbytes, flops, least):
    assert work.apply_bytes(n, nnz, batch, eta=7) == nbytes
    assert work.apply_flops(n, nnz, batch, K=20, eta=7) == flops
    assert work.least_seconds(n, nnz, batch, 20, 7, chips,
                              "TPU v5 lite") == pytest.approx(least, rel=1e-12)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        work.peaks("cpu")
