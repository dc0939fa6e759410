"""The Graph500 SSSP configuration: its weighted generator, search keys, the
driver's comparison and the frontier metric, at small sizes on the CPU."""
import types

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

from bench.harness import manifest as M
from bench.harness import reference
from bench.harness.drivers import Data

CELL = "kron15.sssp"
SEEDS = (7, 2**33 + 8)


def small_params():
    cfg = M.resolve(CELL).config
    return dict(cfg["params"], **cfg["small"])


def weighted(seed):
    rows, cols, w, shape = M.generator("kronecker_sssp").triplets(
        small_params(), seed)
    return rows, cols, w, shape, sp.csr_matrix((w, (rows, cols)), shape=shape)


@pytest.mark.parametrize("seed", SEEDS)
def test_weights_are_symmetric_on_kroneckers_structure(seed):
    rows, cols, w, shape, a = weighted(seed)
    kr, kc, _, kshape = M.generator("kronecker").triplets(small_params(),
                                                          seed)
    assert shape == kshape
    np.testing.assert_array_equal(rows, kr)  # the same edges, same order
    np.testing.assert_array_equal(cols, kc)
    assert a.nnz == len(w) and not np.any(rows == cols)
    assert abs(a - a.T).max() == 0
    assert w.dtype == np.float64 and 0 <= w.min() and w.max() < 1


def test_seeds_search_the_same_weighted_graph_up_to_labels():
    runs = []
    for seed in SEEDS:
        rows, cols, w, shape, a = weighted(seed)
        keys = M.generator("kronecker_sssp").search_keys(small_params(),
                                                          rows, w)
        assert len(set(keys.tolist())) == len(keys) == 64
        assert np.bincount(rows, minlength=shape[0])[keys].min() >= 1
        runs.append((np.sort(w), np.sort(dijkstra(a, indices=keys), axis=1)))
    (w1, d1), (w2, d2) = runs
    np.testing.assert_array_equal(w1, w2)
    np.testing.assert_array_equal(d1, d2)  # each key's distances alike


def test_check_reads_inf_when_the_reached_sets_differ():
    cell = M.resolve(CELL)
    params, seed = small_params(), 11
    gen = M.generator(cell.config["generator"])
    rows, cols, w, shape = gen.triplets(params, seed)
    data = Data(rows, cols, w, shape, "float32", seed, gen, params)
    driver = M.driver(cell.traffic["driver"])(cell.traffic, data, [])
    ref = reference.Reference(rows, cols, w, shape)
    d = dijkstra(ref.a.T.tocsr(), indices=driver.keys[0]).astype(np.float32)
    driver.results = [(d, 8, True)]
    assert driver.check(ref)["sssp_err"] < 1e-6
    far = d.copy()
    far[np.argmax(np.isinf(d))] = 1.0  # a vertex no path reaches
    driver.results = [(far, 8, True)]
    assert driver.check(ref)["sssp_err"] == float("inf")


def test_frontier_share_reads_the_counter_and_none_without_it():
    reader = M.metric_reader("solve.frontier_pct")
    run = types.SimpleNamespace(shape=(100, 100), window={
        "frontier": [(8, 200), (16, 400)]})
    assert reader.read(run) == pytest.approx(25.0)
    run.window = {"frontier": [(8, None)]}  # a program with no counter
    assert reader.read(run) is None
    run.window = {}
    assert reader.read(run) is None
