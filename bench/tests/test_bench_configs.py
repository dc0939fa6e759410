"""The deployment generators, at small sizes on the CPU."""
import json

import numpy as np
import pytest

from bench.harness import manifest as M

KRON = {"scale": 8, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19,
        "structure_seed": 1}


def csr(t):
    import scipy.sparse as sp

    rows, cols, vals, shape = t
    return sp.csr_matrix((vals, (rows, cols)), shape=shape)


@pytest.mark.parametrize("n", [(3, 3, 3), (4, 5, 6), (8, 8, 8)])
def test_stencil_shape_symmetry_and_diagonal(n):
    gen = M.generator("stencil27")
    t = gen.triplets({"nx": n[0], "ny": n[1], "nz": n[2]}, seed=0)
    rows, cols, vals, shape = t
    assert shape == (n[0] * n[1] * n[2],) * 2
    assert len(vals) == np.prod([3 * k - 2 for k in n])
    a = csr(t)
    assert a.nnz == len(vals)  # no duplicate coordinates
    assert abs(a - a.T).max() == 0
    np.testing.assert_array_equal(a.diagonal(), 26.0)
    assert set(np.unique(vals)) == {-1.0, 26.0}
    # an interior point has all 26 neighbours, so its row sums to zero
    counts = np.bincount(rows, minlength=shape[0])
    assert counts.max() == 27


def test_kronecker_symmetric_no_self_loops_stochastic_columns():
    gen = M.generator("kronecker")
    t = gen.triplets(KRON, seed=5)
    rows, cols, vals, shape = t
    assert shape == (256, 256)
    assert not np.any(rows == cols)
    a = csr(t)
    assert a.nnz == len(vals)
    pattern = (a != 0).astype(np.int8)
    assert abs(pattern - pattern.T).max() == 0
    sums = np.asarray(a.sum(axis=0)).ravel()
    degree = np.bincount(cols, minlength=shape[0])
    np.testing.assert_allclose(sums[degree > 0], 1.0, rtol=1e-12)
    assert np.all(sums[degree == 0] == 0)


def test_kronecker_same_seed_same_triplets():
    gen = M.generator("kronecker")
    big = 2**40 + 3  # seeds beyond 32 bits work
    one, two = gen.triplets(KRON, big), gen.triplets(KRON, big)
    for x, y in zip(one[:3], two[:3]):
        np.testing.assert_array_equal(x, y)
    other = gen.triplets(KRON, big + 1)
    assert len(other[0]) != len(one[0]) or np.any(other[0] != one[0])


def test_kronecker_seeds_share_the_graph_up_to_labels():
    gen = M.generator("kronecker")
    one, two = gen.triplets(KRON, 7), gen.triplets(KRON, 2**33 + 8)
    assert len(one[0]) == len(two[0])
    for t in (one, two):
        assert sorted(np.bincount(t[1], minlength=256)) == sorted(
            np.bincount(one[1], minlength=256))
    assert np.any(one[0] != two[0])
    other = gen.triplets(dict(KRON, structure_seed=2), 7)
    assert len(other[0]) != len(one[0]) or np.any(other[0] != one[0])


def cg_residuals(a, b, steps):
    """float64 CG from x0 = 0: ||r_k|| / ||b|| for k = 1..steps."""
    x = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rs = r @ r
    out = []
    for _ in range(steps):
        ap = a @ p
        alpha = rs / (p @ ap)
        x += alpha * p
        r -= alpha * ap
        rs_next = r @ r
        p = r + rs_next / rs * p
        rs = rs_next
        out.append(np.sqrt(rs) / np.linalg.norm(b))
    return np.array(out)


@pytest.mark.parametrize("n", [(4, 5, 6), (8, 8, 8)])
def test_stencil_solutions_weigh_every_eigenvector_alike(n):
    gen = M.generator("stencil27")
    params = {"nx": n[0], "ny": n[1], "nz": n[2]}
    a = csr(gen.triplets(params, seed=0))
    xs = gen.solutions(params, np.random.default_rng(3), 3)
    assert xs.shape == (3, a.shape[0])
    # x* has weight +-1 on each orthonormal eigenvector, so |x*|^2 = n
    np.testing.assert_allclose((xs ** 2).sum(axis=1), a.shape[0])
    assert np.any(xs[0] != xs[1])
    # the same spectrum: CG takes the same path for every x*
    runs = [cg_residuals(a, a @ x, 12) for x in xs]
    for r in runs[1:]:
        np.testing.assert_allclose(r, runs[0], rtol=1e-8, atol=1e-14)


def test_stencil_solutions_are_eigenvector_sums():
    gen = M.generator("stencil27")
    params = {"nx": 5, "ny": 4, "nz": 3}
    a = csr(gen.triplets(params, seed=0))
    # one sign tensor with a single nonzero weight is an eigenvector
    sx, sy, sz = (gen.sine_modes(m) for m in (5, 4, 3))
    v = np.einsum("a,b,c->abc", sz[:, 1], sy[:, 2], sx[:, 0]).ravel()
    lam = 27.0 - np.prod([1 + 2 * np.cos(np.pi * (k + 1) / (m + 1))
                          for k, m in ((1, 3), (2, 4), (0, 5))])
    np.testing.assert_allclose(a @ v, lam * v, atol=1e-12)


def test_kronecker_edge_count_and_skew():
    u, v, n = M.generator("kronecker").edges(KRON, seed=1)
    assert len(u) == len(v) == 16 * 256
    assert u.min() >= 0 and max(u.max(), v.max()) < n
    degree = np.bincount(np.concatenate([u, v]), minlength=n)
    assert degree.max() > 8 * degree.mean()  # scale-free hubs


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (M.BENCH / "configs").glob("*.json")))
def test_config_files_name_their_generator(name):
    cfg = json.loads(M.config_path(name).read_text())
    assert cfg["name"] == name
    gen = M.generator(cfg["generator"])
    assert callable(gen.triplets)
    assert len(cfg["source"]) <= 200
    assert set(cfg["reduced"]) and cfg["dtype"] in ("float32", "float64")
