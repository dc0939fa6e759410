"""The (min, +) semiring: the row-slice kernel, plans, fingerprints and the
``"sssp"`` relaxation loop, against plain references.

The kernel and the one-device cases run inline; the ``1d.nnz`` boundary
merge of a row split across parts, and SSSP on meshes of 2 and 4 devices,
run in a hermetic subprocess with 4 forced fake devices
(tests/_semiring_runner.py).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

from repro import compat
from repro.api import (SparseMatrix, fingerprint_matrix, plan_from_ir,
                       plan_from_partitioned)
from repro.core import semiring as S
from repro.core import distributed as D
from repro.core.partition import partition_1d_coo
from repro.engine import SpmvEngine
from repro.engine.registry import RegisteredMatrix
from repro.kernels import ref as kref
from repro.kernels.row_slices import build_row_slices, row_slice_spmv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [3, 2**33 + 17]


def rows_of(kind, rng, n=64):
    """Row-sorted weighted entries of ``kind``: "short" rows of 0, 2 or 3
    entries (one slice a row), or "skewed" (a 50-entry row: merged)."""
    lens = rng.integers(2, 4, n)
    lens[rng.random(n) < 0.25] = 0  # empty rows
    if kind == "skewed":
        lens[9] = 50
    r = np.repeat(np.arange(n), lens)
    c = np.concatenate([np.sort(rng.choice(n, k, replace=False))
                        for k in lens])
    w = rng.random(len(r)).astype(np.float32)
    w[::7] = 0.0  # stored zeros are edges
    return r, c.astype(np.int64), w


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind,one_slice", [("short", True),
                                            ("skewed", False)])
def test_min_plus_row_slices_match_the_reference(kind, one_slice, seed):
    rng = np.random.default_rng(seed)
    n = 64
    r, c, w = rows_of(kind, rng, n)
    layout = build_row_slices(r[None].astype(np.int32),
                              c[None].astype(np.int32), w[None],
                              np.array([len(r)]), n, semiring="min_plus")
    assert layout.one_slice == one_slice
    vals = layout.arrays["slice_values"][0]
    assert np.isinf(vals).sum() == layout.gathered - len(r)  # pad lanes
    x = rng.random(n).astype(np.float32)
    x[rng.random(n) < 0.3] = np.inf  # columns not reached
    row = layout.arrays.get("slice_row")
    got = np.asarray(row_slice_spmv(
        jnp.asarray(layout.arrays["slice_colind"][0]), jnp.asarray(vals),
        jnp.asarray(x), n, None if row is None else jnp.asarray(row[0]),
        semiring="min_plus"))
    want = np.asarray(kref.coo_min_plus_ref(
        jnp.asarray(r), jnp.asarray(c), jnp.asarray(w), jnp.asarray(x), n))
    np.testing.assert_array_equal(got, want)
    empty = np.bincount(r, minlength=n) == 0
    assert empty.any() and np.all(np.isinf(got[empty]))
    # the plain definition, row by row
    for i in range(n):
        cand = w[r == i] + x[c[r == i]]
        assert got[i] == (cand.min() if len(cand) else np.inf)


def test_semiring_zeros_and_plus_times_padding():
    assert S.lookup("plus_times").zero == 0.0
    assert S.lookup("min_plus").zero == np.inf
    with pytest.raises(ValueError, match="unknown semiring"):
        S.lookup("max_times")
    r, c, w = rows_of("skewed", np.random.default_rng(0))
    layout = build_row_slices(r[None].astype(np.int32),
                              c[None].astype(np.int32), w[None],
                              np.array([len(r)]), 64)
    vals = layout.arrays["slice_values"][0]
    assert np.isfinite(vals).all()


# --------------------------------------------------------- the mesh runner


@pytest.fixture(scope="module")
def mesh_output():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_semiring_runner.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    if "SEMIRING SKIP" in proc.stdout:
        pytest.skip("the (min, +) mesh cases need 4 (forced) devices")
    if proc.returncode != 0:
        pytest.fail(f"semiring runner crashed:\n{proc.stderr[-3000:]}")
    return proc.stdout


@pytest.mark.parametrize("case", ["split_row.1d2", "split_row.1d4",
                                  "engine.sssp.1d2", "engine.sssp.1d4"])
def test_min_plus_on_a_mesh(mesh_output, case):
    """The boundary merge of a split row under (min, +), and SSSP sessions
    on a mesh, against the plain references."""
    assert f"CASE {case}: OK" in mesh_output, mesh_output


# ------------------------------------------------------ plans and identity


def small_graph(seed=0, n=48):
    rng = np.random.default_rng(seed)
    r, c = rng.integers(0, n, 200), rng.integers(0, n, 200)
    keep = r != c
    return r[keep], c[keep], rng.random(int(keep.sum())), n


def triplet_matrix(seed=0, dtype=np.float32):
    r, c, w, n = small_graph(seed)
    return SparseMatrix.from_parts(r, c, w, (n, n), dtype=dtype)


def one_device():
    return jax.devices()[:1]


@pytest.mark.parametrize("kwargs,names", [
    (dict(scheme="2d"), "2d"),
    (dict(scheme="1d", fmt="bcoo"), "bcoo"),
    (dict(impl="pallas"), "pallas"),
    (dict(), "single-device"),
    (dict(scheme="tune"), "tune"),
    (dict(scheme="1d.rows"), None),
])
def test_plans_that_cannot_run_min_plus_are_refused(kwargs, names):
    sm = triplet_matrix()
    pool = {} if names == "single-device" else {"devices": one_device()}
    if names is None:  # a 1D scalar XLA plan on a mesh runs
        ep = sm.plan(semiring="min_plus", **pool, **kwargs)
        assert ep.semiring == "min_plus" and "semiring=min_plus" in \
            ep.describe()
        ir = json.loads(json.dumps(ep.to_ir()))  # the plan IR keeps it
        assert plan_from_ir(ir, sm, devices=one_device()).semiring == \
            "min_plus"
        return
    with pytest.raises(ValueError, match="semiring 'min_plus'.*" + names):
        sm.plan(semiring="min_plus", **pool, **kwargs)


def test_ring_and_integer_plans_are_refused():
    r, c, w, n = small_graph()
    part = partition_1d_coo(np.sort(r), c, w.astype(np.float32), (n, n), 1)
    ring, counts = D.bucket_by_source_shard(part, 1)
    mesh = compat.make_mesh((1,), ("data",))
    ep = plan_from_partitioned(ring, mesh, ring=True, ring_counts=counts)
    with pytest.raises(ValueError, match="ring schedule"):
        dataclasses.replace(ep, semiring="min_plus")
    with pytest.raises(ValueError, match="dtype int32"):
        triplet_matrix(dtype=np.int32).plan(devices=one_device(),
                                            semiring="min_plus")
    with pytest.raises(ValueError, match="unknown semiring"):
        triplet_matrix().plan(devices=one_device(), semiring="max_plus")


def test_auto_plan_under_min_plus_is_1d_scalar():
    # a regular matrix, for which the rules pick a 2D plan under (+, x)
    n = 64
    r = np.repeat(np.arange(n), 3)
    c = (r + np.tile([0, 1, 2], n)) % n
    sm = SparseMatrix.from_parts(r, c, np.ones(len(r)), (n, n),
                                 dtype=np.float32)
    assert sm.plan(devices=one_device()).partitioning == "2d"
    ep = sm.plan(devices=one_device(), semiring="min_plus")
    assert (ep.partitioning, ep.fmt, ep.impl) == ("1d", "coo", "xla")


def test_dense_min_plus_registration_is_refused():
    eng = SpmvEngine(devices=one_device())
    a = np.eye(8, dtype=np.float32)
    with pytest.raises(ValueError, match="which zeros are edges"):
        eng.register("A", a, semiring="min_plus")
    with pytest.raises(ValueError, match="which zeros are edges"):
        SparseMatrix.from_dense(a).fingerprint("min_plus")
    assert "A" not in eng.registry


def test_fingerprints_by_semiring_and_stored_zero():
    r, c, w, n = small_graph()
    sm = SparseMatrix.from_parts(r, c, w, (n, n), dtype=np.float32)
    dense = sm.dense()
    # the default leaves an existing matrix's fingerprint as it was
    assert sm.fingerprint() == fingerprint_matrix(dense) == \
        SparseMatrix.from_dense(dense).fingerprint()
    assert sm.fingerprint("min_plus") != sm.fingerprint()
    zero = SparseMatrix.from_parts(np.r_[r, 0], np.r_[c, n - 1], np.r_[w, 0],
                                   (n, n), dtype=np.float32)
    if dense[0, n - 1] == 0:  # (0, n-1) is new: a stored 0 only
        assert zero.fingerprint() == sm.fingerprint()
    assert zero.fingerprint("min_plus") != sm.fingerprint("min_plus")
    ri, ci, vals = zero.entries()
    assert ((ri == 0) & (ci == n - 1) & (vals == 0)).any()


def test_same_triplets_under_two_semirings_compile_two_plans():
    sm = triplet_matrix()
    eng = SpmvEngine(devices=one_device())
    for name, semiring in (("plus", "plus_times"), ("min", "min_plus")):
        stage(eng, name, sm)
        eng.register(name, None, warmup=False, semiring=semiring)
    plus, mins = eng.registry.get("plus"), eng.registry.get("min")
    assert plus.cache_key != mins.cache_key and len(eng.cache) == 2
    x = np.random.default_rng(1).random(sm.cols).astype(np.float32)
    ri, ci, vals = sm.entries()
    want = np.asarray(kref.coo_min_plus_ref(
        jnp.asarray(ri), jnp.asarray(ci), jnp.asarray(vals), jnp.asarray(x),
        sm.rows))
    np.testing.assert_array_equal(eng.multiply("min", x), want)
    np.testing.assert_allclose(eng.multiply("plus", x), sm.dense() @ x,
                               rtol=1e-5)


# ------------------------------------------------------------- SSSP loops


def stage(eng, name, sm):
    eng.registry.add(RegisteredMatrix(
        name=name, fingerprint="", shape=tuple(sm.shape),
        dtype=sm.dtype.str, stats=None, plan=None, cache_key=None,
        matrix=sm))


def kronecker(seed, scale=7, edgefactor=8):
    """A weighted, undirected Graph500 Kronecker graph (A = 0.57, B = C =
    0.19) with isolated vertices and one edge of weight 0."""
    n, m = 1 << scale, edgefactor << scale
    rng = np.random.default_rng(seed)
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        ub = rng.random(m) > 0.76
        vb = rng.random(m) > np.where(ub, 0.19 / 0.24, 0.57 / 0.76)
        u += ub.astype(np.int64) << bit
        v += vb.astype(np.int64) << bit
    keep = u != v
    lo, hi = np.minimum(u, v)[keep], np.maximum(u, v)[keep]
    key = np.unique(lo * n + hi)
    lo, hi = key // n, key % n
    w = rng.random(len(key))
    w[len(w) // 2] = 0.0
    r, c = np.r_[lo, hi], np.r_[hi, lo]
    return r, c, np.r_[w, w], n


def bellman_ford(r, c, w, n, key):
    """float32 frontier Bellman-Ford: (distances, steps, frontier total)."""
    d = np.full(n, np.inf, np.float32)
    d[key] = 0
    frontier = np.isfinite(d)
    total = steps = 0
    while frontier.any():
        fed = np.where(frontier, d, np.inf).astype(np.float32)
        y = np.full(n, np.inf, np.float32)
        np.minimum.at(y, r, w.astype(np.float32) + fed[c])
        total += int(frontier.sum())
        steps += 1
        new = np.minimum(d, y)
        frontier, d = new < d, new
    return d, steps, total


@pytest.mark.parametrize("seed", SEEDS)
def test_sssp_solve_matches_dijkstra(seed):
    r, c, w, n = kronecker(seed)
    sm = SparseMatrix.from_parts(r, c, w, (n, n), dtype=np.float32)
    eng = SpmvEngine(devices=one_device())
    stage(eng, "G", sm)
    eng.register("G", None, warmup=False, semiring="min_plus")
    a = sp.csr_matrix((w, (r, c)), shape=(n, n))
    deg = np.bincount(r, minlength=n)
    assert (deg == 0).any()  # isolated vertices stay unreached
    zero = r[w == 0][0]
    for key in (zero, int(np.argmax(deg))):
        x0 = np.full(n, np.inf, np.float32)
        x0[key] = 0
        res = eng.solve("G", x0, tol=0.5, combine="sssp", max_steps=128,
                        check_every=4)
        want = dijkstra(a.T.tocsr(), indices=key)
        assert res.converged and res.residual == 0
        np.testing.assert_array_equal(np.isfinite(res.x), np.isfinite(want))
        reached = np.isfinite(want)
        err = np.abs(res.x[reached] - want[reached]).max() / want.max(
            where=reached, initial=0)
        assert err <= 1e-6
        # the frontier counter and the distances of a NumPy Bellman-Ford
        d, steps, total = bellman_ford(r, c, w, n, key)
        np.testing.assert_array_equal(res.x, d)
        assert res.frontier_total == total
        assert res.steps == -(-steps // 4) * 4  # steps past it are no-ops


def test_sssp_counter_in_steps_mode_and_other_combines():
    r, c, w, n = kronecker(5)
    sm = SparseMatrix.from_parts(r, c, w, (n, n), dtype=np.float32)
    exe = sm.plan(devices=one_device(), semiring="min_plus").compile()
    x0 = np.full(n, np.inf, np.float32)
    x0[r[0]] = 0
    res = exe.iterate(x0, steps=40, combine="sssp")
    d, steps, total = bellman_ford(r, c, w, n, r[0])
    assert steps < 40 and res.frontier_total == total
    np.testing.assert_array_equal(res.x, d)
    plus = SparseMatrix.from_parts(r, c, w, (n, n), dtype=np.float32).plan(
        devices=one_device()).compile()
    assert plus.iterate(np.ones(n, np.float32), steps=2).frontier_total is None


def test_sssp_on_a_plus_times_matrix_is_refused():
    sm = triplet_matrix()
    eng = SpmvEngine(devices=one_device())
    stage(eng, "A", sm)
    eng.register("A", None, warmup=False)
    x0 = np.zeros(sm.cols, np.float32)
    with pytest.raises(ValueError, match="runs on a min_plus matrix"):
        eng.solve("A", x0, tol=0.5, combine="sssp")
    eng.register("A", None, warmup=False, semiring="min_plus")
    with pytest.raises(ValueError, match="runs on a plus_times matrix"):
        eng.solve("A", x0, steps=2, combine="plain")
