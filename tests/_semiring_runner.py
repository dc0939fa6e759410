"""Subprocess body for the (min, +) mesh tests (4 forced fake devices must
be set before jax initializes).  Invoked by tests/test_semiring.py; prints
one ``CASE <name>: OK`` (or ``FAIL <why>``) line per case.

The split-row cases partition a weighted graph whose rows the element
split cuts across parts, run ``spmv_1d`` under ``semiring="min_plus"`` and
compare the assembled y, exactly, with ``kref.coo_min_plus_ref`` on the
whole matrix.  The engine cases register the same kind of graph from
triplets on a mesh of 2 or 4 devices and run ``solve(combine="sssp")``
against SciPy's float64 Dijkstra.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from scipy.sparse.csgraph import dijkstra
import scipy.sparse as sp

from repro import compat
from repro.api import SparseMatrix
from repro.core import distributed as D
from repro.core.partition import partition_1d_coo
from repro.engine import SpmvEngine
from repro.engine.registry import RegisteredMatrix
from repro.kernels import ref as kref

N = 96


def graph(rng, symmetric):
    """Weighted edges: a hub row of 60, a third of the rows empty, one
    weight 0; sorted by row then column, each coordinate once."""
    r = rng.integers(0, N, 300)
    c = rng.integers(0, N, 300)
    r = np.concatenate([r, np.full(60, 7)])
    c = np.concatenate([c, rng.choice(N, 60, replace=False)])
    empty = rng.random(N) < 0.33
    empty[7] = False
    keep = ~empty[r] & (r != c)
    r, c = r[keep], c[keep]
    if symmetric:
        r, c = np.concatenate([r, c]), np.concatenate([c, r])
    key = np.unique(r * N + c)
    r, c = key // N, key % N
    w = rng.random(len(r))
    w[len(w) // 2] = 0.0
    if symmetric:
        w = np.maximum(w, w[np.searchsorted(key, c * N + r)])
    return r, c, w


def split_row_case(parts, rng):
    r, c, w = graph(rng, symmetric=False)
    part = partition_1d_coo(r, c, w.astype(np.float32), (N, N), parts)
    if not D._boundary_meta(part)[0].any():
        return "no row is split across parts"
    layout = D.slice_arrays(part, "min_plus")
    mesh = compat.make_mesh((parts,), ("data",))
    arrs = D.place_1d(part, mesh, "data", extra=layout.arrays)
    x = rng.random(N).astype(np.float32)
    x[rng.random(N) < 0.4] = np.inf  # columns not reached yet
    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data")))
    out = D.spmv_1d(part, mesh, "data", semiring="min_plus")(arrs, xs)
    got = D.assemble_rows(out)
    want = np.asarray(kref.coo_min_plus_ref(
        jnp.asarray(r), jnp.asarray(c), jnp.asarray(w, jnp.float32),
        jnp.asarray(x), N))
    if not np.array_equal(got, want):
        return f"{int((got != want).sum())} rows differ from coo_min_plus_ref"
    if not np.isinf(got).any():
        return "no row reads +inf"
    return None


def engine_case(devices, rng):
    r, c, w = graph(rng, symmetric=True)
    sm = SparseMatrix.from_parts(r, c, w, (N, N), dtype=np.float32)
    eng = SpmvEngine(devices=jax.devices()[:devices])
    eng.registry.add(RegisteredMatrix(
        name="G", fingerprint="", shape=(N, N), dtype="float32", stats=None,
        plan=None, cache_key=None, matrix=sm))
    entry = eng.register("G", None, semiring="min_plus")
    part = eng.plan_for("G").part
    if part.n_parts != devices or not D._boundary_meta(part)[0].any():
        return f"plan {entry.plan.tag} splits no row over {devices} parts"
    a = sp.csr_matrix((w, (r, c)), shape=(N, N))
    for key in (7, int(r[len(r) // 3])):
        x0 = np.full(N, np.inf, np.float32)
        x0[key] = 0
        res = eng.solve("G", x0, tol=0.5, combine="sssp", max_steps=64)
        want = dijkstra(a.T.tocsr(), indices=key)
        if not res.converged:
            return f"key {key}: not converged in {res.steps} steps"
        if not np.array_equal(np.isfinite(res.x), np.isfinite(want)):
            return f"key {key}: the reached sets differ"
        fin = np.isfinite(want)
        err = np.abs(res.x[fin] - want[fin]).max() / want[fin].max()
        if err > 1e-6:
            return f"key {key}: {err:.3g} of the largest distance"
    return None


CASES = {
    "split_row.1d2": lambda rng: split_row_case(2, rng),
    "split_row.1d4": lambda rng: split_row_case(4, rng),
    "engine.sssp.1d2": lambda rng: engine_case(2, rng),
    "engine.sssp.1d4": lambda rng: engine_case(4, rng),
}


def main():
    print(f"DEVICES {jax.device_count()}")
    if jax.device_count() < 4:
        print("SEMIRING SKIP")
        return
    for name, case in CASES.items():
        why = case(np.random.default_rng(len(name)))
        print(f"CASE {name}: {'OK' if why is None else 'FAIL ' + why}",
              flush=True)
    print("SEMIRING DONE")


if __name__ == "__main__":
    main()
