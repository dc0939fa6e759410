"""Closed-loop LDBC Graphalytics PageRank sessions (``SpmvEngine.solve``).

Each session runs a fixed ``steps`` iterations of
x' = (1 - d) / n + d A x + d / n * (mass of x on dangling vertices)
on the column-normalised transition matrix, from a start distribution: one
of :data:`POOL` drawn from the seed, rolled by the session's index.  Columns
of vertices with edges sum to 1, so the dangling mass is what the product
lost, sum(x) - sum(A x); the combine is handed to the program as a callable.

The check: :data:`SAMPLE` sessions drawn from the seed against the same
iterations in float64 on the reference matrix, max|x - x_ref| / max|x_ref|.
"""
from __future__ import annotations

import numpy as np

from bench.harness import reference
from bench.harness.drivers import NAME, POOL, SolveLoop, rng

SAMPLE = 64  # sessions compared after the window, at most


def combine(n: int, damping: float):
    """The Graphalytics step as ``f(x, y) -> x_next`` for the program; the
    program compiles one loop per callable object, so it is built once."""
    import jax.numpy as jnp

    teleport = (1.0 - damping) / n

    def step(x, y):
        lost = jnp.sum(x) - jnp.sum(y)
        return damping * y + (teleport + damping * lost / n)

    return step


def reference_pagerank(ref: reference.Reference, x0, damping: float,
                       steps: int):
    """``steps`` float64 iterations from x0, an (n, k) block of starts."""
    x = np.asarray(x0, np.float64)
    n = ref.shape[0]
    dangling = np.asarray(ref.a.getnnz(axis=0) == 0)
    for _ in range(steps):
        lost = x[dangling].sum(axis=0)
        x = damping * (ref.a @ x) + (1.0 - damping) / n + damping * lost / n
    return x


class Driver(SolveLoop):

    def __init__(self, params, data, devices):
        super().__init__(params, data, devices)
        n = data.n
        p = rng(data.seed, 1).random((POOL, n))
        self.pool = (p / p.sum(axis=1, keepdims=True)).astype(data.dtype)
        self.fn = combine(n, float(params["damping"]))

    def inputs(self, i: int):
        return np.roll(self.pool[i % POOL], i // POOL), None

    def call(self, x0, b):
        return self.eng.solve(NAME, x0, steps=int(self.params["steps"]),
                              combine=self.fn)

    def check(self, ref: reference.Reference) -> dict:
        idx = self.answered()
        pick = np.sort(rng(self.data.seed, 3).permutation(len(idx))[:SAMPLE])
        block = [idx[j] for j in pick]
        X0 = np.stack([self.inputs(i)[0] for i in block], axis=1)
        want = reference_pagerank(ref, X0, float(self.params["damping"]),
                                  int(self.params["steps"]))
        got = np.stack([self.results[i][0] for i in block], axis=1)
        return {"pagerank_err": float(reference.max_rel_err(got, want).max())}
