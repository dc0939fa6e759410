"""Closed-loop CG sessions to a relative tolerance (``SpmvEngine.solve``).

Each session solves A x = b from x0 = 0 with the program's own ``"cg"``
combine, stopping once CG's recurrence residual is at most
``tol_rel * ||b||`` (checked every ``check_every`` steps) or at
``max_steps``.  b = s A x*, where x* is one of :data:`POOL` solutions that
the configuration's generator draws from the seed (its ``solutions``
function) and s a scale from the seed.  The generator gives every solution
the same weight on each eigenvector of A, so every seed's sessions need the
same number of steps; only the signs, and so the vectors, differ.

The check: every answered session's true residual ||b - A x|| / ||b||, in
float64, worst first.  A session that did not converge is counted failed.
"""
from __future__ import annotations

import numpy as np

from bench.harness import reference
from bench.harness.drivers import NAME, POOL, SolveLoop, rng


class Driver(SolveLoop):

    to_tolerance = True

    def __init__(self, params, data, devices):
        super().__init__(params, data, devices)
        x_star = data.generator.solutions(data.params, rng(data.seed, 1),
                                          POOL)
        self.pool = np.stack([data.matvec(x) for x in x_star]).astype(
            data.dtype)

    def inputs(self, i: int):
        b = self.pool[i % POOL] * self.scales[i]
        return np.zeros_like(b), b

    def call(self, x0, b):
        p = self.params
        tol = float(p["tol_rel"]) * float(np.linalg.norm(b.astype(np.float64)))
        return self.eng.solve(NAME, x0, tol=tol, combine="cg", b=b,
                              max_steps=int(p["max_steps"]),
                              check_every=int(p["check_every"]))

    def check(self, ref: reference.Reference) -> dict:
        idx = self.answered()
        worst = 0.0
        for k in range(0, len(idx), 64):
            block = idx[k:k + 64]
            X = np.stack([self.results[i][0] for i in block], axis=1)
            B = np.stack([self.inputs(i)[1] for i in block], axis=1)
            worst = max(worst, float(reference.rel_residual(ref, X, B).max()))
        return {"residual": worst}
