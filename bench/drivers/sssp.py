"""Closed-loop Graph500 SSSP sessions (kernel 3) through ``SpmvEngine.solve``.

The matrix is registered under the (min, +) semiring, so one step relaxes
every vertex's distance over its edges: y_i = min_j (A_ij + x_j).  Each
session searches from one of the configuration's search keys (session i
from key i mod the key count, in order): x0 is +inf everywhere and 0 at the
key, and the program's ``"sssp"`` combine runs Bellman-Ford fed only the
frontier until the frontier is empty (``tol`` below 1), checked every
``check_every`` steps, or ``max_steps``.  A session that did not reach the
fixed point is counted failed.  Each session's frontier total (the sum of
the frontier sizes its steps were fed) is kept with its steps.

The check, after the window: for the first answered session of each key,
max|d - d_ref| / max d_ref over the vertices the reference reaches, where
d_ref is SciPy's float64 Dijkstra on the generator's weights (every stored
weight an edge, a 0 included); it reads inf where the sets of reached
vertices differ in any vertex.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse.csgraph import dijkstra

from bench.harness import program, reference
from bench.harness.drivers import NAME, SolveLoop


class Driver(SolveLoop):

    to_tolerance = True

    def __init__(self, params, data, devices):
        super().__init__(params, data, devices)
        self.keys = data.generator.search_keys(data.params, data.rows,
                                               data.values)
        self.frontier = []  # (steps, frontier total) per answered session

    def setup(self, spans: dict, sm) -> None:
        self.eng = program.engine(self.devices)

        def register():
            program.stage(self.eng, NAME, sm)
            return self.eng.register(NAME, None, warmup=False,
                                     semiring="min_plus")

        self.timed(spans, "register_s", register)
        # one session compiles the loop; its answer is not compared
        self.timed(spans, "warm_s", lambda: self.call(*self.inputs(
            self.next_index())))
        self.scales.clear()
        self.frontier.clear()

    def inputs(self, i: int):
        x0 = np.full(self.data.n, np.inf, self.data.dtype)
        x0[self.keys[i % len(self.keys)]] = 0
        return x0, None

    def call(self, x0, b):
        p = self.params
        r = self.eng.solve(NAME, x0, tol=float(p["tol"]), combine="sssp",
                           max_steps=int(p["max_steps"]),
                           check_every=int(p["check_every"]))
        self.frontier.append((r.steps, r.frontier_total))
        return r

    def window(self, seconds: float) -> dict:
        out = super().window(seconds)
        out["frontier"] = self.frontier
        return out

    def check(self, ref: reference.Reference) -> dict:
        first = {}
        for i in self.answered():
            first.setdefault(i % len(self.keys), i)
        if not first:
            return {"sssp_err": 0.0}
        order = sorted(first)
        want = dijkstra(ref.a.T.tocsr(), indices=self.keys[order])
        worst = 0.0
        for k, d_ref in zip(order, want):
            d = np.asarray(self.results[first[k]][0], np.float64)
            reached = np.isfinite(d_ref)
            if np.any(np.isfinite(d) != reached):
                return {"sssp_err": float("inf")}
            scale = max(float(d_ref[reached].max()), np.finfo(float).tiny)
            worst = max(worst, float(
                np.abs(d[reached] - d_ref[reached]).max()) / scale)
        return {"sssp_err": worst}
