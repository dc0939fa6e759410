"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole small run on the CPU (everything but the look for a
chip) with one fault planted in the program: a solver step that returns its
state unchanged, a tolerance solve cut short, and an answer altered where
the kernel produces it.  Each fault is planted in every place that the
timed path can reach it: every built-in combine, and every tile kernel of
a scalar-format plan.  The cells batch no requests, so no batch can lose
half its members, and they run on one chip, so there is no exchange between
chips to leave out.
"""
import importlib

import pytest

from bench.harness import manifest as M
from bench.harness.drivers import SolveLoop

from .small import run_small

BENCH = M.manifest()
CELLS = [w["name"] for w in BENCH["workloads"]]
SOLVE = [c for c in CELLS
         if issubclass(M.driver(M.resolve(c).traffic["driver"]), SolveLoop)]

# Every tile kernel that a COO or CSR plan can run on a mesh of one chip or
# more, as "module.function"; a new tile kernel is added here.
TILE_KERNELS = ("repro.kernels.row_slices.row_slice_spmv",
                "repro.kernels.ref.coo_spmv_ref")


@pytest.mark.parametrize("cell", SOLVE)
def test_step_that_returns_its_state_unchanged(cell, monkeypatch):
    from repro.api import iterate

    for cls in (*iterate.COMBINES.values(), iterate.CallableCombine):
        monkeypatch.setattr(cls, "step",
                            lambda self, carry, y, params: carry)
    assert not run_small(cell)["correct"]


@pytest.mark.parametrize("cell", [c for c in SOLVE if M.resolve(
    c).traffic.get("tol_rel")])
def test_tolerance_solve_cut_short(cell, monkeypatch):
    from repro.engine import SpmvEngine

    solve = SpmvEngine.solve

    def short(self, *args, **kwargs):
        if kwargs.get("tol") is not None:
            kwargs["max_steps"] = 2
        return solve(self, *args, **kwargs)

    monkeypatch.setattr(SpmvEngine, "solve", short)
    res = run_small(cell)
    assert not res["correct"]
    assert res["checks"]["failed"]["value"] == res["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_it_is_produced(cell, monkeypatch):
    def altered(exact):
        return lambda *args, **kwargs: exact(*args, **kwargs) * 1.001

    for target in TILE_KERNELS:
        module_name, _, name = target.rpartition(".")
        try:
            module = importlib.import_module(module_name)
        except ModuleNotFoundError:
            continue  # a tree from before this kernel
        exact = getattr(module, name, None)
        if exact is not None:
            monkeypatch.setattr(module, name, altered(exact))
    assert not run_small(cell)["correct"]
