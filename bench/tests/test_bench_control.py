"""The control: the program in bfloat16, the precision below the float32
the configurations state, must come out not correct; float32 must pass."""
import pytest

from bench.harness import manifest as M

from .small import run_small

CELLS = [w["name"] for w in M.manifest()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_program_at_its_stated_precision_is_correct(cell):
    res = run_small(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(cell):
    res = run_small(cell, dtype="bfloat16")
    assert not res["correct"]
    worst = max(c["value"] / c["limit"] for c in res["checks"].values()
                if c["limit"] > 0)
    assert worst > 3.0, res["checks"]
