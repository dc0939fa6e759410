"""Plan IR (`ExecutionPlan.to_ir` / `plan_from_ir`) — the wire form plans
ship across cluster processes in.

Inline: single-device round-trips per format, JSON stability, tuned
``measured`` metadata riding along, and the error boundary (version
rejection, malformed records, unknown fmt/impl, part-carrying plans,
too-few-devices).  The distributed grid (formats x dtypes x {single, 1D,
2D} x named scheme variants, bit-identical results on a 4-device mesh)
runs in a hermetic subprocess with forced fake devices — same pattern as
tests/test_api.py — and skips cleanly when the forcing doesn't take.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import IR_VERSION, SparseMatrix, plan_from_ir
from repro.data.matrices import block_matrix

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sm():
    return SparseMatrix.from_dense(
        block_matrix(48, 64, block=(8, 16), block_density=0.3, seed=3)
    )


# ---------------------------------------------------- single-device inline


@pytest.mark.parametrize("fmt", ["coo", "csr", "bcoo", "bcsr"])
def test_roundtrip_single_device(fmt):
    sm = _sm()
    p1 = sm.plan(fmt=fmt)
    ir = json.loads(json.dumps(p1.to_ir()))  # a real wire round-trip
    p2 = plan_from_ir(ir, sm)
    assert p2.scheme_id == p1.scheme_id
    assert p2.describe() == p1.describe()
    x = np.random.default_rng(0).standard_normal(sm.shape[1]).astype(np.float32)
    y1 = np.asarray(p1.compile()(x))
    y2 = np.asarray(p2.compile()(x))
    assert np.array_equal(y1, y2)  # bit-identical, not just close


def test_ir_is_json_stable():
    ir = _sm().plan().to_ir()
    assert ir == json.loads(json.dumps(ir))
    assert ir["ir_version"] == IR_VERSION


def test_ir_carries_no_interpret_flag():
    """Interpret mode follows the devices a plan is loaded on, so the IR
    does not carry it, and an IR that does (older writers) still loads."""
    sm = _sm()
    ir = sm.plan(impl="pallas", fmt="bcoo").to_ir()
    assert "interpret" not in ir
    x = np.random.default_rng(1).standard_normal(sm.shape[1]).astype(np.float32)
    got = plan_from_ir({**ir, "interpret": False}, sm).compile()(x)
    np.testing.assert_allclose(got, sm.dense() @ x, rtol=1e-5, atol=1e-5)


def test_measured_metadata_rides_the_ir():
    sm = _sm()
    p = sm.plan()
    # numpy scalars must serialize to plain floats, not smuggle live objects
    p.measured = {"mean_s": np.float32(1.5), "speedup": np.float64(2.0),
                  "candidates": 3}
    ir = json.loads(json.dumps(p.to_ir()))
    assert ir["measured"] == {"mean_s": 1.5, "speedup": 2.0, "candidates": 3}
    p2 = plan_from_ir(ir, sm)
    assert p2.measured == ir["measured"]


def test_estimate_rides_the_ir():
    sm = _sm()
    p = sm.plan()
    ir = json.loads(json.dumps(p.to_ir()))
    assert ir["estimate"] == {k: float(v) for k, v in p.estimate.items()}
    assert plan_from_ir(ir, sm).estimate == ir["estimate"]


# ------------------------------------------------------------ error bounds


def test_v1_payload_still_loads():
    """Pre-topology (v1) records keep loading: the topo key is optional
    and its absence means 'no placement metadata', never an error."""
    sm = _sm()
    ir = sm.plan().to_ir()
    ir["ir_version"] = 1
    ir.pop("topo", None)
    p = plan_from_ir(ir, sm)
    assert p.topo_assignment is None
    assert p.scheme_id == sm.plan().scheme_id


def test_unknown_ir_version_rejected():
    sm = _sm()
    ir = sm.plan().to_ir()
    ir["ir_version"] = IR_VERSION + 99
    with pytest.raises(ValueError, match="version"):
        plan_from_ir(ir, sm)


def test_malformed_ir_rejected():
    sm = _sm()
    ir = sm.plan().to_ir()
    del ir["scheme"]
    with pytest.raises(ValueError, match="malformed"):
        plan_from_ir(ir, sm)


def test_unknown_format_and_impl_rejected():
    sm = _sm()
    ir = sm.plan().to_ir()
    bad_fmt = {**ir, "scheme": {**ir["scheme"], "fmt": "ell"}}
    with pytest.raises(ValueError, match="format"):
        plan_from_ir(bad_fmt, sm)
    with pytest.raises(ValueError, match="impl"):
        plan_from_ir({**ir, "impl": "cuda"}, sm)


def test_part_carrying_plan_rejected():
    sm = _sm()
    p = sm.plan()
    p.part = object()  # stands in for a prebuilt PartitionedMatrix
    with pytest.raises(ValueError, match="part"):
        p.to_ir()


def test_mesh_needs_enough_devices():
    sm = _sm()
    ir = sm.plan().to_ir()
    ir["scheme"]["grid"] = [1024, 1]
    ir["mesh"] = {"shape": [1024], "axes": ["parts"]}
    with pytest.raises(ValueError, match="devices"):
        plan_from_ir(ir, sm)


def test_live_objects_do_not_serialize():
    sm = _sm()
    p = sm.plan()
    p.measured = {"leak": object()}
    with pytest.raises(TypeError, match="serializable"):
        p.to_ir()


# ------------------------------------------- distributed grid (subprocess)


@pytest.fixture(scope="module")
def ir_grid_output():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_ir_runner.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    if "IR SKIP" in proc.stdout:
        pytest.skip("distributed IR tests need 4 (forced) devices")
    if proc.returncode != 0:
        pytest.fail(f"IR runner crashed:\n{proc.stderr[-3000:]}")
    return proc.stdout


def test_ir_grid_all_ok(ir_grid_output):
    assert "IR DONE" in ir_grid_output
    assert "FAIL" not in ir_grid_output


@pytest.mark.parametrize("fmt", ["coo", "csr", "bcoo", "bcsr"])
@pytest.mark.parametrize("scope", ["single", "1d", "2d"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ir_grid_cell(ir_grid_output, fmt, scope, dtype):
    assert f"IR roundtrip {fmt}.{scope}.{dtype}: OK" in ir_grid_output


@pytest.mark.parametrize("scheme", ["1d.rows", "1d.nnz", "2d.equally-sized",
                                    "2d.equally-wide", "2d.variable-sized"])
def test_ir_grid_scheme_variant(ir_grid_output, scheme):
    assert f"IR roundtrip scheme.{scheme}: OK" in ir_grid_output


@pytest.mark.parametrize("fmt", ["coo", "bcoo"])
@pytest.mark.parametrize("cell", ["model_pick", "@rows=host,cols=bank",
                                  "@rows=bank,cols=host"])
def test_ir_grid_topo_assignment(ir_grid_output, fmt, cell):
    """IR v2 rehydrates every axis assignment bit-identically (mesh device
    order included), and the same payload read as v1 still loads."""
    sep = "." if cell == "model_pick" else ""
    assert f"IR roundtrip topo.{fmt}{sep}{cell}: OK" in ir_grid_output
