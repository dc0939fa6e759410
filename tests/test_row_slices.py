"""The XLA scalar tile kernel's row-slice layout (repro.kernels.row_slices).

The kernel cases run on 1, 2 and 4 parts in a hermetic subprocess with 4
forced fake devices (tests/_row_slices_runner.py); the width rule, the
plan's and the registry's report of the layout, and the lowered programs
run inline on one device.
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.api import plan_from_partitioned
from repro.core import distributed as D
from repro.core.partition import PartitionedMatrix, partition_1d, partition_2d
from repro.data.matrices import regular_matrix, scale_free_matrix
from repro.engine import SpmvEngine
from repro.kernels.row_slices import SLICE_WIDTHS, choose_width

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASES = [
    "empty_rows.merged.1d1", "empty_rows.one_slice.2d1", "long_row.merged.1d2",
    "one_slice.1d2", "one_slice.2d4", "capacity_padding.1d4",
    "split_rows.1d4", "merged.2d2", "variable_sized.2d4", "csr.1d2",
    "spmm_b3.1d2", "spmm_b3.2d4", "bf16.1d2", "bf16.spmm_b3.2d4",
]


@pytest.fixture(scope="module")
def kernel_output():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_row_slices_runner.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    if "ROW SLICES SKIP" in proc.stdout:
        pytest.skip("the row-slice mesh cases need 4 (forced) devices")
    if proc.returncode != 0:
        pytest.fail(f"row-slice runner crashed:\n{proc.stderr[-3000:]}")
    return proc.stdout


@pytest.mark.parametrize("case", CASES)
def test_row_slice_tile_kernel(kernel_output, case):
    """Each case matches coo_spmv_ref tile by tile and a float64 dense
    product end to end, in the mode (one slice a row or merged) it names."""
    assert f"CASE {case}: OK" in kernel_output, kernel_output


# ------------------------------------------------------------ the width rule


def stencil27_lengths(n: int = 32) -> np.ndarray:
    """Row lengths of HPCG's 27-point stencil on an n^3 grid."""
    axis = np.full(n, 3)
    axis[[0, -1]] = 2
    return np.einsum("i,j,k->ijk", axis, axis, axis).ravel()


def stencil27_dense(n: int = 8) -> np.ndarray:
    """HPCG's 27-point stencil matrix on an n^3 grid, dense."""
    idx = np.arange(n ** 3)
    pts = np.stack(np.unravel_index(idx, (n, n, n)), axis=1)
    near = np.abs(pts[:, None, :] - pts[None, :, :]).max(axis=2) <= 1
    return np.where(np.eye(n ** 3, dtype=bool), 26.0, -1.0 * near)


def kronecker_lengths(scale: int = 15, edgefactor: int = 16) -> np.ndarray:
    """Row lengths of a symmetrised Graph500 Kronecker graph (A = 0.57,
    B = C = 0.19), self-loops and duplicates dropped."""
    n, m = 1 << scale, edgefactor << scale
    rng = np.random.default_rng(1)
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        u_bit = rng.random(m) > 0.76
        v_bit = rng.random(m) > np.where(u_bit, 0.19 / 0.24, 0.57 / 0.76)
        u += u_bit.astype(np.int64) << bit
        v += v_bit.astype(np.int64) << bit
    keep = u != v
    key = np.unique(np.concatenate([u[keep] * n + v[keep],
                                    v[keep] * n + u[keep]]))
    return np.bincount(key // n, minlength=n)


def _one_part(lengths: np.ndarray) -> PartitionedMatrix:
    """A one-part 1D COO partition with these row lengths."""
    rows = len(lengths)
    rowind = np.repeat(np.arange(rows), lengths).astype(np.int32)
    rng = np.random.default_rng(0)
    colind = rng.integers(0, rows, len(rowind)).astype(np.int32)
    values = rng.standard_normal(len(rowind)).astype(np.float32)
    one = lambda v: jnp.asarray([v], jnp.int32)  # noqa: E731
    return PartitionedMatrix(
        rowind=jnp.asarray(rowind[None]), colind=jnp.asarray(colind[None]),
        values=jnp.asarray(values[None]), nnz=one(len(rowind)),
        row_start=one(0), col_start=one(0), row_extent=one(rows),
        col_extent=one(rows), shape=(rows, rows), grid=(1, 1), fmt="coo",
        scheme="1d.nnz", block=(1, 1), h_pad=rows, w_pad=rows,
    )


def _describes(text: str, rs: dict) -> bool:
    return (f"row slices: w={rs['width']} padded={rs['padded_share']:.2%} "
            f"merged={rs['merged']}/SpMV") in text


@pytest.mark.parametrize("histogram,width,one_slice", [
    ("stencil27", 27, True),
    ("kronecker", 8, False),
])
def test_width_rule(histogram, width, one_slice):
    """The stencil gives one slice a row at its longest row (27) and merges
    nothing; the skewed Kronecker graph gives w = 8, merged.  The compiled
    plan's describe() and the engine's registry entry report the layout."""
    lengths = {"stencil27": stencil27_lengths,
               "kronecker": kronecker_lengths}[histogram]()
    assert choose_width([lengths]) == (width, one_slice)

    slices = len(lengths) if one_slice else int((-(-lengths // width)).sum())
    want = {"width": width,
            "padded_share": 1.0 - lengths.sum() / (slices * width),
            "merged": 0 if one_slice else slices}
    mesh = compat.make_mesh((1,), ("data",))
    exe = plan_from_partitioned(_one_part(lengths), mesh).compile()
    assert exe.plan.row_slices == pytest.approx(want)
    assert _describes(exe.plan.describe(), exe.plan.row_slices)

    # the registry entry of a small matrix of the same kind
    a = (stencil27_dense() if one_slice
         else scale_free_matrix(512, 512, 6000, seed=4))
    eng = SpmvEngine(devices=jax.devices()[:1])
    entry = eng.register("m", a)
    plan = eng.plan_for("m").executor.plan
    assert entry.row_slices == plan.row_slices
    assert (entry.row_slices["merged"] == 0) == one_slice
    assert entry.row_slices["width"] in ((27,) if one_slice else SLICE_WIDTHS)
    assert 0.0 <= entry.row_slices["padded_share"] < 1.0
    assert _describes(plan.describe(), entry.row_slices)


# ---------------------------------------------------------- lowered programs

_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = \S+ ([a-z-]+)\(.*?"
                    r'op_name="([^"]*)"', re.M)
# ops that carry data into or out of the kernel and are no part of it
_NOT_KERNEL = {"parameter", "constant", "bitcast", "copy", "tuple",
               "get-tuple-element", "all-gather", "all-reduce"}


@pytest.mark.parametrize("scheme,one_slice", [
    ("1d", False), ("1d", True), ("2d", False), ("2d", True),
])
def test_tile_program_ops_are_named_for_the_kernel(scheme, one_slice):
    """Every op the compiled 1D / 2D program runs for the tile kernel sits
    under ``spmv.coo.xla`` (what ``kernel.spmv_roofline`` reads); the
    one-slice-a-row program has no scatter, the merged one a single sorted
    scatter."""
    if one_slice:
        a = regular_matrix(64, 96, 6, seed=1)
    else:
        rng = np.random.default_rng(0)
        a = (rng.random((64, 96)) < 0.05) * rng.standard_normal((64, 96))
        a[3] = 1.0  # a row longer than any slice
    a = a.astype(np.float32)
    if scheme == "1d":
        part = partition_1d(a, 1, fmt="coo", balance="nnz")
        mesh = compat.make_mesh((1,), ("data",))
        program = D.spmv_1d(part, mesh, "data")
    else:
        part = partition_2d(a, (1, 1), fmt="coo", scheme="equally-sized")
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        program = D.spmv_2d(part, mesh, ("data", "model"))
    layout = D.slice_arrays(part)
    assert layout.one_slice == one_slice
    arrs = {**D._arrays(part), **layout.arrays}
    if scheme == "2d":
        arrs = {k: np.asarray(v)[None] for k, v in arrs.items()}
    text = program.jitted.lower(arrs, jnp.zeros(96, jnp.float32)) \
        .compile().as_text()
    # the scalar reducers of reduce, scatter and psum are no ops of their own
    bodies = [c for c in text.split("\n\n")
              if not re.search(r"\) -> \w+\[\] \{", c)]
    ops = _INSTR.findall("\n\n".join(bodies))
    kernel = [(op, name) for op, name in ops if op not in _NOT_KERNEL]
    assert any(op == "gather" or "gather" in name for op, name in kernel)
    outside = [(op, name) for op, name in kernel if "spmv.coo.xla" not in name]
    assert not outside, outside
    assert ("scatter" in text) == (not one_slice)
    if not one_slice:
        assert "indices_are_sorted=true" in text


# --------------------------------------------------- the benchmark's check


@pytest.mark.parametrize("cell", ["hpcg32.cg", "kron15.pagerank"])
def test_benchmark_run_with_an_altered_tile_kernel_is_not_correct(
        cell, monkeypatch):
    """A small CPU run of each benchmark cell, its answer altered where the
    row-slice kernel produces it, comes out not correct."""
    from bench.tests.small import run_small
    from repro.kernels import row_slices

    exact = row_slices.row_slice_spmv
    monkeypatch.setattr(row_slices, "row_slice_spmv",
                        lambda *a, **k: exact(*a, **k) * 1.001)
    assert not run_small(cell)["correct"]
