"""Subprocess body for the row-slice tile kernel tests (4 forced fake devices
must be set before jax initializes).  Invoked by tests/test_row_slices.py;
prints one ``CASE <name>: OK`` (or ``FAIL <why>``) line per case.

Each case partitions a small matrix, builds its row slices
(``distributed.slice_arrays``) and checks

* every part's tile against ``kref.coo_spmv_ref`` on the partition arrays,
* the assembled ``spmv_1d`` / ``spmv_2d`` result against a float64 dense
  product,
* the layout's mode (one slice a row, or merged) against the case's.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import distributed as D
from repro.core.partition import partition_1d, partition_2d
from repro.kernels import ref as kref
from repro.kernels.row_slices import row_slice_spmv

ROWS, COLS = 96, 128


def regular(rng):
    """Five entries a row: every row fits one slice."""
    a = np.zeros((ROWS, COLS))
    for r in range(ROWS):
        a[r, rng.choice(COLS, 5, replace=False)] = rng.standard_normal(5)
    return a


def skewed(rng):
    """Short rows, a third of them empty, and one full row (128 > w)."""
    a = (rng.random((ROWS, COLS)) < 0.04) * rng.standard_normal((ROWS, COLS))
    a[rng.random(ROWS) < 0.33] = 0.0
    a[11] = rng.standard_normal(COLS)
    return a


def short_rows(rng):
    """Rows of 1 to 3 entries and a few empty ones, so the longest row (3)
    gives one slice a row, empty rows included."""
    a = np.zeros((ROWS, COLS))
    for r in range(ROWS):
        if r % 12 == 5:
            continue
        k = 1 + r % 3
        a[r, rng.choice(COLS, k, replace=False)] = rng.standard_normal(k)
    return a


MATRICES = {"regular": regular, "skewed": skewed, "short_rows": short_rows}

# name: matrix, fmt, scheme, grid/parts, batch, dtype, one slice a row
CASES = {
    "empty_rows.merged.1d1": ("skewed", "coo", "1d.nnz", 1, None, "float32", False),
    "empty_rows.one_slice.2d1": ("short_rows", "coo", "2d.equally-sized", (1, 1), None, "float32", True),
    "long_row.merged.1d2": ("skewed", "coo", "1d.nnz-rgrn", 2, None, "float32", False),
    "one_slice.1d2": ("regular", "coo", "1d.rows", 2, None, "float32", True),
    "one_slice.2d4": ("regular", "coo", "2d.equally-sized", (2, 2), None, "float32", True),
    "capacity_padding.1d4": ("skewed", "coo", "1d.rows", 4, None, "float32", False),
    "split_rows.1d4": ("skewed", "coo", "1d.nnz", 4, None, "float32", False),
    "merged.2d2": ("skewed", "coo", "2d.equally-sized", (2, 1), None, "float32", False),
    "variable_sized.2d4": ("skewed", "coo", "2d.variable-sized", (2, 2), None, "float32", False),
    "csr.1d2": ("skewed", "csr", "1d.nnz-rgrn", 2, None, "float32", False),
    "spmm_b3.1d2": ("skewed", "coo", "1d.nnz", 2, 3, "float32", False),
    "spmm_b3.2d4": ("regular", "coo", "2d.equally-sized", (2, 2), 3, "float32", True),
    "bf16.1d2": ("skewed", "coo", "1d.nnz", 2, None, "bfloat16", False),
    "bf16.spmm_b3.2d4": ("regular", "coo", "2d.equally-sized", (2, 2), 3, "bfloat16", True),
}

# A tile against the oracle, element by element: |y - y_ref| <= REL *
# |y_ref| + 1e-5 * max|y_ref|.  float32 sums in another order; bfloat16
# rounds each float32-accumulated output once (2^-9 of it), where a
# bfloat16 accumulation would round at every add.
REL = {"float32": 0.0, "bfloat16": 2.0 ** -8}
# The assembled y against float64, over max|y|: bfloat16 tiles are merged
# across parts in bfloat16, as the merge collectives run in the values dtype
TOL = {"float32": 1e-5, "bfloat16": 2.0 ** -7}


def off(got, want, dtype) -> float:
    """A tile's largest error over its allowance (> 1 fails)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    allow = REL[dtype] * np.abs(want) + 1e-5 * max(1.0, np.abs(want).max())
    return float((np.abs(got - want) / allow).max())


def partition(a, fmt, scheme, grid):
    kind, balance = scheme.split(".", 1)
    if kind == "1d":
        return partition_1d(a, grid, fmt=fmt, balance=balance)
    return partition_2d(a, grid, fmt=fmt, scheme=balance)


def run_case(name, spec, rng):
    mat, fmt, scheme, grid, batch, dtype, one_slice = spec
    dt = jnp.dtype(dtype)
    a = MATRICES[mat](rng).astype(dt)
    shape = (COLS,) if batch is None else (COLS, batch)
    x = rng.standard_normal(shape).astype(dt)
    part = partition(a, fmt, scheme, grid)
    layout = D.slice_arrays(part)
    if layout.one_slice != one_slice:
        return f"one_slice={layout.one_slice}, want {one_slice}"
    if name.startswith("capacity_padding") and int(np.min(part.nnz)) == part.capacity:
        return "no part is padded past its nnz"

    # every tile against the oracle on the partition arrays
    xl = np.zeros((max(part.w_pad, COLS),) + shape[1:], dt)
    xl[:COLS] = x
    xl = jnp.asarray(xl)
    for p in range(part.n_parts):
        sl = {k: jnp.asarray(v[p]) for k, v in layout.arrays.items()}
        got = row_slice_spmv(sl["slice_colind"], sl["slice_values"], xl,
                             part.h_pad, sl.get("slice_row"))
        want = kref.coo_spmv_ref(part.rowind[p], part.colind[p],
                                 part.values[p], xl, part.h_pad,
                                 nnz=part.nnz[p])
        if got.dtype != want.dtype:
            return f"part {p}: dtype {got.dtype}, oracle {want.dtype}"
        if (e := off(got, want, dtype)) > 1:
            return f"part {p}: {e:.3g} times the allowance from coo_spmv_ref"

    # the distributed program against a float64 dense product
    n = part.n_parts
    if scheme.startswith("1d"):
        mesh = compat.make_mesh((n,), ("data",))
        arrs = D.place_1d(part, mesh, "data", extra=layout.arrays)
        xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data")))
        out = D.spmv_1d(part, mesh, "data")(arrs, xs)
    else:
        mesh = compat.make_mesh(grid, ("data", "model"))
        arrs = D.place_2d(part, mesh, ("data", "model"), extra=layout.arrays)
        xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("model")))
        out = D.spmv_2d(part, mesh, ("data", "model"))(arrs, xs)
    got = np.asarray(D.assemble_rows(out), np.float64)
    want = np.asarray(a, np.float64) @ np.asarray(x, np.float64)
    err = np.abs(got - want).max() / np.abs(want).max()
    if err > TOL[dtype]:
        return f"{err:.3g} of max|y| from the float64 product"
    return None


def main():
    print(f"DEVICES {jax.device_count()}")
    if jax.device_count() < 4:
        print("ROW SLICES SKIP")
        return
    for name, spec in CASES.items():
        why = run_case(name, spec, np.random.default_rng(len(name)))
        print(f"CASE {name}: {'OK' if why is None else 'FAIL ' + why}",
              flush=True)
    print("ROW SLICES DONE")


if __name__ == "__main__":
    main()
