"""Format containers: round-trips, conversions, dtype coverage."""
import numpy as np
import pytest

from repro.core import formats as F

RNG = np.random.default_rng(0)


def rand_sparse(m, n, density=0.1, dtype=np.float32, seed=None):
    rng = np.random.default_rng(seed) if seed is not None else RNG
    mask = rng.random((m, n)) < density
    a = mask * rng.standard_normal((m, n))
    if np.issubdtype(np.dtype(dtype), np.integer):
        a = (a * 10).astype(dtype)
    return a.astype(dtype)


@pytest.mark.parametrize("make", [F.dense_to_csr, F.dense_to_coo])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32, np.int8])
def test_scalar_roundtrip(make, dtype):
    a = rand_sparse(48, 64, 0.15, dtype, seed=1)
    m = make(a)
    # f64 narrows to f32 on device (jax x64 disabled; TPU has no f64 path —
    # DESIGN.md changed-assumption #5): compare at storage precision.
    want = a.astype(np.float32) if dtype == np.float64 else a
    np.testing.assert_array_equal(np.asarray(F.to_dense(m), want.dtype), want)


@pytest.mark.parametrize("make", [F.dense_to_bcsr, F.dense_to_bcoo])
@pytest.mark.parametrize("block", [(4, 4), (8, 16), (8, 128)])
def test_block_roundtrip(make, block):
    a = rand_sparse(block[0] * 8, block[1] * 4, 0.1, seed=2)
    m = make(a, block=block)
    np.testing.assert_allclose(np.asarray(F.to_dense(m)), a, rtol=1e-6)


def test_csr_coo_conversions():
    a = rand_sparse(32, 40, 0.2, seed=3)
    csr = F.dense_to_csr(a)
    coo = F.csr_to_coo(csr)
    np.testing.assert_array_equal(np.asarray(F.to_dense(coo)), a)
    back = F.coo_to_csr(coo)
    np.testing.assert_array_equal(np.asarray(back.rowptr), np.asarray(csr.rowptr))
    np.testing.assert_array_equal(np.asarray(F.to_dense(back)), a)


def test_coo_row_sorted_invariant():
    a = rand_sparse(30, 30, 0.2, seed=4)
    coo = F.dense_to_coo(a)
    ri = np.asarray(coo.rowind)[: int(coo.nnz)]
    assert np.all(np.diff(ri) >= 0), "COO must be row-sorted (paper §3.2)"


def test_capacity_padding():
    a = rand_sparse(16, 16, 0.2, seed=5)
    nnz = int((a != 0).sum())
    coo = F.dense_to_coo(a, capacity=nnz + 37)
    assert coo.capacity == nnz + 37
    assert int(coo.nnz) == nnz
    np.testing.assert_array_equal(np.asarray(F.to_dense(coo)), a)


def test_empty_matrix():
    a = np.zeros((8, 8), np.float32)
    for make in (F.dense_to_csr, F.dense_to_coo):
        m = make(a)
        np.testing.assert_array_equal(np.asarray(F.to_dense(m)), a)
    mb = F.dense_to_bcoo(a, block=(4, 4))
    np.testing.assert_array_equal(np.asarray(F.to_dense(mb)), a)


@pytest.mark.parametrize("shape,block,density,seed", [
    ((48, 64), (8, 16), 0.3, 3), ((96, 256), (8, 128), 0.5, 1),
])
def test_block_matrix_matches_the_kron_formula(shape, block, density, seed):
    """The generator draws block-row by block-row; its values must equal
    the whole-matrix formula it documents, bit for bit."""
    from repro.data.matrices import block_matrix

    rng = np.random.default_rng(seed)
    r, c = block
    mask = rng.random((shape[0] // r, shape[1] // c)) < density
    want = (np.kron(mask, np.ones((r, c))).astype(np.float32)
            * rng.standard_normal(shape).astype(np.float32))
    got = block_matrix(*shape, block=block, block_density=density, seed=seed)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)
