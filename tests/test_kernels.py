"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Per the deliverable contract: each kernel sweeps shapes and dtypes and is
asserted allclose against the kernels/ref.py oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats as F
from repro.kernels import ops, ref
from repro.kernels.bcsr_spmv import bcoo_spmv_pallas
from repro.kernels.coo_spmv import coo_spmv_pallas, plan_chunks
from repro.kernels.csr_spmv import csr_plan_chunks, csr_spmv_pallas
from repro.kernels.ell_spmv import dense_to_ell, ell_spmv_pallas

RNG = np.random.default_rng(7)

SHAPES = [(16, 32), (64, 96), (130, 70), (256, 512)]
DTYPES = [np.float32, np.int32, np.int8]


def rand_sparse(m, n, density=0.1, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    mask = rng.random((m, n)) < density
    if np.issubdtype(np.dtype(dtype), np.integer):
        a = mask * rng.integers(-4, 5, (m, n))
    else:
        a = mask * rng.standard_normal((m, n))
    return a.astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-4, atol=2e-4) if dtype == np.float32 else dict(rtol=0, atol=0)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_coo_kernel_sweep(shape, dtype):
    m, n = shape
    a = rand_sparse(m, n, 0.08, dtype, seed=m + n)
    x = rand_sparse(1, n, 1.0, dtype, seed=n)[0]
    ri, ci = np.nonzero(a)
    plan = plan_chunks(ri, ci, a[ri, ci], m, chunk=64, span=64)
    got = coo_spmv_pallas(plan, jnp.asarray(x))
    want = ref.coo_spmv_ref(jnp.asarray(ri.astype(np.int32)),
                            jnp.asarray(ci.astype(np.int32)),
                            jnp.asarray(a[ri, ci]), jnp.asarray(x), m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_csr_kernel_sweep(shape, dtype):
    m, n = shape
    a = rand_sparse(m, n, 0.08, dtype, seed=2 * m + n)
    x = rand_sparse(1, n, 1.0, dtype, seed=n + 1)[0]
    csr = F.dense_to_csr(a)
    plan = csr_plan_chunks(np.asarray(csr.rowptr), np.asarray(csr.colind),
                           np.asarray(csr.values), m, chunk=64, span=64)
    got = csr_spmv_pallas(plan, jnp.asarray(x))
    want = a.astype(np.float64) @ x.astype(np.float64)
    np.testing.assert_allclose(np.asarray(got).astype(np.float64), want,
                               **_tol(dtype))


@pytest.mark.parametrize("block", [(4, 8), (8, 16), (8, 128)])
@pytest.mark.parametrize("dtype", [np.float32, np.int8])
@pytest.mark.parametrize("batch", [None, 4])
def test_block_kernel_sweep(block, dtype, batch):
    r, c = block
    m, n = r * 10, c * 6
    a = rand_sparse(m, n, 0.15, dtype, seed=r * c)
    bcoo = F.dense_to_bcoo(a, block=block)
    if batch is None:
        x = rand_sparse(1, n, 1.0, dtype, seed=5)[0]
        want = a.astype(np.float64) @ x.astype(np.float64)
    else:
        x = rand_sparse(n, batch, 1.0, dtype, seed=5)
        want = a.astype(np.float64) @ x.astype(np.float64)
    got = bcoo_spmv_pallas(bcoo.browind, bcoo.bcolind, bcoo.bvalues,
                           jnp.asarray(x), m, bcoo.nblocks)
    np.testing.assert_allclose(np.asarray(got).astype(np.float64), want,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("k_pad", [None, 3, 17])
def test_ell_kernel(k_pad):
    a = rand_sparse(90, 64, 0.1, np.float32, seed=11)
    ci, vv, rn = dense_to_ell(a, k=k_pad)
    rand_x = RNG.standard_normal(64).astype(np.float32)
    got = ell_spmv_pallas(jnp.asarray(ci), jnp.asarray(vv), jnp.asarray(rn),
                          jnp.asarray(rand_x))
    want = ref.ell_spmv_ref(jnp.asarray(ci), jnp.asarray(vv), jnp.asarray(rand_x),
                            jnp.asarray(rn))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_ops_dispatch_all_formats():
    a = rand_sparse(64, 96, 0.1, np.float32, seed=21)
    x = RNG.standard_normal(96).astype(np.float32)
    want = a @ x
    for make in (F.dense_to_csr, F.dense_to_coo,
                 lambda z: F.dense_to_bcsr(z, (8, 16)),
                 lambda z: F.dense_to_bcoo(z, (8, 16))):
        mat = make(a)
        for impl in ("xla", "pallas"):
            got = ops.spmv(mat, jnp.asarray(x), impl=impl)
            np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                                       atol=2e-4, err_msg=f"{type(mat)} {impl}")


SPMM_MAKERS = {
    "coo": F.dense_to_coo,
    "csr": F.dense_to_csr,
    "bcoo": lambda z: F.dense_to_bcoo(z, (8, 16)),
    "bcsr": lambda z: F.dense_to_bcsr(z, (8, 16)),
}
SPMM_TOL = {"float32": dict(rtol=2e-4, atol=2e-4),
            "bfloat16": dict(rtol=5e-2, atol=5e-2)}


@pytest.mark.parametrize("fmt", list(SPMM_MAKERS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batch", [1, 3, 8])
def test_spmm_parity_pallas_xla_dense(fmt, dtype, batch):
    """SpMM acceptance grid: pallas == xla oracle == dense, all formats."""
    a = rand_sparse(64, 96, 0.1, np.float32, seed=41)
    if dtype == "bfloat16":
        a = a.astype(jnp.bfloat16)
    af = np.asarray(a, np.float32)
    X = np.random.default_rng(42).standard_normal((96, batch)).astype(a.dtype)
    Xf = np.asarray(X, np.float32)
    m = SPMM_MAKERS[fmt](np.asarray(a))
    got_p = np.asarray(ops.spmm(m, jnp.asarray(X), impl="pallas"), np.float32)
    got_x = np.asarray(ops.spmm(m, jnp.asarray(X), impl="xla"), np.float32)
    want = af @ Xf
    np.testing.assert_allclose(got_p, want, **SPMM_TOL[dtype])
    np.testing.assert_allclose(got_x, want, **SPMM_TOL[dtype])
    if batch == 1:
        # B=1 must match the SpMV kernel bit-exactly (same grid, same math)
        y = np.asarray(ops.spmv(m, jnp.asarray(X[:, 0]), impl="pallas"))
        np.testing.assert_array_equal(np.asarray(
            ops.spmm(m, jnp.asarray(X), impl="pallas"))[:, 0], y)


@pytest.mark.parametrize("values", ["integer", "float"])
def test_spmm_batch_tiling_is_invariant(values):
    """Lane-tiled batch grids (including ragged B) match the untiled result.

    The batch a served request lands in must not change its answer beyond
    float32 rounding (docs/kernels.md).  The merge matmul may sum in another
    order per tile width, so integer-valued data, whose sums are exact in
    any order, must match bit for bit, and float data to 1e-5 of the scale.
    """
    rng = np.random.default_rng(44)
    if values == "integer":
        a = rand_sparse(70, 90, 0.1, np.int32, seed=43).astype(np.float32)
        X = rng.integers(-4, 5, (90, 6)).astype(np.float32)
    else:
        a = rand_sparse(70, 90, 0.1, np.float32, seed=43)
        X = rng.standard_normal((90, 6)).astype(np.float32)
    ri, ci = np.nonzero(a)
    plan = plan_chunks(ri, ci, a[ri, ci], 70, chunk=64, span=64)
    base = np.asarray(coo_spmv_pallas(plan, jnp.asarray(X)))
    for bt in (1, 2, 4):  # 6 % 4 != 0 exercises the batch-pad path
        tiled = np.asarray(coo_spmv_pallas(plan, jnp.asarray(X), batch_tile=bt))
        if values == "integer":
            np.testing.assert_array_equal(tiled, base)
        else:
            np.testing.assert_allclose(tiled, base, rtol=1e-5,
                                       atol=1e-5 * np.abs(base).max())


def test_interpret_mode_follows_the_platform():
    """Compiled on TPU, interpreted on CPU, refused anywhere else."""
    from types import SimpleNamespace

    from repro.kernels import interpret_mode

    def devices(*platforms):
        return [SimpleNamespace(platform=p) for p in platforms]

    assert interpret_mode(devices("tpu", "tpu", "tpu", "tpu")) is False
    assert interpret_mode(devices("cpu")) is True
    assert interpret_mode() is (jax.default_backend() == "cpu")
    for bad in (devices("gpu"), devices("cpu", "tpu")):
        with pytest.raises(ValueError, match="compiled on TPU"):
            interpret_mode(bad)


def test_ell_spmm_batches():
    a = rand_sparse(90, 64, 0.1, np.float32, seed=45)
    ci, vv, rn = dense_to_ell(a)
    X = np.random.default_rng(46).standard_normal((64, 5)).astype(np.float32)
    got = ell_spmv_pallas(jnp.asarray(ci), jnp.asarray(vv), jnp.asarray(rn),
                          jnp.asarray(X), batch_tile=2)
    want = ref.ell_spmv_ref(jnp.asarray(ci), jnp.asarray(vv), jnp.asarray(X),
                            jnp.asarray(rn))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_spmm_rejects_non_2d():
    m = F.dense_to_coo(rand_sparse(16, 16, 0.2, np.float32, seed=47))
    with pytest.raises(ValueError, match="cols, B"):
        ops.spmm(m, jnp.zeros((16,), jnp.float32))


def test_bf16_accumulates_f32():
    a = rand_sparse(32, 512, 0.5, np.float32, seed=31).astype(jnp.bfloat16)
    x = jnp.asarray(RNG.standard_normal(512), jnp.bfloat16)
    bcoo = F.dense_to_bcoo(np.asarray(a.astype(jnp.float32)), block=(8, 128))
    got = bcoo_spmv_pallas(bcoo.browind, bcoo.bcolind,
                           bcoo.bvalues.astype(jnp.bfloat16), x, 32,
                           bcoo.nblocks)
    assert got.dtype == jnp.float32  # MXU accumulator semantics
    want = np.asarray(a.astype(jnp.float32)) @ np.asarray(x.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-2, atol=2e-2)


def test_dense_row_pathology():
    """Paper Obs. 4: one very dense row — element-granular chunking splits it."""
    a = np.zeros((64, 128), np.float32)
    a[7] = RNG.standard_normal(128)  # one dense row
    a[20, 3] = 1.0
    ri, ci = np.nonzero(a)
    plan = plan_chunks(ri, ci, a[ri, ci], 64, chunk=32, span=64)
    assert plan.rowind.shape[0] >= 4  # the dense row spans multiple chunks
    x = RNG.standard_normal(128).astype(np.float32)
    got = coo_spmv_pallas(plan, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), a @ x, rtol=1e-4, atol=1e-5)
