"""Pure-jnp oracles for every SparseP kernel.

Each Pallas kernel in this package is validated (tests/test_kernels_*.py)
against the functions here across shape/dtype sweeps.  The oracles are also
the *production XLA path* used inside ``shard_map`` on backends without the
Pallas TPU kernels (and for the CPU dry-run lowering): they are pure
``jax.lax``/``jnp`` and lower everywhere.

Conventions (shared with core/partition.py):
  * index arrays may be padded past ``nnz``; contributions at k >= nnz are
    masked to zero,
  * ``x`` may be a vector (n,) or a batch (n, B) — SpMV or SpMM,
  * output length/height is passed statically (local tile height).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "coo_spmv_ref",
    "coo_min_plus_ref",
    "csr_spmv_ref",
    "bcsr_spmv_ref",
    "bcoo_spmv_ref",
    "ell_spmv_ref",
]


def _acc_dtype(dtype):
    """Accumulation dtype: f32 for low-precision floats, i32 for small ints.

    Mirrors the paper's observation that the DPU multiplies in a wider unit
    (8x8->16 multiplier with 32-bit accumulate); on TPU the MXU accumulates
    bf16 products in f32.
    """
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    if dtype in (jnp.int8, jnp.int16):
        return jnp.int32
    return dtype


def coo_spmv_ref(
    rowind: jax.Array,
    colind: jax.Array,
    values: jax.Array,
    x: jax.Array,
    out_rows: int,
    nnz: jax.Array | int | None = None,
) -> jax.Array:
    """COO SpMV/SpMM: y[r] = sum_k values[k] * x[colind[k]] for rowind[k]==r.

    The scatter-add is XLA's native lock-free merge — the TPU analogue of the
    paper's ``lf`` synchronization scheme (DESIGN.md §2).
    """
    cap = values.shape[0]
    valid = jnp.ones((cap,), jnp.bool_) if nnz is None else jnp.arange(cap) < nnz
    acc = _acc_dtype(values.dtype)
    xv = jnp.take(x, colind, axis=0, mode="clip").astype(acc)
    prod = values.astype(acc)[(...,) + (None,) * (x.ndim - 1)] * xv
    prod = jnp.where(valid[(...,) + (None,) * (x.ndim - 1)], prod, 0)
    y = jnp.zeros((out_rows,) + x.shape[1:], acc)
    y = y.at[rowind].add(prod, mode="drop")
    return y.astype(values.dtype) if values.dtype != acc else y


def coo_min_plus_ref(
    rowind: jax.Array,
    colind: jax.Array,
    values: jax.Array,
    x: jax.Array,
    out_rows: int,
) -> jax.Array:
    """(min, +) product over COO triplets: y[r] = min_k values[k] + x[colind[k]]
    for rowind[k] == r, +inf for a row with no entry.

    The plain reference of the semiring the row-slice kernel runs
    (``row_slice_spmv(..., semiring="min_plus")``): every triplet is an
    entry (a stored 0 is an edge of weight 0), no slices, no mesh.
    """
    acc = _acc_dtype(values.dtype)
    cand = values.astype(acc) + jnp.take(x, colind, axis=0).astype(acc)
    y = jnp.full((out_rows,), jnp.inf, acc).at[rowind].min(cand)
    return y.astype(values.dtype) if values.dtype != acc else y


def csr_spmv_ref(
    rowptr: jax.Array,
    colind: jax.Array,
    values: jax.Array,
    x: jax.Array,
    out_rows: int | None = None,
) -> jax.Array:
    """CSR SpMV/SpMM via rowptr expansion (row-sorted gather + segment add)."""
    out_rows = out_rows if out_rows is not None else rowptr.shape[0] - 1
    cap = values.shape[0]
    k = jnp.arange(cap, dtype=jnp.int32)
    rowind = jnp.searchsorted(rowptr, k, side="right").astype(jnp.int32) - 1
    rowind = jnp.clip(rowind, 0, out_rows - 1)
    return coo_spmv_ref(rowind, colind, values, x, out_rows, nnz=rowptr[-1])


def bcoo_spmv_ref(
    browind: jax.Array,
    bcolind: jax.Array,
    bvalues: jax.Array,
    x: jax.Array,
    out_rows: int,
    nblocks: jax.Array | int | None = None,
) -> jax.Array:
    """BCOO SpMV/SpMM: dense (r, c) blocks hit the MXU; block scatter merges.

    y[browind[k]*r : +r] += bvalues[k] @ x[bcolind[k]*c : +c]
    """
    nb_cap, r, c = bvalues.shape
    valid = (
        jnp.ones((nb_cap,), jnp.bool_)
        if nblocks is None
        else jnp.arange(nb_cap) < nblocks
    )
    acc = _acc_dtype(bvalues.dtype)
    xb = x.reshape((x.shape[0] // c, c) + x.shape[1:])  # (bc, c, ...)
    xg = jnp.take(xb, bcolind, axis=0, mode="clip").astype(acc)  # (nb, c, ...)
    # per-block product on the MXU: (nb, r, c) x (nb, c, ...) -> (nb, r, ...)
    # HIGHEST: at default precision the TPU multiplies f32 in one bf16 pass
    prod = jnp.einsum("krc,kc...->kr...", bvalues.astype(acc), xg,
                      precision=jax.lax.Precision.HIGHEST)
    prod = jnp.where(valid[(...,) + (None,) * (prod.ndim - 1)], prod, 0)
    yb = jnp.zeros((out_rows // r, r) + x.shape[1:], acc)
    yb = yb.at[browind].add(prod, mode="drop")
    y = yb.reshape((out_rows,) + x.shape[1:])
    return y.astype(bvalues.dtype) if bvalues.dtype != acc else y


def bcsr_spmv_ref(
    browptr: jax.Array,
    bcolind: jax.Array,
    bvalues: jax.Array,
    x: jax.Array,
    out_rows: int | None = None,
) -> jax.Array:
    """BCSR SpMV/SpMM via browptr expansion to block rows."""
    r = bvalues.shape[1]
    out_rows = out_rows if out_rows is not None else (browptr.shape[0] - 1) * r
    nb_cap = bvalues.shape[0]
    k = jnp.arange(nb_cap, dtype=jnp.int32)
    browind = jnp.searchsorted(browptr, k, side="right").astype(jnp.int32) - 1
    browind = jnp.clip(browind, 0, out_rows // r - 1)
    return bcoo_spmv_ref(browind, bcolind, bvalues, x, out_rows, nblocks=browptr[-1])


def ell_spmv_ref(
    colind: jax.Array,
    values: jax.Array,
    x: jax.Array,
    row_nnz: jax.Array | None = None,
) -> jax.Array:
    """ELL (padded-row) SpMV/SpMM — the beyond-paper TPU-native format.

    colind/values: (rows, K); contributions at k >= row_nnz[r] are masked.
    No scatter at all: pure gather + reduce — the most VPU-friendly layout.
    """
    rows, K = values.shape
    acc = _acc_dtype(values.dtype)
    xv = jnp.take(x, colind.reshape(-1), axis=0, mode="clip").astype(acc)
    xv = xv.reshape((rows, K) + x.shape[1:])
    prod = values.astype(acc)[(...,) + (None,) * (x.ndim - 1)] * xv
    if row_nnz is not None:
        mask = jnp.arange(K)[None, :] < row_nnz[:, None]
        prod = jnp.where(mask[(...,) + (None,) * (x.ndim - 1)], prod, 0)
    y = prod.sum(axis=1)
    return y.astype(values.dtype) if values.dtype != acc else y
