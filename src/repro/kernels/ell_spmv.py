"""Pallas TPU kernel: ELL (padded-row) SpMV/SpMM — beyond-paper TPU format.

SparseP stops at CSR/COO/BCSR/BCOO.  On TPU, the scatter-free layout the VPU
actually wants is ELL: every row padded to K slots (colind/values of shape
(rows, K)).  SpMV becomes a pure gather + lane-wise multiply + row reduction —
no merge step of any kind, so the paper's entire synchronization axis
(§3.4.2) vanishes by construction.  The price is padding FLOPs/bytes, which
is exactly the trade the paper studies for transfer padding (Obs. 10/14);
benchmarks/fig9_single_core.py reports the padding efficiency next to the
kernel time so the trade is visible.

Grid: one step per (tile of T rows, lane tile of the batch).  The x tile
stays VMEM-resident per batch tile; colind and values stream in as (T, K)
blocks and are reused across batch tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .instrument import record_build
from .mode import interpret_mode

__all__ = ["ell_spmv_pallas", "dense_to_ell", "ROW_TILE", "BATCH_TILE"]

ROW_TILE = 64  # rows per grid step (8-sublane aligned)
BATCH_TILE = 128  # SpMM lane tile: RHS columns per grid step


def dense_to_ell(a: np.ndarray, k: int | None = None):
    """Host-side ELL packing: (colind, values, row_nnz), rows padded to K."""
    a = np.asarray(a)
    rows, _ = a.shape
    row_nnz = (a != 0).sum(axis=1).astype(np.int32)
    K = int(k if k is not None else max(1, row_nnz.max(initial=1)))
    colind = np.zeros((rows, K), np.int32)
    values = np.zeros((rows, K), a.dtype)
    for r in range(rows):
        cols = np.nonzero(a[r])[0][:K]
        colind[r, : len(cols)] = cols
        values[r, : len(cols)] = a[r, cols]
    return colind, values, np.minimum(row_nnz, K)


def _acc_dtype(dtype):
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    if dtype in (jnp.int8, jnp.int16):
        return jnp.int32
    return dtype


def _kernel(ci_ref, val_ref, nnz_ref, x_ref, y_ref):
    T, K = ci_ref.shape
    acc = y_ref.dtype
    ci = ci_ref[...]  # (T, K)
    vals = val_ref[...].astype(acc)
    mask = jnp.arange(K, dtype=jnp.int32)[None, :] < nnz_ref[...][:, None]
    xv = jnp.take(x_ref[...], ci.reshape(-1), axis=0, mode="clip").astype(acc)
    xv = xv.reshape(T, K, -1)  # (T, K, B)
    prod = jnp.where(mask[:, :, None], vals[:, :, None] * xv, 0)
    y_ref[...] = prod.sum(axis=1)


def ell_spmv_pallas(
    colind: jax.Array,
    values: jax.Array,
    row_nnz: jax.Array,
    x: jax.Array,
    interpret: bool | None = None,
    row_tile: int = ROW_TILE,
    batch_tile: int | None = None,
) -> jax.Array:
    """y = A @ x with A in ELL form (SpMV or multi-RHS SpMM).

    Args:
      colind/values: (rows, K) padded-row layout from :func:`dense_to_ell`.
      row_nnz: (rows,) real slots per row; the tail is masked.
      x: (cols,) or (cols, B).  B > 1 adds a lane-tiled batch grid axis:
        each grid step computes a (row tile, batch tile) output block.
      interpret: run the kernel body in interpret mode; None decides from
        the default backend (:func:`~repro.kernels.mode.interpret_mode`).
      row_tile: rows per grid step (8-sublane aligned).
      batch_tile: RHS columns per grid step; default ``min(B, BATCH_TILE)``.

    Returns:
      y (rows,) or (rows, B) in the accumulation dtype.
    """
    rows, K = values.shape
    squeeze = x.ndim == 1
    xm = x[:, None] if squeeze else x
    B = xm.shape[1]
    bt = max(1, min(B, BATCH_TILE if batch_tile is None else batch_tile))
    b_pad = -(-B // bt) * bt
    if b_pad != B:
        xm = jnp.pad(xm, ((0, 0), (0, b_pad - B)))
    T = min(row_tile, rows)
    pad_rows = -(-rows // T) * T
    if pad_rows != rows:
        colind = jnp.pad(colind, ((0, pad_rows - rows), (0, 0)))
        values = jnp.pad(values, ((0, pad_rows - rows), (0, 0)))
        row_nnz = jnp.pad(row_nnz, (0, pad_rows - rows))
    acc = _acc_dtype(values.dtype)
    record_build("ell", B)
    if interpret is None:
        interpret = interpret_mode()
    y = pl.pallas_call(
        _kernel,
        grid=(pad_rows // T, b_pad // bt),
        in_specs=[
            pl.BlockSpec((T, K), lambda i, b: (i, 0)),
            pl.BlockSpec((T, K), lambda i, b: (i, 0)),
            pl.BlockSpec((T,), lambda i, b: (i,)),
            pl.BlockSpec((xm.shape[0], bt), lambda i, b: (0, b)),  # x tile
        ],
        out_specs=pl.BlockSpec((T, bt), lambda i, b: (i, b)),
        out_shape=jax.ShapeDtypeStruct((pad_rows, b_pad), acc),
        interpret=interpret,
    )(colind, values, row_nnz, xm)
    y = y[:rows, :B]
    return y[:, 0] if squeeze else y
