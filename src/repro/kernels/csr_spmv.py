"""Pallas TPU kernel path for CSR: row-granular chunking of the windowed kernel.

The paper's key CSR finding (Obs. 7/16) is that CSR differs from COO not in
the inner multiply loop but in *balancing granularity*: CSR is row-sorted, so
work can only be split at row boundaries.  The TPU port makes that literal —
CSR shares the windowed MXU-merge kernel with COO (kernels/coo_spmv.py) and
differs only in the host-side chunk planner, which respects row boundaries
(``row_granular=True``).  A row longer than one chunk still splits (the
paper's "one very dense row" pathology, Obs. 4 — visible here as chunk-count
imbalance, measured in benchmarks/fig9_single_core.py).
"""
from __future__ import annotations

import jax
import numpy as np

from .coo_spmv import CHUNK_E, ROW_SPAN, ChunkPlan, coo_spmv_pallas, plan_chunks

__all__ = ["csr_plan_chunks", "csr_spmv_pallas"]


def _expand_rowptr(rowptr: np.ndarray) -> np.ndarray:
    """rowptr (rows+1,) -> per-element row indices (nnz,)."""
    rowptr = np.asarray(rowptr, np.int64)
    counts = np.diff(rowptr)
    return np.repeat(np.arange(len(counts), dtype=np.int64), counts)


def csr_plan_chunks(
    rowptr: np.ndarray,
    colind: np.ndarray,
    values: np.ndarray,
    out_rows: int | None = None,
    chunk: int = CHUNK_E,
    span: int = ROW_SPAN,
) -> ChunkPlan:
    """Plan row-granular chunks from CSR arrays (host side)."""
    rowind = _expand_rowptr(rowptr)
    nnz = int(rowptr[-1])
    out_rows = out_rows if out_rows is not None else len(rowptr) - 1
    return plan_chunks(
        rowind,
        np.asarray(colind)[:nnz],
        np.asarray(values)[:nnz],
        out_rows,
        chunk=chunk,
        span=span,
        row_granular=True,
    )


def csr_spmv_pallas(plan: ChunkPlan, x: jax.Array, interpret: bool | None = None,
                    batch_tile: int | None = None):
    """CSR SpMV/SpMM — same windowed kernel, row-granular chunk plan.

    x may be (cols,) or (cols, B); multi-RHS batches are lane-tiled exactly
    as in :func:`repro.kernels.coo_spmv.coo_spmv_pallas`.
    """
    return coo_spmv_pallas(plan, x, interpret=interpret, batch_tile=batch_tile)
