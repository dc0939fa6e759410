"""Data partitioning techniques of SparseP (paper §3.2–3.3, Figs. 5–8).

Two families, exactly as the paper:

* **1D** — the matrix is horizontally partitioned across PIM cores and the
  whole input vector is copied (broadcast) to each core.  Balancing options
  per format (paper Table 1): rows, nnz at row granularity, nnz at element
  granularity (COO only; rows may split across neighboring cores — at most one
  partial per boundary, merged cheaply), blocks / nnz at block-row granularity
  (BCSR), blocks / nnz at element granularity (BCOO).

* **2D** — the matrix is split into R x C tiles, one per core; only a slice of
  the input vector is copied per core; partial outputs must be merged:
    - ``equally-sized``  : equal tile heights and widths (DCSR/DCOO/...)
    - ``equally-wide``   : equal widths, heights balance nnz per vertical
                           partition (RBD*)
    - ``variable-sized`` : widths balance nnz across vertical partitions, then
                           heights balance nnz within each (BD*)

TPU adaptation (DESIGN.md §2): SPMD requires equal array shapes per device, so
every partition is materialized at a common *capacity* (max tile nnz) with
explicit per-tile ``nnz`` counts and masked tails.  This is the same
"equal transfer size per DRAM bank" constraint as UPMEM, and the padding
efficiency we report per partition is the paper's padding overhead (Obs. 10/14).

All partitioners run host-side on numpy (matrix preprocessing, paper §3.1 notes
matrix load time is amortized) and emit a single pytree, ``PartitionedMatrix``,
with a leading device axis ready for ``jax.device_put`` + ``shard_map``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PartitionedMatrix",
    "partition_1d",
    "partition_1d_coo",
    "partition_2d",
    "BALANCE_1D",
    "SCHEMES_2D",
]

BALANCE_1D = ("rows", "nnz-rgrn", "nnz")  # paper Table 1 (CSR/COO naming)
SCHEMES_2D = ("equally-sized", "equally-wide", "variable-sized")


@dataclass(frozen=True)
class PartitionedMatrix:
    """A sparse matrix partitioned over P = R*C parts, stacked on axis 0.

    Local coordinates: ``rowind``/``colind`` are relative to each part's
    (row_start, col_start).  Values/indices beyond ``nnz[p]`` are padding
    (values zero, indices clamped in-range) — the kernels mask by ``nnz``.

    For block formats, values has shape (P, cap, r, c) and indices are in
    block units (block-row / block-col local indices).
    """

    rowind: jax.Array  # (P, cap) int32, local
    colind: jax.Array  # (P, cap) int32, local
    values: jax.Array  # (P, cap) dtype  |  (P, cap, r, c) for block formats
    nnz: jax.Array  # (P,) int32 — nonzeros (or nonzero blocks) per part
    row_start: jax.Array  # (P,) int32 — global row offset (element units)
    col_start: jax.Array  # (P,) int32 — global col offset (element units)
    row_extent: jax.Array  # (P,) int32 — actual tile height (element units)
    col_extent: jax.Array  # (P,) int32 — actual tile width  (element units)
    shape: Tuple[int, int]  # global matrix shape (static)
    grid: Tuple[int, int]  # (R, C) part grid; 1D => (P, 1) (static)
    fmt: str  # 'csr'|'coo'|'bcsr'|'bcoo' — which local kernel runs (static)
    scheme: str  # partitioning/balancing scheme name (static)
    block: Tuple[int, int]  # (1,1) for scalar formats (static)
    h_pad: int  # padded tile height (max over parts, element units) (static)
    w_pad: int  # padded tile width  (element units) (static)

    @property
    def n_parts(self) -> int:
        return self.grid[0] * self.grid[1]

    @property
    def capacity(self) -> int:
        return self.values.shape[1]

    @property
    def padding_efficiency(self) -> float:
        """Useful fraction of transferred nnz payload (paper Obs. 10/14)."""
        total = float(np.asarray(self.nnz).sum())
        return total / float(self.n_parts * self.capacity)

    @property
    def dtype(self):
        return self.values.dtype


# ---------------------------------------------------------------------------
# balancing primitives (host side)
# ---------------------------------------------------------------------------


def _split_rows_equal(rows: int, parts: int) -> np.ndarray:
    """Equal row ranges: boundaries (parts+1,). CSR.row / COO.row scheme."""
    return np.linspace(0, rows, parts + 1).round().astype(np.int64)


def _split_rows_by_nnz(row_nnz: np.ndarray, parts: int) -> np.ndarray:
    """Row-granular nnz balancing: boundary rows so each part gets ~nnz/parts.

    CSR.nnz / COO.nnz-rgrn scheme (paper Fig. 6 left).  Greedy prefix split on
    the cumulative nnz curve.
    """
    rows = len(row_nnz)
    cum = np.concatenate([[0], np.cumsum(row_nnz, dtype=np.int64)])
    total = cum[-1]
    targets = (np.arange(1, parts, dtype=np.float64) * total / parts)
    cuts = np.searchsorted(cum, targets, side="left")
    bounds = np.concatenate([[0], cuts, [rows]])
    return np.maximum.accumulate(bounds)  # monotone even on empty matrices


def _split_elements(total_nnz: int, parts: int) -> np.ndarray:
    """Element-granular (perfect) nnz split: COO.nnz scheme (rows may split)."""
    return np.linspace(0, total_nnz, parts + 1).round().astype(np.int64)


def _pad_stack(chunks, cap: int, pad_val=0):
    """Stack variable-length 1D/3D chunks into (P, cap, ...) with padding."""
    first = chunks[0]
    out = np.full((len(chunks), cap) + first.shape[1:], pad_val, dtype=first.dtype)
    for p, ch in enumerate(chunks):
        out[p, : len(ch)] = ch
    return out


# ---------------------------------------------------------------------------
# sorted-COO extraction (all formats normalize through this on the host)
# ---------------------------------------------------------------------------


def _as_sorted_coo(a: np.ndarray):
    rowind, colind = np.nonzero(a)
    order = np.lexsort((colind, rowind))
    return rowind[order].astype(np.int64), colind[order].astype(np.int64), a[
        rowind[order], colind[order]
    ]


def _as_sorted_block_coo(a: np.ndarray, block: Tuple[int, int]):
    """(browind, bcolind, bvalues) block-row-sorted, bvalues (nb, r, c)."""
    r, c = block
    rows, cols = a.shape
    assert rows % r == 0 and cols % c == 0, f"{a.shape} % {block} != 0"
    tiles = a.reshape(rows // r, r, cols // c, c).transpose(0, 2, 1, 3)
    mask = np.abs(tiles).sum(axis=(2, 3)) != 0
    bri, bci = np.nonzero(mask)
    return bri.astype(np.int64), bci.astype(np.int64), tiles[bri, bci]


# ---------------------------------------------------------------------------
# 1D partitioning (paper §3.3.1, Figs. 6-7)
# ---------------------------------------------------------------------------


def partition_1d(
    a: np.ndarray,
    parts: int,
    fmt: str = "coo",
    balance: str = "nnz",
    block: Tuple[int, int] = (8, 128),
) -> PartitionedMatrix:
    """1D (horizontal) partitioning across ``parts`` cores.

    balance:
      * ``rows``      — equal rows per part (CSR.row / COO.row)
      * ``nnz-rgrn``  — nnz balanced at row granularity (CSR.nnz / COO.nnz-rgrn);
                        for block formats this is block-row granularity
                        (BCSR.block / BCSR.nnz)
      * ``nnz``       — perfect element/block balance (COO.nnz / BCOO.block /
                        BCOO.nnz); rows may split across parts — the distributed
                        SpMV merges at most one boundary row per neighbor pair
                        (paper §3.3.1).
    """
    if fmt in ("csr", "coo"):
        return partition_1d_coo(*_as_sorted_coo(a), a.shape, parts, fmt=fmt,
                                balance=balance)
    if fmt not in ("bcsr", "bcoo"):
        raise ValueError(f"unknown fmt {fmt!r}")
    return _partition_1d_sorted(*_as_sorted_block_coo(a, block), a.shape,
                                parts, fmt, balance, block)


def partition_1d_coo(
    rowind: np.ndarray,
    colind: np.ndarray,
    values: np.ndarray,
    shape: Tuple[int, int],
    parts: int,
    fmt: str = "coo",
    balance: str = "nnz",
) -> PartitionedMatrix:
    """:func:`partition_1d` of a scalar format from its entries, never dense.

    Args:
      rowind, colind, values: the entries, sorted by row then column, each
        coordinate once; every one is stored, a value of 0 included.
      shape: global (rows, cols).
      parts, fmt, balance: as :func:`partition_1d` (``fmt`` "coo"/"csr").
    """
    if fmt not in ("csr", "coo"):
        raise ValueError(f"partition_1d_coo takes a scalar format; got {fmt!r}")
    return _partition_1d_sorted(np.asarray(rowind, np.int64),
                                np.asarray(colind, np.int64),
                                np.asarray(values), shape, parts, fmt,
                                balance, (1, 1))


def _partition_1d_sorted(ri, ci, vals, shape, parts, fmt, balance, block):
    """The 1D split of row-sorted entries (block units for block formats)."""
    rows, cols = shape
    if fmt in ("bcsr", "bcoo"):
        unit_rows, r_blk = rows // block[0], block[0]
    else:
        unit_rows, r_blk = rows, 1
    nnz_total = len(ri)

    if balance == "rows":
        bounds = _split_rows_equal(unit_rows, parts)
        cuts = np.searchsorted(ri, bounds)
    elif balance == "nnz-rgrn":
        row_nnz = np.bincount(ri, minlength=unit_rows)
        bounds = _split_rows_by_nnz(row_nnz, parts)
        cuts = np.searchsorted(ri, bounds)
    elif balance == "nnz":
        if fmt in ("csr", "bcsr"):
            # Paper: CSR/BCSR are row-sorted; element balancing is *limited to
            # row granularity* (Obs. 7 root cause) — enforce the constraint.
            raise ValueError(f"{fmt} supports only row-granular balancing")
        cuts = _split_elements(nnz_total, parts)
        bounds = None
    else:
        raise ValueError(f"unknown balance {balance!r}")

    chunks_r, chunks_c, chunks_v = [], [], []
    row_start = np.zeros(parts, np.int64)
    row_extent = np.zeros(parts, np.int64)
    nnz = np.zeros(parts, np.int64)
    for p in range(parts):
        lo, hi = int(cuts[p]), int(cuts[p + 1])
        nnz[p] = hi - lo
        if balance == "nnz":
            # part's row range = rows actually touched (may split at edges)
            r0 = int(ri[lo]) if hi > lo else (int(ri[lo - 1]) if lo > 0 else 0)
            r1 = int(ri[hi - 1]) + 1 if hi > lo else r0 + 1
        else:
            r0, r1 = int(bounds[p]), int(bounds[p + 1])
            if r1 == r0:
                r1 = r0 + 1  # keep extents nonzero for SPMD buffers
        row_start[p] = r0
        row_extent[p] = r1 - r0
        chunks_r.append((ri[lo:hi] - r0).astype(np.int32))
        chunks_c.append(ci[lo:hi].astype(np.int32))
        chunks_v.append(vals[lo:hi])
    cap = max(1, int(nnz.max()))

    return PartitionedMatrix(
        rowind=jnp.asarray(_pad_stack(chunks_r, cap)),
        colind=jnp.asarray(_pad_stack(chunks_c, cap)),
        values=jnp.asarray(_pad_stack(chunks_v, cap)),
        nnz=jnp.asarray(nnz.astype(np.int32)),
        row_start=jnp.asarray((row_start * r_blk).astype(np.int32)),
        col_start=jnp.zeros(parts, jnp.int32),
        row_extent=jnp.asarray((row_extent * r_blk).astype(np.int32)),
        col_extent=jnp.full(parts, cols, jnp.int32),
        shape=(rows, cols),
        grid=(parts, 1),
        fmt=fmt,
        scheme=f"1d.{balance}",
        block=block if fmt in ("bcsr", "bcoo") else (1, 1),
        h_pad=int(row_extent.max()) * r_blk,
        w_pad=cols,
    )


# ---------------------------------------------------------------------------
# 2D partitioning (paper §3.3.2, Fig. 8)
# ---------------------------------------------------------------------------


def partition_2d(
    a: np.ndarray,
    grid: Tuple[int, int],
    fmt: str = "coo",
    scheme: str = "equally-sized",
    block: Tuple[int, int] = (8, 128),
) -> PartitionedMatrix:
    """2D tiling into an R x C grid of tiles, one per core.

    * equally-sized  : static equal tile heights/widths (paper Fig. 8a)
    * equally-wide   : equal widths; per-vertical-partition nnz-balanced
                       heights (row granularity for CSR, block-row for BCSR,
                       element-exact for COO/BCOO) (Fig. 8b)
    * variable-sized : nnz-balanced widths (column granularity), then
                       nnz-balanced heights within each vertical partition
                       (Fig. 8c)
    """
    if scheme not in SCHEMES_2D:
        raise ValueError(f"unknown 2D scheme {scheme!r}")
    R, C = grid
    rows, cols = a.shape
    if fmt in ("csr", "coo"):
        ri_all, ci_all, vals_all = _as_sorted_coo(a)
        unit_rows, unit_cols = rows, cols
        r_blk, c_blk = 1, 1
    elif fmt in ("bcsr", "bcoo"):
        ri_all, ci_all, vals_all = _as_sorted_block_coo(a, block)
        unit_rows, unit_cols = rows // block[0], cols // block[1]
        r_blk, c_blk = block
    else:
        raise ValueError(f"unknown fmt {fmt!r}")

    # --- vertical partition (column) boundaries -----------------------------
    if scheme == "variable-sized":
        col_nnz = np.bincount(ci_all, minlength=unit_cols)
        col_bounds = _split_rows_by_nnz(col_nnz, C)
    else:
        col_bounds = _split_rows_equal(unit_cols, C)

    row_granular = fmt in ("csr", "bcsr")  # paper: CSR limited to row granularity
    P = R * C
    chunks_r, chunks_c, chunks_v = [None] * P, [None] * P, [None] * P
    nnz = np.zeros(P, np.int64)
    row_start = np.zeros(P, np.int64)
    col_start = np.zeros(P, np.int64)
    row_extent = np.zeros(P, np.int64)
    col_extent = np.zeros(P, np.int64)

    for c in range(C):
        c0, c1 = int(col_bounds[c]), int(col_bounds[c + 1])
        c1 = max(c1, c0 + 1) if unit_cols else c1
        sel = (ci_all >= c0) & (ci_all < c1)
        ri, ci, vals = ri_all[sel], ci_all[sel], vals_all[sel]
        # rows already sorted within the vertical slice (stable selection)

        # --- horizontal boundaries within this vertical partition ----------
        if scheme == "equally-sized":
            rbounds = _split_rows_equal(unit_rows, R)
            cuts = np.searchsorted(ri, rbounds)
        else:  # equally-wide / variable-sized: balance nnz down the slice
            if row_granular:
                row_nnz = np.bincount(ri, minlength=unit_rows)
                rbounds = _split_rows_by_nnz(row_nnz, R)
                cuts = np.searchsorted(ri, rbounds)
            else:
                cuts = _split_elements(len(ri), R)
                rbounds = None

        for r in range(R):
            p = r * C + c  # row-major part id == mesh (data, model) layout
            lo, hi = int(cuts[r]), int(cuts[r + 1])
            nnz[p] = hi - lo
            if rbounds is not None:
                r0, r1 = int(rbounds[r]), int(rbounds[r + 1])
                if r1 == r0:
                    r1 = min(r0 + 1, unit_rows) or 1
            else:  # element-granular: touched row range
                r0 = int(ri[lo]) if hi > lo else 0
                r1 = int(ri[hi - 1]) + 1 if hi > lo else r0 + 1
            row_start[p], col_start[p] = r0, c0
            row_extent[p], col_extent[p] = r1 - r0, c1 - c0
            chunks_r[p] = (ri[lo:hi] - r0).astype(np.int32)
            chunks_c[p] = (ci[lo:hi] - c0).astype(np.int32)
            chunks_v[p] = vals[lo:hi]

    cap = max(1, int(nnz.max()))
    return PartitionedMatrix(
        rowind=jnp.asarray(_pad_stack(chunks_r, cap)),
        colind=jnp.asarray(_pad_stack(chunks_c, cap)),
        values=jnp.asarray(_pad_stack(chunks_v, cap)),
        nnz=jnp.asarray(nnz.astype(np.int32)),
        row_start=jnp.asarray((row_start * r_blk).astype(np.int32)),
        col_start=jnp.asarray((col_start * c_blk).astype(np.int32)),
        row_extent=jnp.asarray((row_extent * r_blk).astype(np.int32)),
        col_extent=jnp.asarray((col_extent * c_blk).astype(np.int32)),
        shape=(rows, cols),
        grid=grid,
        fmt=fmt,
        scheme=f"2d.{scheme}",
        block=block if fmt in ("bcsr", "bcoo") else (1, 1),
        h_pad=int(row_extent.max()) * r_blk,
        w_pad=int(col_extent.max()) * c_blk,
    )
