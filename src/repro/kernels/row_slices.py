"""Row-slice layout and tile kernel for the scalar formats (COO/CSR).

The XLA tile kernel of ``spmv_1d`` / ``spmv_2d`` reduces each row's products
densely instead of scatter-adding one update per nonzero.  Host-side, once
per compiled plan, every part's row-sorted entries are cut into *slices* of
``w`` entries (a row's last slice padded with value 0, column 0) and stored
column-major, ``(w, S)``: the lane axis runs over slices, so summing a slice
is ``w`` lane-dense vector adds.  Two modes, chosen from the rows alone:

* one slice a row (``w`` = the longest row, every row, empty ones included,
  gets exactly one slice): ``S`` is the tile height and the slice sums *are*
  y — no scatter at all;
* merged: rows of any length, ``ceil(len / w)`` slices each; the partial
  sums are merged into y by one sorted segment sum over ``slice_row``
  (``S`` updates, where the scatter-add made one per nonzero).

``choose_width`` picks ``w`` from the row-length histogram of the whole
partition (docs/kernels.md, "COO / CSR on the XLA path: row slices").

The layout and the kernel take a *semiring* (:mod:`repro.core.semiring`):
``"plus_times"`` (y = A x) or ``"min_plus"`` (y_i = min_j (A_ij + x_j), the
relaxation of shortest paths).  Padding holds the semiring's zero: value 0
under (+, x), +inf under (min, +), so a pad lane, a padding slice or a row
with no slice reads that zero.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semiring import lookup

from .ref import _acc_dtype

__all__ = [
    "SLICE_WIDTHS",
    "ONE_SLICE_MAX",
    "MERGE_COST",
    "SliceLayout",
    "choose_width",
    "build_row_slices",
    "row_slice_spmv",
]

SLICE_WIDTHS = (4, 8, 16, 32)
# the longest row is a candidate width (one slice a row) up to this length
ONE_SLICE_MAX = 64
# cost of merging one slice's partial sum over the cost of gathering one
# entry, on a TPU v5e: the scatter-add's 9.0 ns a nonzero over the gather's
# 6.8 ns an entry in the XLA COO kernel (device trace of the hpcg-27pt-32
# and graph500-kron-s15 benchmark cells).  The widths picked for those two
# matrices are the fastest measured there, and stay the picks for any value
# from 0.6 to 2.8 (PERF.md, "Row slices: measure first").
MERGE_COST = 1.3

@dataclass(frozen=True)
class SliceLayout:
    """One partition's row slices: the placed arrays and their counts."""

    arrays: dict  # slice_colind, slice_values (P, w, S)[, slice_row (P, S)]
    width: int  # w, entries a slice
    slices: int  # S, slices a part (equal across parts)
    parts: int
    nnz: int  # real entries over all parts

    @property
    def one_slice(self) -> bool:
        """Every row is one slice: the slice sums are y, nothing merges."""
        return "slice_row" not in self.arrays

    @property
    def gathered(self) -> int:
        """Entries one SpMV gathers, padding included."""
        return self.parts * self.slices * self.width

    @property
    def padded_share(self) -> float:
        return 1.0 - self.nnz / self.gathered if self.gathered else 0.0

    @property
    def merged(self) -> int:
        """Partial sums merged into y per SpMV (0 with one slice a row)."""
        return 0 if self.one_slice else self.parts * self.slices

    def summary(self) -> dict:
        return {"width": self.width, "padded_share": self.padded_share,
                "merged": self.merged}


def _slice_counts(row_lengths: Sequence[np.ndarray], w: int) -> int:
    """Slices a part needs at width ``w`` (the most any part needs)."""
    return max(int((-(-lens // w)).sum()) for lens in row_lengths)


def choose_width(row_lengths: Sequence[np.ndarray]) -> tuple[int, bool]:
    """Pick the slice width from every part's row lengths.

    Candidates are :data:`SLICE_WIDTHS` plus the longest row when it is at
    most :data:`ONE_SLICE_MAX` long; that candidate gives one slice a row.
    The pick has the least ``gathered + MERGE_COST * merged``, each counted
    as the kernel runs them (``S`` equal across parts).

    Args:
      row_lengths: one array per part, the entries of each tile row (its
        length is the padded tile height).

    Returns:
      ``(w, one_slice)``.
    """
    parts = len(row_lengths)
    h_pad = len(row_lengths[0])
    longest = max(1, max(int(lens.max(initial=0)) for lens in row_lengths))
    best = None
    for w in sorted(set(SLICE_WIDTHS) | ({longest} if longest <= ONE_SLICE_MAX
                                         else set())):
        if w == longest:
            cost = parts * h_pad * w
        else:
            s = max(1, _slice_counts(row_lengths, w))
            cost = parts * s * (w + MERGE_COST)
        if best is None or cost < best[0]:
            best = (cost, w, w == longest)
    return best[1], best[2]


def build_row_slices(rowind: np.ndarray, colind: np.ndarray,
                     values: np.ndarray, nnz: np.ndarray,
                     h_pad: int, semiring: str = "plus_times") -> SliceLayout:
    """Lay a partition's entries out as row slices (host side, O(nnz)).

    Args:
      rowind, colind, values: (P, capacity) local tile coordinates and
        values, the first ``nnz[p]`` entries of part p real.
      nnz: (P,) real entries a part.
      h_pad: the padded tile height (rows of y a part).
      semiring: whose zero pads lanes and slices.

    Returns:
      The :class:`SliceLayout`.  Parts are padded to one ``S`` with
      padding slices on the last tile row, so ``slice_row`` stays sorted.
    """
    parts = rowind.shape[0]
    entries = []
    for p in range(parts):
        n = int(nnz[p])
        order = np.argsort(rowind[p, :n], kind="stable")
        r = rowind[p, :n][order].astype(np.int64)
        entries.append((r, colind[p, :n][order], values[p, :n][order]))
    lengths = [np.bincount(r, minlength=h_pad) for r, _, _ in entries]
    w, one_slice = choose_width(lengths)
    S = h_pad if one_slice else max(1, _slice_counts(lengths, w))
    ci = np.zeros((parts, w, S), np.int32)
    vv = np.full((parts, w, S), lookup(semiring).zero, values.dtype)
    srow = np.full((parts, S), h_pad - 1, np.int32)
    for p, ((r, c, v), lens) in enumerate(zip(entries, lengths)):
        pos = np.arange(len(r)) - (np.cumsum(lens) - lens)[r]
        if one_slice:
            slot, lane = r, pos
        else:
            per = -(-lens // w)
            slot = (np.cumsum(per) - per)[r] + pos // w
            lane = pos % w
            srow[p, :per.sum()] = np.repeat(np.arange(h_pad), per)
        ci[p, lane, slot] = c
        vv[p, lane, slot] = v
    arrays = {"slice_colind": ci, "slice_values": vv}
    if not one_slice:
        arrays["slice_row"] = srow
    return SliceLayout(arrays=arrays, width=w, slices=S, parts=parts,
                       nnz=int(np.sum(nnz)))


def row_slice_spmv(colind: jax.Array, values: jax.Array, x: jax.Array,
                   out_rows: int, row: Optional[jax.Array] = None,
                   semiring: str = "plus_times") -> jax.Array:
    """y = A x (or A X) for one tile in row slices, in ``semiring``.

    Args:
      colind, values: (w, S) slice entries (padding: the semiring's zero,
        column 0).
      x: (n,) vector or (n, B) batch.
      out_rows: rows of y (the tile height).
      row: (S,) sorted tile row of each slice; None when every row is one
        slice (then ``S == out_rows``).
      semiring: ``"plus_times"`` (products summed) or ``"min_plus"``
        (``values + x[col]``, the least taken; a row with no slice reads
        +inf).  Static.

    Returns:
      y (out_rows[, B]) in the values dtype, accumulated in
      ``_acc_dtype(values.dtype)``, as :func:`~repro.kernels.ref.coo_spmv_ref`
      (or :func:`~repro.kernels.ref.coo_min_plus_ref`).
    """
    acc = _acc_dtype(values.dtype)
    sr = lookup(semiring)
    xv = jnp.take(x, colind, axis=0, mode="clip").astype(acc)  # (w, S[, B])
    v = values.astype(acc)[(...,) + (None,) * (x.ndim - 1)]
    partial = sr.reduce(sr.times(v, xv), axis=0)  # (S[, B])
    y = partial if row is None else sr.segment(
        partial, row, num_segments=out_rows, indices_are_sorted=True)
    return y.astype(values.dtype) if values.dtype != acc else y
