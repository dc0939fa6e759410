"""Pallas TPU kernel: element-granular COO SpMV/SpMM via windowed MXU merge.

TPU adaptation of SparseP's COO kernels with the lock-free (``lf``)
synchronization scheme (paper §3.4.2, Obs. 6).  On UPMEM, ``lf`` has each
tasklet accumulate partial results for its nnz range in WRAM and one thread
merge them.  On TPU there are no mutexes to choose from — the TPU-native
lock-free merge is a **one-hot matmul on the MXU**:

  * host side: the row-sorted nnz stream is cut into *chunks* of at most E
    elements, each chunk confined to one output *window* of SPAN rows
    (window w covers rows [w*SPAN, (w+1)*SPAN)).  Chunk -> window ids are
    scalar-prefetched; consecutive chunks of one window revisit its output
    block and accumulate (zero-init on first visit, like the block kernel);
  * kernel step: gather x[colind] for the chunk (VMEM gather), multiply by
    values, then merge with ``one_hot(rel_row, SPAN).T @ products`` —
    an (SPAN, E) x (E, B) MXU issue.  The segment reduction that UPMEM does
    with WRAM scratch + a merge thread runs on the systolic array instead;
  * the x tile is kept VMEM-resident (local tile widths from the 1D/2D
    partitioners are VMEM-sized — the WRAM analogue).

Element-granular chunking gives the perfect nnz balance of ``COO.nnz``
(paper Obs. 5); the row-granular variant used for CSR semantics only moves
the host-side chunk boundaries (kernels/csr_spmv.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .instrument import record_build
from .mode import interpret_mode

__all__ = ["coo_spmv_pallas", "plan_chunks", "stack_chunk_plans", "ChunkPlan",
           "CHUNK_E", "ROW_SPAN", "BATCH_TILE"]

CHUNK_E = 512  # nnz per grid step (paper: 256-byte WRAM fetches; here VMEM-sized)
ROW_SPAN = 512  # output window height (multiple of 8 sublanes)
BATCH_TILE = 128  # SpMM lane tile: RHS columns per grid step (one lane row)


def _acc_dtype(dtype):
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    if dtype in (jnp.int8, jnp.int16):
        return jnp.int32
    return dtype


@dataclass(frozen=True)
class ChunkPlan:
    """Host-side chunking of a row-sorted COO stream (static per matrix).

    Array fields are normally concrete ``np.ndarray`` (built host-side by
    :func:`plan_chunks`) but may be traced ``jax.Array`` with the same static
    shapes — that is how the distributed layer runs this kernel inside
    ``shard_map``: per-shard plans are stacked host-side
    (:func:`stack_chunk_plans`), placed with the matrix, and re-wrapped as a
    ChunkPlan per local shard.  Only the *shapes* and the three ints are
    static to the kernel.
    """

    rowind: np.ndarray  # (n_chunks, E) int32 — rows, relative to window start
    colind: np.ndarray  # (n_chunks, E) int32
    values: np.ndarray  # (n_chunks, E)
    window: np.ndarray  # (n_chunks,)  int32 — output window id per chunk
    count: np.ndarray  # (n_chunks,)  int32 — real elements per chunk
    n_windows: int
    out_rows: int
    span: int = ROW_SPAN  # window height the plan was built with


def plan_chunks(
    rowind: np.ndarray,
    colind: np.ndarray,
    values: np.ndarray,
    out_rows: int,
    chunk: int = CHUNK_E,
    span: int = ROW_SPAN,
    row_granular: bool = False,
) -> ChunkPlan:
    """Cut a row-sorted COO stream into window-confined chunks.

    row_granular=True keeps whole rows inside one chunk where possible
    (CSR.row / *.nnz-rgrn semantics); False splits anywhere (COO.nnz perfect
    balance).  Rows longer than ``chunk`` split regardless (a row longer than
    a chunk is the paper's "one very dense row" case, Obs. 4).
    """
    rowind = np.asarray(rowind, np.int64)
    colind = np.asarray(colind, np.int64)
    values = np.asarray(values)
    nnz = len(rowind)
    n_windows = max(1, -(-out_rows // span))

    # chunk boundaries: never cross a window boundary; at most `chunk` long.
    bounds = [0]
    while bounds[-1] < nnz:
        lo = bounds[-1]
        w = rowind[lo] // span
        # furthest element still inside window w
        hi_win = int(np.searchsorted(rowind, (w + 1) * span, side="left"))
        hi = min(lo + chunk, hi_win)
        if row_granular and hi < hi_win:
            # retreat to a row boundary (keep rows whole) unless that empties
            # the chunk (row longer than `chunk`)
            r_hi = rowind[hi]
            back = int(np.searchsorted(rowind, r_hi, side="left"))
            if back > lo:
                hi = back
        bounds.append(hi)
    bounds = np.asarray(bounds, np.int64)
    n_chunks = len(bounds) - 1

    ri = np.zeros((n_chunks, chunk), np.int32)
    ci = np.zeros((n_chunks, chunk), np.int32)
    vv = np.zeros((n_chunks, chunk), values.dtype)
    win = np.zeros(n_chunks, np.int32)
    cnt = np.zeros(n_chunks, np.int32)
    for j in range(n_chunks):
        lo, hi = int(bounds[j]), int(bounds[j + 1])
        w = int(rowind[lo] // span) if hi > lo else 0
        win[j] = w
        cnt[j] = hi - lo
        ri[j, : hi - lo] = rowind[lo:hi] - w * span  # window-relative
        ci[j, : hi - lo] = colind[lo:hi]
        vv[j, : hi - lo] = values[lo:hi]
    # Keep window ids non-decreasing even for empty plans.
    return ChunkPlan(ri, ci, vv, win, cnt, n_windows, out_rows, span)


def _kernel(win_ref, cnt_ref, ri_ref, ci_ref, val_ref, x_ref, y_ref):
    """One grid step = one chunk of <=E elements in one SPAN-row window.

    Grid is (batch tiles, chunks): the chunk axis is innermost so all chunks
    of a window are visited consecutively per batch tile (the accumulate-in-
    VMEM invariant); each batch tile revisits the chunk stream against its
    own lane slice of x/y.
    """
    j = pl.program_id(1)
    first = (j == 0) | (win_ref[j] != win_ref[jnp.maximum(j - 1, 0)])

    @pl.when(first)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    E = ri_ref.shape[-1]
    acc = y_ref.dtype
    rel = ri_ref[0]  # (E,) window-relative rows
    cix = ci_ref[0]  # (E,)
    vals = val_ref[0].astype(acc)  # (E,)
    mask = jnp.arange(E, dtype=jnp.int32) < cnt_ref[j]

    xv = jnp.take(x_ref[...], cix, axis=0, mode="clip").astype(acc)  # (E, B)
    prod = jnp.where(mask[:, None], vals[:, None] * xv, 0)  # (E, B)
    span = y_ref.shape[0]
    # Lock-free merge on the MXU: scatter rel-rows as a one-hot matmul.
    onehot = (rel[:, None] == jnp.arange(span, dtype=jnp.int32)[None, :]).astype(acc)
    # HIGHEST: at default precision the TPU multiplies f32 in one bf16 pass
    y_ref[...] += jnp.dot(onehot.T, prod, preferred_element_type=acc,
                          precision=jax.lax.Precision.HIGHEST)


def coo_spmv_pallas(
    plan: ChunkPlan,
    x: jax.Array,
    interpret: bool | None = None,
    batch_tile: int | None = None,
) -> jax.Array:
    """Run the windowed COO kernel for a ChunkPlan (SpMV or multi-RHS SpMM).

    Args:
      plan: host-built (or traced, see :class:`ChunkPlan`) chunk plan.
      x: (cols,) for SpMV or (cols, B) for SpMM.  For B > 1 the grid gains a
        leading lane-tiled batch axis: B is padded to a multiple of
        ``batch_tile`` lanes and each grid step works on one (chunk, lane
        tile) pair, reusing the same chunk stream across tiles.
      interpret: run the kernel body in interpret mode; None decides from
        the default backend (:func:`~repro.kernels.mode.interpret_mode`).
      batch_tile: RHS columns per grid step; default ``min(B, BATCH_TILE)``.

    Returns:
      y of shape (out_rows,) or (out_rows, B) in the accumulation dtype
      (f32 for bf16 input, i32 for i8/i16).
    """
    squeeze = x.ndim == 1
    xm = x[:, None] if squeeze else x
    B = xm.shape[1]
    bt = max(1, min(B, BATCH_TILE if batch_tile is None else batch_tile))
    b_pad = -(-B // bt) * bt
    if b_pad != B:
        xm = jnp.pad(xm, ((0, 0), (0, b_pad - B)))
    n_b = b_pad // bt
    n_chunks, E = plan.rowind.shape
    span = plan.span
    out_pad = plan.n_windows * span
    acc = _acc_dtype(plan.values.dtype)
    if n_chunks == 0:  # empty matrix: nothing to launch
        y = jnp.zeros((plan.out_rows, B), acc)
        return y[:, 0] if squeeze else y
    record_build("coo", B)
    if interpret is None:
        interpret = interpret_mode()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_b, n_chunks),
        in_specs=[
            pl.BlockSpec((1, E), lambda b, j, w, c: (j, 0)),  # rowind chunk
            pl.BlockSpec((1, E), lambda b, j, w, c: (j, 0)),  # colind chunk
            pl.BlockSpec((1, E), lambda b, j, w, c: (j, 0)),  # values chunk
            pl.BlockSpec((xm.shape[0], bt), lambda b, j, w, c: (0, b)),  # x tile
        ],
        out_specs=pl.BlockSpec((span, bt), lambda b, j, w, c: (w[j], b)),
    )
    y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((out_pad, b_pad), acc),
        interpret=interpret,
    )(
        jnp.asarray(plan.window),
        jnp.asarray(plan.count),
        jnp.asarray(plan.rowind),
        jnp.asarray(plan.colind),
        jnp.asarray(plan.values),
        xm,
    )
    # Windows with no chunks are never initialized: mask them.  Scatter-add
    # (not set): several chunks — including padded count-0 ones — may carry
    # the same window id, and duplicate-index set order is unspecified.
    touched = (
        jnp.zeros((plan.n_windows,), jnp.int32)
        .at[jnp.asarray(plan.window)]
        .add((jnp.asarray(plan.count) > 0).astype(jnp.int32), mode="drop")
    ) > 0
    y = jnp.where(jnp.repeat(touched, span)[:, None], y, 0)
    y = y[: plan.out_rows, :B]
    return y[:, 0] if squeeze else y


def stack_chunk_plans(plans: Sequence[ChunkPlan]) -> dict:
    """Stack per-shard ChunkPlans into SPMD arrays with a leading part axis.

    All plans must share span / n_windows / out_rows / chunk width (they do
    when built per part of one PartitionedMatrix with uniform ``h_pad``).
    Shards with fewer chunks are padded with empty chunks (count 0) whose
    window id repeats the shard's last real window, so the padded grid steps
    neither re-zero a window nor contribute values.

    Returns a dict of host arrays — ``window``/``count`` of shape
    (P, n_chunks) and ``rowind``/``colind``/``values`` of (P, n_chunks, E) —
    ready for ``jax.device_put`` with the part axis sharded, plus the shared
    static metadata under ``span`` / ``n_windows`` / ``out_rows``.
    """
    if not plans:
        raise ValueError("stack_chunk_plans needs at least one plan")
    first = plans[0]
    for p in plans[1:]:
        if (p.span, p.n_windows, p.out_rows, p.rowind.shape[1]) != (
            first.span, first.n_windows, first.out_rows, first.rowind.shape[1]
        ):
            raise ValueError("per-shard chunk plans have mismatched metadata")
    E = first.rowind.shape[1]
    nc = max(1, max(p.rowind.shape[0] for p in plans))
    Pn = len(plans)
    ri = np.zeros((Pn, nc, E), np.int32)
    ci = np.zeros((Pn, nc, E), np.int32)
    vv = np.zeros((Pn, nc, E), np.asarray(first.values).dtype)
    win = np.zeros((Pn, nc), np.int32)
    cnt = np.zeros((Pn, nc), np.int32)
    for p, plan in enumerate(plans):
        n = plan.rowind.shape[0]
        ri[p, :n] = plan.rowind
        ci[p, :n] = plan.colind
        vv[p, :n] = plan.values
        win[p, :n] = plan.window
        cnt[p, :n] = plan.count
        if n:  # padding chunks revisit the last real window with count 0
            win[p, n:] = plan.window[-1]
    return dict(rowind=ri, colind=ci, values=vv, window=win, count=cnt,
                span=first.span, n_windows=first.n_windows,
                out_rows=first.out_rows)
