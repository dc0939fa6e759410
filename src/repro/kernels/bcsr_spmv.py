"""Pallas TPU kernel: block-sparse SpMV/SpMM (BCSR / BCOO in BCOO normal form).

TPU adaptation of SparseP's block formats (paper §2.1.1 BCSR/BCOO, §3.5).
The paper's UPMEM kernel DMAs r x c = 4x4 blocks MRAM->WRAM and feeds the
DPU's 8x8-bit multiplier.  The TPU-native rethink (DESIGN.md §2, changed
assumption #3):

  * blocks are MXU/VPU-aligned — (8, 128) by default — each nonzero block is
    one dense (r, c) x (c, B) MXU issue;
  * the block-coordinate stream is **scalar-prefetched**
    (pltpu.PrefetchScalarGridSpec): the BlockSpec index_map DMAs exactly the
    x window a block needs, HBM->VMEM — the TPU equivalent of the paper's
    fine-grained MRAM accesses to the input vector (§3.5 point 2);
  * grid steps sharing a block-row revisit the same output window and
    accumulate in VMEM (zero-init on first visit).  The lock-free merge
    (paper ``lf``, Obs. 2/6) falls out of the sequential grid — no mutexes
    exist or are needed on TPU;
  * padded steps (i >= nblocks) carry zero blocks and a clamped browind equal
    to the last real row, so they revisit that window and add zero.

The same kernel executes BCSR (expand browptr host-side) and BCOO — the
formats differ only in their *partitionability* (paper Obs. 7), which is a
host-side concern (core/partition.py).

Validated in interpret mode against kernels/ref.py:bcoo_spmv_ref over
shape/dtype sweeps (tests/test_kernels_block.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .instrument import record_build
from .mode import interpret_mode

__all__ = ["bcoo_spmv_pallas", "DEFAULT_BLOCK", "BATCH_TILE"]

DEFAULT_BLOCK = (8, 128)  # MXU-aligned (sublane x lane)
BATCH_TILE = 128  # SpMM lane tile: RHS columns per grid step


def _acc_dtype(dtype):
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    if dtype in (jnp.int8, jnp.int16):
        return jnp.int32
    return dtype


def _kernel(browind_ref, bcolind_ref, nb_ref, bval_ref, x_ref, y_ref):
    """One grid step = one nonzero (r, c) block against its (c, BT) x window.

    Grid is (batch tiles, blocks): the block axis is innermost so the
    accumulate-in-VMEM invariant (consecutive visits per block-row) holds per
    batch tile; each batch tile replays the block stream against its own lane
    slice of x/y.
    """
    i = pl.program_id(1)
    # First visit of this output window <=> first step or block-row changed
    # (stream is block-row sorted — format invariant).
    first = (i == 0) | (browind_ref[i] != browind_ref[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    valid = i < nb_ref[0]
    a = bval_ref[0]  # (r, c)
    xb = x_ref[...]  # (c, B) window at block-column bcolind[i]
    acc = y_ref.dtype
    # HIGHEST: at default precision the TPU multiplies f32 in one bf16 pass
    prod = jnp.dot(a.astype(acc), xb.astype(acc), preferred_element_type=acc,
                   precision=jax.lax.Precision.HIGHEST)
    y_ref[...] += jnp.where(valid, prod, 0)


def bcoo_spmv_pallas(
    browind: jax.Array,
    bcolind: jax.Array,
    bvalues: jax.Array,
    x: jax.Array,
    out_rows: int,
    nblocks: jax.Array | int | None = None,
    interpret: bool | None = None,
    batch_tile: int | None = None,
) -> jax.Array:
    """Block-sparse y = A @ x, A given as a block-row-sorted BCOO stream.

    Args:
      browind/bcolind: (nb_cap,) int32 block coordinates (block units).
      bvalues: (nb_cap, r, c) dense blocks, zero past ``nblocks``.
      x: (cols,) for SpMV or (cols, B) for SpMM; x is zero-padded up to a
        multiple of c so the per-block (c, BT) windows always align.  For
        B > 1 the grid gains a leading lane-tiled batch axis (B padded to a
        multiple of ``batch_tile``); each nonzero block becomes one
        (r, c) x (c, BT) MXU issue per batch tile.
      out_rows: static output height (multiple of r).
      nblocks: true nonzero-block count (<= nb_cap); None means all.
      interpret: execute the kernel body in Python; None decides from the
        default backend (:func:`~repro.kernels.mode.interpret_mode`).
      batch_tile: RHS columns per grid step; default ``min(B, BATCH_TILE)``.

    Returns y (out_rows[, B]) in the accumulation dtype (f32 for bf16 input,
    i32 for i8/i16 — the MXU accumulator semantics).
    """
    nb_cap, r, c = bvalues.shape
    squeeze = x.ndim == 1
    xm = x[:, None] if squeeze else x
    B = xm.shape[1]
    bt = max(1, min(B, BATCH_TILE if batch_tile is None else batch_tile))
    b_pad = -(-B // bt) * bt
    col_pad = -(-xm.shape[0] // c) * c
    if col_pad != xm.shape[0] or b_pad != B:
        xm = jnp.pad(xm, ((0, col_pad - xm.shape[0]), (0, b_pad - B)))
    nb = jnp.asarray(nb_cap if nblocks is None else nblocks, jnp.int32)

    # Sanitize padding coordinates: padded steps must revisit the *last real*
    # block-row (never jump back to row 0, which would re-zero its window).
    k = jnp.arange(nb_cap, dtype=jnp.int32)
    last_row = browind[jnp.maximum(nb - 1, 0)]
    browind = jnp.where(k < nb, browind, last_row)
    bcolind = jnp.where(k < nb, bcolind, 0)

    acc = _acc_dtype(bvalues.dtype)
    record_build("bcoo", B)
    if interpret is None:
        interpret = interpret_mode()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b_pad // bt, nb_cap),
        in_specs=[
            pl.BlockSpec((1, r, c), lambda b, i, bri, bci, nb_: (i, 0, 0)),
            pl.BlockSpec((c, bt), lambda b, i, bri, bci, nb_: (bci[i], b)),
        ],
        out_specs=pl.BlockSpec((r, bt), lambda b, i, bri, bci, nb_: (bri[i], b)),
    )
    y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((out_rows, b_pad), acc),
        interpret=interpret,
    )(browind, bcolind, nb.reshape(1), bvalues, xm)

    # Block-rows with no nonzero blocks are never visited: mask them.
    # Scatter-add (not set): padded steps share the last real block-row id,
    # and duplicate-index set order is unspecified.
    touched = jnp.zeros((out_rows // r,), jnp.int32).at[browind].add(
        (k < nb).astype(jnp.int32), mode="drop"
    ) > 0
    y = jnp.where(jnp.repeat(touched, r)[:, None], y, 0)
    y = y[:, :B]
    return y[:, 0] if squeeze else y
