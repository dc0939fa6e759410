"""Distributed SpMV over a device mesh — the paper's §3/§6 on TPU collectives.

Execution model (paper Fig. 4) and its TPU mapping (DESIGN.md §2):

  paper step                       | TPU realization
  ---------------------------------+------------------------------------------
  load   (broadcast x to banks)    | 1D: all_gather(x) over the part axis
                                   | 2D: x arrives sharded over the column axis
                                   |     (equally-sized/-wide need NO load
                                   |     collective; variable-sized all-gathers
                                   |     + re-slices)
  kernel (per-core SpMV)           | per-device local SpMV (kernels/)
  retrieve + merge (host gathers   | 1D row-granular: none (rows disjoint)
  partials, CPU merges)            | 1D element-granular: one boundary value
                                   |     per neighbor pair via ppermute
                                   | 2D equally-sized: psum / psum_scatter over
                                   |     the column axis (in-network merge)
                                   | 2D equally-wide / variable-sized: partials
                                   |     scattered into a global buffer and
                                   |     psum'd over the whole mesh — the
                                   |     faithful analogue of the paper's
                                   |     retrieve bottleneck (Obs. 12)

All functions build a jitted shard_map program for a given PartitionedMatrix
(static metadata) and mesh; the matrix arrays are placed with the leading part
axis sharded over the mesh axes.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.partition import PartitionedMatrix
from repro.core.semiring import lookup
from repro.kernels import ref as kref
from repro.kernels import row_slices
from repro.kernels.ops import kernel_scope

__all__ = [
    "SpmvOutput",
    "place_1d",
    "place_2d",
    "spmv_1d",
    "spmv_2d",
    "spmv_1d_ring",
    "assemble_rows",
    "bucket_by_source_shard",
    "pallas_chunk_arrays",
    "slice_arrays",
]


@dataclass(frozen=True)
class SpmvOutput:
    """Distributed SpMV result: per-part output slices + placement metadata."""

    y_parts: jax.Array  # (P, h_pad[, B]) — device-sharded partial/owned slices
    row_start: np.ndarray  # (P,) host copy for assembly
    row_extent: np.ndarray  # (P,)
    rows: int
    merge: str = "none"  # none | psum | psum_scatter | global
    replicated_global: jax.Array | None = None  # set by 2D merge="global"
    semiring: str = "plus_times"  # how 1D parts' rows combine on assembly


def _pallas_span(h_pad: int) -> int:
    """Output-window height for per-shard chunk plans: the padded tile height,
    8-sublane aligned and capped at the single-device ROW_SPAN (local tiles
    are far shorter than a whole matrix)."""
    from repro.kernels.coo_spmv import ROW_SPAN

    return max(8, min(ROW_SPAN, -(-h_pad // 8) * 8))


def pallas_chunk_arrays(mat: PartitionedMatrix, chunk: int | None = None) -> dict:
    """Host-side per-shard Pallas chunk plans for a scalar-format partition.

    Builds one windowed :class:`~repro.kernels.coo_spmv.ChunkPlan` per part
    (row-granular for CSR, element-granular for COO — the same balancing
    semantics the single-device kernels use) against the uniform padded tile
    height ``h_pad``, and stacks them with a leading part axis
    (:func:`~repro.kernels.coo_spmv.stack_chunk_plans`) so they can be
    ``device_put`` alongside the matrix arrays and sliced per shard inside
    ``shard_map``.  Matrices are preprocessing artifacts (paper §3.1): this
    runs once per compiled plan, never per request.

    Returns a dict of host arrays keyed ``chunk_rowind`` / ``chunk_colind`` /
    ``chunk_values`` (P, n_chunks, E) and ``chunk_window`` / ``chunk_count``
    (P, n_chunks).  The static window metadata is derived from ``mat`` alone
    (``_pallas_span``), so the program builder needs no side channel.
    """
    from repro.kernels.coo_spmv import CHUNK_E, plan_chunks, stack_chunk_plans

    if mat.fmt not in ("coo", "csr"):
        raise ValueError("chunk plans are for scalar formats; block formats "
                         "run bcoo_spmv_pallas on the partition arrays")
    chunk = CHUNK_E if chunk is None else chunk
    span = _pallas_span(mat.h_pad)
    rowind = np.asarray(mat.rowind)
    colind = np.asarray(mat.colind)
    values = np.asarray(mat.values)
    nnz = np.asarray(mat.nnz)
    plans = []
    for p in range(mat.n_parts):
        n = int(nnz[p])
        plans.append(plan_chunks(
            rowind[p, :n], colind[p, :n], values[p, :n], mat.h_pad,
            chunk=chunk, span=span, row_granular=(mat.fmt == "csr"),
        ))
    stacked = stack_chunk_plans(plans)
    return {f"chunk_{k}": v for k, v in stacked.items()
            if isinstance(v, np.ndarray)}


def slice_arrays(mat: PartitionedMatrix,
                 semiring: str = "plus_times") -> row_slices.SliceLayout:
    """Host-side row-slice layout of a scalar-format partition, for the XLA
    tile kernel (:mod:`repro.kernels.row_slices`), padded with the
    ``semiring``'s zero.

    Cuts each part's first ``nnz[p]`` entries into slices of one width,
    chosen from the row-length histogram of the whole partition, with one
    slice count ``S`` for every part.  The layout's ``arrays``
    (``slice_colind`` / ``slice_values`` (P, w, S), ``slice_row`` (P, S)
    unless every row is one slice) are placed with the matrix, like the
    Pallas chunk plans; this runs once per compiled plan.
    """
    if mat.fmt not in ("coo", "csr"):
        raise ValueError("row slices are for scalar formats; block formats "
                         "run bcoo_spmv_ref on the partition arrays")
    return row_slices.build_row_slices(
        np.asarray(mat.rowind), np.asarray(mat.colind),
        np.asarray(mat.values), np.asarray(mat.nnz), mat.h_pad, semiring)


def _local_kernel(mat: PartitionedMatrix, impl: str, mesh,
                  semiring: str = "plus_times"):
    """Build the per-shard kernel ``f(sl, x_local) -> y (h_pad[, B])``.

    impl="xla" runs the row-slice kernel for COO/CSR (its ``slice_*``
    arrays, from :func:`slice_arrays`, carried in the placed arrays) and
    the jnp block oracle for BCSR/BCOO (lower everywhere, shard-safe);
    impl="pallas" runs the TPU kernels on the local tile — the chunked
    windowed kernel for COO/CSR (plans prebuilt host-side by
    :func:`pallas_chunk_arrays` and carried in the placed arrays under
    ``chunk_*``), the block kernel for BCSR/BCOO.  Both impls return the
    values dtype (accumulation happens wider inside, matching the oracle
    contract the merge collectives rely on).  The Pallas kernels compile
    for a TPU mesh and run in interpret mode on a CPU mesh.  The kernel's
    ops sit under :func:`kernel_scope`.  ``semiring`` reaches the row-slice
    kernel only; plans refuse (min, +) on every other kernel
    (:func:`repro.api.plan.check_semiring`).
    """
    local = _tile_kernel(mat, impl, mesh, semiring)

    def named(sl, x_local):
        with kernel_scope(mat.fmt, impl, semiring):
            return local(sl, x_local)

    return named


def _tile_kernel(mat: PartitionedMatrix, impl: str, mesh, semiring: str):
    if impl == "xla":
        if mat.fmt in ("coo", "csr"):
            return lambda sl, x_local: row_slices.row_slice_spmv(
                sl["slice_colind"], sl["slice_values"], x_local, mat.h_pad,
                sl.get("slice_row"), semiring=semiring)
        return lambda sl, x_local: kref.bcoo_spmv_ref(
            sl["rowind"], sl["colind"], sl["values"], x_local, mat.h_pad,
            nblocks=sl["nnz"])
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}: 'xla' or 'pallas'")
    from repro.kernels.mode import interpret_mode

    interpret = interpret_mode(mesh.devices.flat)
    dtype = mat.dtype

    if mat.fmt in ("coo", "csr"):
        from repro.kernels.coo_spmv import ChunkPlan, coo_spmv_pallas

        span = _pallas_span(mat.h_pad)
        n_windows = max(1, -(-mat.h_pad // span))

        def run_scalar(sl, x_local):
            plan = ChunkPlan(
                rowind=sl["chunk_rowind"], colind=sl["chunk_colind"],
                values=sl["chunk_values"], window=sl["chunk_window"],
                count=sl["chunk_count"], n_windows=n_windows,
                out_rows=mat.h_pad, span=span,
            )
            y = coo_spmv_pallas(plan, x_local, interpret=interpret)
            return y.astype(dtype) if y.dtype != dtype else y

        return run_scalar

    from repro.kernels.bcsr_spmv import bcoo_spmv_pallas

    def run_block(sl, x_local):
        y = bcoo_spmv_pallas(
            sl["rowind"], sl["colind"], sl["values"], x_local, mat.h_pad,
            nblocks=sl["nnz"], interpret=interpret,
        )
        return y.astype(dtype) if y.dtype != dtype else y

    return run_block


def _slice0(tree):
    """Strip the leading size-1 shard axis inside shard_map."""
    return jax.tree.map(lambda a: a[0], tree)


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------


def _arrays(mat: PartitionedMatrix) -> dict:
    return dict(
        rowind=mat.rowind,
        colind=mat.colind,
        values=mat.values,
        nnz=mat.nnz,
        row_start=mat.row_start,
        col_start=mat.col_start,
    )


def place_1d(mat: PartitionedMatrix, mesh, axis: str | tuple = "data",
             extra: dict | None = None) -> dict:
    """Shard the part axis of a 1D partition over one (or more) mesh axes.

    ``extra`` merges additional host arrays with the same leading part axis
    into the placed pytree (the XLA ``slice_*`` or Pallas ``chunk_*``
    arrays of the scalar formats).
    """
    spec = P(axis)
    arrs = _arrays(mat)
    if extra:
        arrs.update(extra)
    return jax.device_put(arrs, NamedSharding(mesh, spec))


def place_2d(mat: PartitionedMatrix, mesh, axes=("data", "model"),
             extra: dict | None = None) -> dict:
    """Reshape parts (P,)->(R,C) and shard over (row-axis, col-axis).

    ``extra`` merges additional part-leading host arrays (see place_1d).
    """
    R, C = mat.grid
    arrs = _arrays(mat)
    if extra:
        arrs.update(extra)
    arrs = {k: np.asarray(v).reshape((R, C) + v.shape[1:])
            for k, v in arrs.items()}
    return jax.device_put(arrs, NamedSharding(mesh, P(axes[0], axes[1])))


# ---------------------------------------------------------------------------
# 1D execution (paper §6.1)
# ---------------------------------------------------------------------------


def _boundary_meta(mat: PartitionedMatrix):
    """Host-side boundary ownership for element-granular splits (paper §3.3.1:
    'if the row is split between two neighboring PIM cores at most one element
    needs to be accumulated')."""
    rs = np.asarray(mat.row_start)
    re_ = rs + np.asarray(mat.row_extent)
    Pn = mat.n_parts
    head_shared = np.zeros(Pn, bool)
    head_shared[1:] = rs[1:] < re_[:-1]  # my first row already started upstream
    recv_pos = np.zeros(Pn, np.int32)
    recv_pos[:-1] = np.clip(rs[1:] - rs[:-1], 0, mat.h_pad - 1)
    next_shared = np.zeros(Pn, bool)
    next_shared[:-1] = head_shared[1:]
    return head_shared, next_shared, recv_pos


def spmv_1d(
    mat: PartitionedMatrix,
    mesh,
    axis: str = "data",
    x_sharding_axis: str | None = None,
    impl: str = "xla",
    semiring: str = "plus_times",
) -> callable:
    """Build jitted distributed 1D SpMV: (placed_arrays, x) -> SpmvOutput.

    x enters sharded over ``axis`` (its natural production placement) and is
    all-gathered inside — the paper's broadcast/load step, now on ICI.  Row-
    granular schemes need no merge; element-granular ('1d.nnz') corrects the
    single split row per boundary with one collective_permute.  Under
    ``semiring="min_plus"`` the split row's duplicate holds +inf in place of
    0 and the neighbour's partial is merged by min in place of a sum.

    ``impl`` selects the per-shard tile kernel (XLA or the Pallas kernels);
    on scalar formats the placed arrays must include the kernel's layout —
    ``slice_arrays(mat).arrays`` for impl="xla", ``pallas_chunk_arrays``
    for impl="pallas" — passed as ``extra=`` to :func:`place_1d`.
    """
    Pn = mat.n_parts
    head_shared, next_shared, recv_pos = _boundary_meta(mat)
    hs = jnp.asarray(head_shared)
    ns = jnp.asarray(next_shared)
    rp = jnp.asarray(recv_pos.astype(np.int32))
    needs_merge = mat.scheme == "1d.nnz"
    perm = [(i, i - 1) for i in range(1, Pn)]
    local = _local_kernel(mat, impl, mesh, semiring)
    sr = lookup(semiring)

    def zero(v):  # a row with nothing to merge
        return sr.fill(v.shape, v.dtype)

    def _step(arrs, hs_l, ns_l, rp_l, x_shard):
        sl = _slice0(arrs)
        x_full = jax.lax.all_gather(x_shard, axis, tiled=True)
        y = local(sl, x_full)  # (h_pad[, B])
        if needs_merge and Pn > 1:
            send = jnp.where(hs_l[0], y[0], zero(y[0]))
            recv = jax.lax.ppermute(send, axis, perm)
            y = y.at[0].set(jnp.where(hs_l[0], zero(y[0]), y[0]))
            y = sr.scatter(y.at[rp_l[0]],
                           jnp.where(ns_l[0], recv, zero(recv)))
        return y[None]

    shmap = jax.shard_map(
        _step,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )

    @jax.jit
    def run(arrs, x_shard):
        y_parts = shmap(arrs, hs, ns, rp, x_shard)
        return y_parts

    meta = dict(
        row_start=np.asarray(mat.row_start),
        row_extent=np.asarray(mat.row_extent),
        rows=mat.shape[0],
        semiring=semiring,
    )

    def call(arrs, x_shard) -> SpmvOutput:
        return SpmvOutput(run(arrs, x_shard), **meta)

    call.jitted = run
    return call


# ---------------------------------------------------------------------------
# 1D ring execution with compute/comm overlap (beyond-paper; DESIGN.md §2)
# ---------------------------------------------------------------------------


def bucket_by_source_shard(
    mat: PartitionedMatrix, n_shards: int
) -> Tuple[PartitionedMatrix, np.ndarray]:
    """Re-lay each part's nnz as equal-capacity per-source-shard buckets.

    Enables the ring schedule: at ring step s each device multiplies only the
    elements whose columns live in the x shard it currently holds, while the
    next shard is in flight (XLA latency hiding overlaps ppermute with
    compute).  This replaces the paper's monolithic broadcast (its 1D
    bottleneck, Obs. 8) with a pipelined one.

    Buckets are padded to the max bucket size (cap_b) so every ring step is
    one static-shape slice — the same equal-transfer-size constraint the
    paper's UPMEM ranks impose, and the same padding-efficiency trade
    (Obs. 10): redundant work = (P*cap_b - nnz)/nnz.

    Returns a re-laid PartitionedMatrix whose capacity is n_shards*cap_b
    (elements of bucket s at [s*cap_b, (s+1)*cap_b)) and counts (P, n_shards).
    """
    cols = mat.shape[1]
    shard_w = -(-cols // n_shards)
    rowind = np.asarray(mat.rowind)
    colind = np.asarray(mat.colind)
    values = np.asarray(mat.values)
    nnz = np.asarray(mat.nnz)
    Pn, _ = rowind.shape
    counts = np.zeros((Pn, n_shards), np.int32)
    per = []  # (rowind, colind, values) per (part, bucket)
    for p in range(Pn):
        n = int(nnz[p])
        src = colind[p, :n] // shard_w
        order = np.argsort(src, kind="stable")
        counts[p] = np.bincount(src, minlength=n_shards)
        per.append((rowind[p, :n][order], colind[p, :n][order],
                    values[p, :n][order]))
    cap_b = max(1, int(counts.max()))
    ri = np.zeros((Pn, n_shards * cap_b), np.int32)
    ci = np.zeros((Pn, n_shards * cap_b), np.int32)
    vv = np.zeros((Pn, n_shards * cap_b), values.dtype)
    for p in range(Pn):
        offs = np.concatenate([[0], np.cumsum(counts[p])])
        for s in range(n_shards):
            lo, hi = int(offs[s]), int(offs[s + 1])
            dst = s * cap_b
            ri[p, dst : dst + hi - lo] = per[p][0][lo:hi]
            ci[p, dst : dst + hi - lo] = per[p][1][lo:hi]
            vv[p, dst : dst + hi - lo] = per[p][2][lo:hi]
    new = PartitionedMatrix(
        rowind=jnp.asarray(ri),
        colind=jnp.asarray(ci),
        values=jnp.asarray(vv),
        nnz=mat.nnz,
        row_start=mat.row_start,
        col_start=mat.col_start,
        row_extent=mat.row_extent,
        col_extent=mat.col_extent,
        shape=mat.shape,
        grid=mat.grid,
        fmt=mat.fmt,
        scheme=mat.scheme + "+ring",
        block=mat.block,
        h_pad=mat.h_pad,
        w_pad=mat.w_pad,
    )
    return new, counts


def spmv_1d_ring(
    mat: PartitionedMatrix,
    bucket_counts: np.ndarray,
    mesh,
    axis: str = "data",
) -> callable:
    """Ring-pipelined 1D SpMV (requires bucket_by_source_shard preprocessing).

    Per ring step: slice the equal-capacity bucket for the currently-held x
    shard, multiply, rotate the shard.  Comm volume equals plain all_gather
    but each transfer overlaps the previous bucket's compute; per-step work
    is one cap_b-sized slice (not a whole-stream masked pass), so total
    compute is nnz * padding-factor rather than nnz * P.
    """
    Pn = mat.n_parts
    cols = mat.shape[1]
    shard_w = -(-cols // Pn)
    cap_total = mat.capacity
    cap_b = cap_total // Pn  # bucket_by_source_shard layout invariant
    counts = jnp.asarray(bucket_counts.astype(np.int32))  # (P, n_shards)
    perm = [(i, (i - 1) % Pn) for i in range(Pn)]
    needs_merge = mat.scheme.startswith("1d.nnz")
    head_shared, next_shared, recv_pos = _boundary_meta(mat)
    hs, ns = jnp.asarray(head_shared), jnp.asarray(next_shared)
    rp = jnp.asarray(recv_pos.astype(np.int32))
    bperm = [(i, i - 1) for i in range(1, Pn)]

    def _step(arrs, counts_l, hs_l, ns_l, rp_l, x_shard):
        sl = _slice0(arrs)
        my_counts = counts_l[0]  # (n_shards,)
        me = jax.lax.axis_index(axis)
        pad = ((0, shard_w - x_shard.shape[0]),) + ((0, 0),) * (x_shard.ndim - 1)
        x_pad = jnp.pad(x_shard, pad)
        barange = jnp.arange(cap_b, dtype=jnp.int32)

        def body(carry, s):
            y, xbuf = carry
            holder = (me + s) % Pn  # shard id currently in xbuf
            with kernel_scope(mat.fmt, "xla"):
                start = holder * cap_b
                br = jax.lax.dynamic_slice_in_dim(sl["rowind"], start, cap_b)
                bc = jax.lax.dynamic_slice_in_dim(sl["colind"], start, cap_b)
                bv = jax.lax.dynamic_slice_in_dim(sl["values"], start, cap_b)
                valid = barange < jnp.take(my_counts, holder)
                local_col = bc - holder * shard_w
                acc = y.dtype
                xv = jnp.take(xbuf, jnp.clip(local_col, 0, shard_w - 1),
                              axis=0).astype(acc)
                prod = bv.astype(acc)[(...,) + (None,) * (xv.ndim - 1)] * xv
                prod = jnp.where(valid[(...,) + (None,) * (prod.ndim - 1)],
                                 prod, 0)
                y = y.at[br].add(prod, mode="drop")
            xbuf = jax.lax.ppermute(xbuf, axis, perm)
            return (y, xbuf), None

        acc_dt = kref._acc_dtype(sl["values"].dtype)
        y0 = jnp.zeros((mat.h_pad,) + x_shard.shape[1:], acc_dt)
        (y, _), _ = jax.lax.scan(body, (y0, x_pad), jnp.arange(Pn))
        if sl["values"].dtype != acc_dt:
            y = y.astype(sl["values"].dtype)
        if needs_merge and Pn > 1:
            send = jnp.where(hs_l[0], y[0], jnp.zeros_like(y[0]))
            recv = jax.lax.ppermute(send, axis, bperm)
            y = y.at[0].set(jnp.where(hs_l[0], jnp.zeros_like(y[0]), y[0]))
            y = y.at[rp_l[0]].add(jnp.where(ns_l[0], recv, jnp.zeros_like(recv)))
        return y[None]

    shmap = jax.shard_map(
        _step,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )

    @jax.jit
    def run(arrs, x_shard):
        return shmap(arrs, counts, hs, ns, rp, x_shard)

    meta = dict(
        row_start=np.asarray(mat.row_start),
        row_extent=np.asarray(mat.row_extent),
        rows=mat.shape[0],
    )

    def call(arrs, x_shard) -> SpmvOutput:
        return SpmvOutput(run(arrs, x_shard), **meta)

    call.jitted = run
    return call


# ---------------------------------------------------------------------------
# 2D execution (paper §6.2)
# ---------------------------------------------------------------------------


def spmv_2d(
    mat: PartitionedMatrix,
    mesh,
    axes: Tuple[str, str] = ("data", "model"),
    merge: str | None = None,
    impl: str = "xla",
) -> callable:
    """Build jitted distributed 2D SpMV: (placed_arrays, x) -> SpmvOutput.

    merge:
      * "psum"         (equally-sized default): reduce partials over the
                        column axis; y ends row-sharded — in-network merge.
      * "psum_scatter" : like psum but y ends sharded over both axes
                        (lowest collective bytes; beyond-paper default).
      * "global"       (equally-wide / variable-sized): partials scattered
                        into a global row buffer and all-reduced over the
                        whole mesh — faithful to the paper's retrieve+merge
                        path and its bottleneck (Obs. 12).

    ``impl`` selects the per-shard tile kernel exactly as in
    :func:`spmv_1d` (scalar formats need their layout placed with the
    matrix, e.g. ``place_2d(..., extra=slice_arrays(mat).arrays)``).
    """
    R, C = mat.grid
    da, ma = axes
    scheme = mat.scheme.split(".", 1)[1]
    if merge is None:
        merge = "psum" if scheme == "equally-sized" else "global"
    aligned = scheme == "equally-sized"
    if merge in ("psum", "psum_scatter") and not aligned:
        raise ValueError(f"{merge} merge requires aligned rows (equally-sized)")
    if scheme != "variable-sized" and mat.shape[1] % C != 0:
        raise ValueError(
            f"{scheme} needs cols % C == 0 to align x shards with tiles "
            f"(got {mat.shape[1]} % {C})"
        )
    if aligned and mat.shape[0] % R != 0:
        raise ValueError("equally-sized needs rows % R == 0")
    rows_pad = mat.h_pad * R if aligned else -(-mat.shape[0] // 8) * 8
    local = _local_kernel(mat, impl, mesh)

    def _step(arrs, x_shard):
        sl = _slice0(jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), arrs))
        if scheme == "variable-sized":
            # column ranges differ from the uniform shard: gather + re-slice
            x_full = jax.lax.all_gather(x_shard, ma, tiled=True)
            x_loc = jax.lax.dynamic_slice_in_dim(
                jnp.pad(x_full, ((0, mat.w_pad),) + ((0, 0),) * (x_full.ndim - 1)),
                sl["col_start"],
                mat.w_pad,
            )
        else:
            # equally-sized / equally-wide: the model-axis shard IS the tile's
            # x slice (paper: only a subset of x per core — no load collective)
            x_loc = x_shard
            if x_loc.shape[0] < mat.w_pad:
                x_loc = jnp.pad(
                    x_loc,
                    ((0, mat.w_pad - x_loc.shape[0]),) + ((0, 0),) * (x_loc.ndim - 1),
                )
        y = local(sl, x_loc)  # (h_pad[, B])
        if merge == "psum":
            y = jax.lax.psum(y, ma)
            return y[None, None]
        if merge == "psum_scatter":
            y = jax.lax.psum_scatter(y, ma, tiled=True)
            return y[None, None]
        # merge == "global": the paper's retrieve/merge path.  The buffer has
        # h_pad overhang so the last tiles' windows never clamp (their tails
        # are zero by construction).
        buf = jnp.zeros((rows_pad + mat.h_pad,) + y.shape[1:], y.dtype)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, y, sl["row_start"], axis=0)
        buf = jax.lax.psum(buf, (da, ma))
        return buf[None, None]

    out_spec = P(da, ma) if merge != "global" else P(None, None)
    shmap = jax.shard_map(
        _step,
        mesh=mesh,
        in_specs=(P(da, ma), P(ma)),
        out_specs=out_spec,
        check_vma=False,
    )

    @jax.jit
    def run(arrs, x_shard):
        return shmap(arrs, x_shard)

    meta = dict(
        row_start=np.asarray(mat.row_start),
        row_extent=np.asarray(mat.row_extent),
        rows=mat.shape[0],
    )

    def call(arrs, x_shard) -> SpmvOutput:
        out = run(arrs, x_shard)
        if merge == "global":
            flat = out[0, 0][: mat.shape[0]]
            return SpmvOutput(out, merge=merge, replicated_global=flat, **meta)
        return SpmvOutput(out, merge=merge, **meta)

    call.jitted = run
    return call


# ---------------------------------------------------------------------------
# assembly (host-side, for tests / examples / benchmarks)
# ---------------------------------------------------------------------------


def assemble_rows(out: SpmvOutput) -> np.ndarray:
    """Assemble the global y from per-part slices (host-side; tests/examples).

    1D (merge="none"): sum per-part slices into their row ranges — the
    boundary ppermute already moved shared-row values to their owner, so
    overlapping duplicates are zero (under ``min_plus``: the least, from
    +inf, duplicates +inf).
    2D psum: every column of the grid holds the merged row-block — take col 0.
    2D psum_scatter: device (r, c) holds segment c of row-block r.
    2D global: already replicated.
    """
    if out.replicated_global is not None:
        return np.asarray(out.replicated_global)
    yp = np.asarray(out.y_parts)
    if out.merge == "psum":  # (R, C, h_pad[, B]) — columns identical
        R, C = yp.shape[:2]
        h = yp.shape[2]
        y = np.zeros((out.rows,) + yp.shape[3:], yp.dtype)
        for r in range(R):
            r0 = int(out.row_start[r * C])
            ext = min(int(out.row_extent[r * C]), out.rows - r0)
            y[r0 : r0 + ext] = yp[r, 0][:ext]
        return y
    if out.merge == "psum_scatter":  # (R, C, h_pad/C[, B])
        R, C = yp.shape[:2]
        seg = yp.shape[2]
        y = np.zeros((out.rows,) + yp.shape[3:], yp.dtype)
        for r in range(R):
            r0 = int(out.row_start[r * C])
            ext = min(int(out.row_extent[r * C]), out.rows - r0)
            block = yp[r].reshape((C * seg,) + yp.shape[3:])
            y[r0 : r0 + ext] = block[:ext]
        return y
    # 1D parts
    sr = lookup(out.semiring)
    y = np.full((out.rows,) + yp.shape[2:], sr.zero, yp.dtype)
    for p in range(yp.shape[0]):
        r0 = int(out.row_start[p])
        ext = min(int(out.row_extent[p]), out.rows - r0)
        sr.host_plus(y[r0 : r0 + ext], yp[p][:ext], out=y[r0 : r0 + ext])
    return y
