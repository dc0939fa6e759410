"""Executor protocol — one call signature for every SpMV path.

An :class:`Executor` is the compiled end of the ``SparseMatrix ->
ExecutionPlan -> Executor`` pipeline: ``y = exe(x)`` for a single vector and
``Y = exe.batch(X)`` for multi-RHS SpMM, regardless of whether the plan runs

  * on a single device through :mod:`repro.kernels.ops` (XLA oracles or the
    Pallas TPU kernels), or
  * distributed over a mesh through :mod:`repro.core.distributed` shard_map
    programs (1D broadcast-x, 1D ring, 2D merge-partials).

Both return host ``np.ndarray`` rows — the serving contract the engine and
the batcher build on.  The mesh executor additionally exposes the three
paper phases (``place`` / ``run_raw`` / ``assemble``, Fig. 4 load / kernel /
retrieve) so the engine's telemetry can time them separately, and
``release()`` to proactively free the device-placed matrix (plan-cache
eviction).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core import distributed as D
from repro.core.partition import PartitionedMatrix
from repro.core.semiring import lookup
from repro.kernels import ops
from repro.obs import NULL_TRACE

from .iterate import IterateResult, run_iterate

__all__ = ["Executor", "SingleDeviceExecutor", "MeshExecutor",
           "IterateResult", "AXIS_1D", "AXES_2D"]

# Canonical mesh axis names for api-built meshes (the engine reuses these).
AXIS_1D = "parts"
AXES_2D = ("rows", "cols")


class Executor:
    """Common surface: ``exe(x) -> y`` and ``exe.batch(X) -> Y`` (host rows)."""

    plan = None  # the ExecutionPlan this executor was compiled from

    @property
    def semiring(self) -> str:
        """The semiring the plan runs: ``plus_times`` or ``min_plus``."""
        return "plus_times" if self.plan is None else self.plan.semiring

    def __call__(self, x) -> np.ndarray:
        """y = A @ x for one vector x of shape (cols,); returns host rows."""
        raise NotImplementedError

    def batch(self, X) -> np.ndarray:
        """Y = A @ X for X of shape (cols, B); one SpMM, returns host rows."""
        raise NotImplementedError

    def release(self) -> None:
        """Free device buffers held by this executor (idempotent)."""

    # -- iterative-solver sessions ----------------------------------------

    def iterate(self, x0, steps=None, tol=None, combine="plain", *,
                b=None, diag=None, omega: float = 1.0,
                max_steps: int = 1000, check_every: int = 8,
                trace=NULL_TRACE) -> IterateResult:
        """Run a compiled solver loop of SpMVs with x resident on device.

        One ``lax.scan`` (``steps=k``) or ``lax.while_loop`` (``tol=...``,
        residual checked every ``check_every`` steps, bounded by
        ``max_steps``) over ``y = A @ x`` plus the per-step ``combine``
        (``plain`` / ``power`` / ``richardson`` / ``jacobi`` / ``cg`` or a
        callable ``f(x, y) -> x_next``) — see :mod:`repro.api.iterate`.
        Requires a square matrix.  The compiled loop is cached per
        (combine, mode), so repeated solves — including with new ``b`` —
        pay no re-trace.  The session's phases (``spmv.solve.place`` /
        ``.loop`` / ``.fetch``) are profiler annotations and, on ``trace``,
        the spans ``load`` / ``kernel`` / ``retrieve``
        (:func:`repro.obs.phase`).

        Returns:
          :class:`IterateResult` — x on host, steps executed, convergence
          flag + residual, per-phase seconds.

        Raises:
          ValueError: non-square matrix, both/neither of steps and tol,
            batched x0, or missing combine params (b / diag).
          TypeError: x0 dtype cannot safely cast to the matrix dtype.
          RuntimeError: the executor was released.
        """
        return run_iterate(
            self, self._iterate_apply(), x0, steps=steps, tol=tol,
            combine=combine, b=b, diag=diag, omega=omega,
            max_steps=max_steps, check_every=check_every, trace=trace,
        )

    def _iterate_shape(self):
        """(n, dtype) for solver loops; raises unless the matrix is square."""
        raise NotImplementedError

    def _iterate_apply(self):
        """Traced device function, logical (n,) -> (n,)."""
        raise NotImplementedError

    @staticmethod
    def _require_square(rows: int, cols: int):
        if rows != cols:
            raise ValueError(
                f"iterate() feeds y back as the next x and therefore needs "
                f"a square matrix; got {rows}x{cols}"
            )
        return cols

    # -- shared input validation ------------------------------------------

    def _check_x(self, x, cols: int, dtype) -> np.ndarray:
        x = np.asarray(x)
        if not np.can_cast(x.dtype, dtype, casting="same_kind"):
            raise TypeError(
                f"x dtype {x.dtype} cannot safely cast to matrix dtype "
                f"{np.dtype(dtype)}"
            )
        x = x.astype(dtype, copy=False)
        if x.shape[0] != cols:
            raise ValueError(f"x has {x.shape[0]} rows, matrix has {cols} cols")
        return x


class SingleDeviceExecutor(Executor):
    """kernels.ops-backed executor (XLA oracle or Pallas kernels).

    For ``impl="pallas"`` the host-side kernel plan (chunk planning for
    COO/CSR, browptr expansion for BCSR) is built once at construction via
    :func:`repro.kernels.ops.pallas_program`; every subsequent ``exe(x)`` /
    ``exe.batch(X)`` runs only the kernel — SpMM batches dispatch onto the
    lane-tiled multi-RHS grid, never a per-column loop.
    """

    def __init__(self, plan, container, impl: str):
        self.plan = plan
        self.container = container
        self.impl = impl
        self._pallas = (ops.pallas_program(container)
                        if impl == "pallas" else None)

    def __call__(self, x) -> np.ndarray:
        """y = A @ x (host rows).

        Args:
          x: (cols,) vector, or (cols, B) — forwarded to :meth:`batch`.

        Raises:
          TypeError: if x's dtype cannot safely cast to the matrix dtype.
          ValueError: on a length mismatch with the matrix columns.
        """
        x = self._check_x(x, self.container.cols, self.container.dtype)
        if x.ndim == 2:
            return self.batch(x)
        if self._pallas is not None:
            return np.asarray(self._pallas(jnp.asarray(x)))
        y = ops.spmv(self.container, jnp.asarray(x), impl=self.impl)
        return np.asarray(y)

    def batch(self, X) -> np.ndarray:
        """Y = A @ X for X of shape (cols, B) — one SpMM, any impl.

        Raises:
          TypeError/ValueError: as :meth:`__call__`, plus ValueError when X
            is not 2D.
        """
        X = self._check_x(X, self.container.cols, self.container.dtype)
        if X.ndim != 2:
            raise ValueError(f"batch expects X of shape (cols, B); got {X.shape}")
        if self._pallas is not None:
            return np.asarray(self._pallas(jnp.asarray(X)))
        return np.asarray(ops.spmm(self.container, jnp.asarray(X)))

    # -- solver-loop backend ----------------------------------------------

    def _iterate_shape(self):
        c = self.container
        return self._require_square(c.rows, c.cols), c.dtype

    def _iterate_apply(self):
        """y = A @ v on device — the same kernel dispatch as ``__call__``
        (XLA oracle or the prebuilt Pallas program), cast back to the
        matrix dtype so the recurrence matches k host-side calls bit for
        bit (``_check_x`` applies the same cast on the host loop)."""
        dtype = self.container.dtype

        if self._pallas is not None:
            def apply(v):
                y = self._pallas(v)
                return y.astype(dtype) if y.dtype != dtype else y
            return apply

        def apply(v):
            y = ops.spmv(self.container, v, impl=self.impl)
            return y.astype(dtype) if y.dtype != dtype else y
        return apply


class MeshExecutor(Executor):
    """shard_map-backed executor: partitioned, placed and traced once.

    Owns everything the one-shot path rebuilds per call: the partitioned
    matrix, its device placement, and the jitted program (wrapped with a
    trace counter so callers can assert steady-state zero-retrace).
    """

    def __init__(
        self,
        plan,
        part: PartitionedMatrix,
        mesh,
        axes: tuple,
        program: Callable,  # D.spmv_* call object with .jitted
        x_spec,
        x_pad: int,
        merge: str,
    ):
        self.plan = plan
        self.part = part
        self.mesh = mesh
        self.axes = axes
        self.program = program
        self.x_spec = x_spec
        self.x_pad = x_pad
        self.merge = merge
        self.arrays = None  # device-placed matrix pytree (set by place_matrix)
        self.build_seconds = 0.0
        self.assemble_meta = dict(
            row_start=np.asarray(part.row_start),
            row_extent=np.asarray(part.row_extent),
            rows=part.shape[0],
        )
        trace_box = {"count": 0}
        inner_jit = program.jitted

        @jax.jit
        def run(arrs, xs):
            trace_box["count"] += 1  # python side effect: fires per (re)trace
            return inner_jit(arrs, xs)

        self.run = run
        self.trace_count_fn = lambda: trace_box["count"]

    @property
    def trace_count(self) -> int:
        return self.trace_count_fn()

    def place_matrix(self, placed_arrays) -> "MeshExecutor":
        self.arrays = placed_arrays
        return self

    # -- the paper's three phases (Fig. 4), individually timeable ---------

    def place(self, x) -> jax.Array:
        """Load phase: validate, pad and place x on the mesh (blocks).

        Args:
          x: (cols,) vector or (cols, B) batch on the host.

        Returns:
          The device-placed (padded) x, sharded with the plan's x spec.

        Raises:
          TypeError/ValueError: on dtype or length mismatches.
        """
        x = self._check_x(x, self.part.shape[1], self.part.dtype)
        if self.x_pad != x.shape[0]:
            x = np.pad(x, ((0, self.x_pad - x.shape[0]),)
                       + ((0, 0),) * (x.ndim - 1))
        xs = jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, self.x_spec))
        return jax.block_until_ready(xs)

    def run_raw(self, xs) -> jax.Array:
        """Kernel phase: the jitted shard_map program (blocks).

        Args:
          xs: device-placed x from :meth:`place`.

        Returns:
          Raw per-part output slices (still device-sharded).

        Raises:
          RuntimeError: if the executor was released (arrays deleted).
        """
        if self.arrays is None:
            raise RuntimeError("executor released or never placed; recompile")
        return jax.block_until_ready(self.run(self.arrays, xs))

    def assemble(self, raw) -> np.ndarray:
        """Retrieve phase: fetch + assemble global rows on the host.

        Args:
          raw: the device output of :meth:`run_raw`.

        Returns:
          The assembled global y as a host ndarray (rows[, B]).
        """
        meta = self.assemble_meta
        if self.plan is not None and self.plan.partitioning == "1d":
            out = D.SpmvOutput(raw, merge="none", semiring=self.semiring,
                               **meta)
        elif self.merge == "global":
            out = D.SpmvOutput(raw, merge="global",
                               replicated_global=raw[0, 0][: meta["rows"]],
                               **meta)
        else:
            out = D.SpmvOutput(raw, merge=self.merge, **meta)
        return D.assemble_rows(out)

    # -- public surface ----------------------------------------------------

    def __call__(self, x) -> np.ndarray:
        """y = A @ x: place -> run_raw -> assemble (the three Fig.-4 phases).

        Args:
          x: (cols,) vector or (cols, B) batch.

        Returns:
          Host rows (rows[, B]).

        Raises:
          TypeError/ValueError: on dtype/shape mismatch.
          RuntimeError: if the executor was released.
        """
        return self.assemble(self.run_raw(self.place(x)))

    def batch(self, X) -> np.ndarray:
        """Y = A @ X as ONE distributed SpMM (the batch rides through the
        same shard_map program; with impl="pallas" the local tile kernels
        run their lane-tiled multi-RHS grids).

        Args:
          X: (cols, B) right-hand sides.

        Returns:
          Host rows (rows, B).

        Raises:
          ValueError: if X is not 2D (plus the __call__ errors).
        """
        X = np.asarray(X)
        if X.ndim != 2:
            raise ValueError(f"batch expects X of shape (cols, B); got {X.shape}")
        return self(X)

    def warmup(self) -> None:
        """Trace + compile the vector-shaped program off the request path."""
        self.run_raw(self.place(np.zeros(self.part.shape[1], self.part.dtype)))

    # -- solver-loop backend ----------------------------------------------

    def _iterate_shape(self):
        rows, cols = self.part.shape
        return self._require_square(rows, cols), self.part.dtype

    def _iterate_apply(self):
        """y = A @ v entirely on the mesh: pad v to the plan's x width,
        re-shard it with the plan's x spec (``with_sharding_constraint`` —
        the in-jit analogue of :meth:`place`), run the shard_map program,
        and assemble the global rows on device with the exact slice/add
        order of :meth:`assemble`, so the recurrence stays bit-identical
        to the host loop."""
        if self.arrays is None:
            raise RuntimeError("executor released or never placed; recompile")
        n, _ = self._iterate_shape()
        x_pad = self.x_pad
        sharding = NamedSharding(self.mesh, self.x_spec)
        arrays = self.arrays
        program = self.program.jitted
        meta = self.assemble_meta
        rows = meta["rows"]
        row_start = [int(r) for r in meta["row_start"]]
        row_extent = [min(int(e), rows - r)
                      for r, e in zip(row_start, meta["row_extent"])]
        is_1d = self.plan is not None and self.plan.partitioning == "1d"
        merge = self.merge
        sr = lookup(self.semiring)

        def assemble_dev(raw):
            if not is_1d and merge == "global":
                return raw[0, 0][:rows]
            if not is_1d and merge in ("psum", "psum_scatter"):
                R, C = raw.shape[:2]
                y = jnp.zeros((rows,) + raw.shape[3:], raw.dtype)
                for r in range(R):
                    r0, ext = row_start[r * C], row_extent[r * C]
                    block = (raw[r, 0] if merge == "psum"
                             else raw[r].reshape((-1,) + raw.shape[3:]))
                    y = y.at[r0:r0 + ext].set(block[:ext])
                return y
            # 1D per-part slices: duplicates on shared boundary rows hold
            # the semiring's zero (the ppermute moved them), so the sum's
            # order matches the host's
            y = sr.fill((rows,) + raw.shape[2:], raw.dtype)
            for p in range(raw.shape[0]):
                r0, ext = row_start[p], row_extent[p]
                y = sr.scatter(y.at[r0:r0 + ext], raw[p][:ext])
            return y

        def apply(v):
            if x_pad != n:
                xp = jnp.pad(v, ((0, x_pad - n),))
            else:
                xp = v
            xs = jax.lax.with_sharding_constraint(xp, sharding)
            return assemble_dev(program(arrays, xs))

        return apply

    def release(self) -> None:
        """Delete the device-placed matrix arrays (plan-cache eviction).

        Makes the executor unusable; callers must recompile.  Idempotent and
        tolerant of backends without explicit deletion.
        """
        arrays, self.arrays = self.arrays, None
        if arrays is None:
            return
        for leaf in jax.tree_util.tree_leaves(arrays):
            try:
                leaf.delete()
            except Exception:
                pass
