"""SparseMatrix — the single front door of the SpMV pipeline.

Wraps a sparse matrix from any source (dense ndarray, scipy.sparse, raw COO
triplets, or an existing container format from :mod:`repro.core.formats`)
together with its sparsity statistics and content fingerprint, and exposes
one method chain for every execution path:

    sm  = SparseMatrix.from_dense(a)
    pln = sm.plan(scheme="auto", impl="xla")        # ExecutionPlan
    exe = pln.compile()                             # Executor
    y   = exe(x)                                    # host rows

Single-device runs keep the chosen container format and dispatch through
kernels.ops; passing ``mesh=`` or ``devices=`` to ``plan`` produces the
distributed shard_map program.  ``SpmvEngine`` layers caching, batching and
telemetry on top of exactly this chain.
"""
from __future__ import annotations

import hashlib
from typing import Optional, Tuple

import numpy as np

from repro import compat
from repro.core import formats as F
from repro.core.adaptive import HardwareModel, Plan, estimate_time
from repro.core.semiring import lookup
from repro.core.stats import MatrixStats, compute_stats
from repro.kernels.mode import interpret_mode

from .executor import AXES_2D, AXIS_1D
from .plan import ExecutionPlan, resolve_scheme

__all__ = ["SparseMatrix", "fingerprint_matrix"]

_CONTAINERS = (F.CSR, F.COO, F.BCSR, F.BCOO)
_FMT_OF = {F.CSR: "csr", F.COO: "coo", F.BCSR: "bcsr", F.BCOO: "bcoo"}


def fingerprint_matrix(a: np.ndarray) -> str:
    """Stable content hash of a dense matrix's sparsity structure + values."""
    a = np.ascontiguousarray(a)
    h = hashlib.sha256()
    h.update(repr((a.shape, a.dtype.str)).encode())
    ri, ci = np.nonzero(a)
    h.update(ri.astype(np.int64).tobytes())
    h.update(ci.astype(np.int64).tobytes())
    h.update(np.ascontiguousarray(a[ri, ci]).tobytes())
    return h.hexdigest()[:16]


def _fingerprint_entries(semiring: str, shape, rowind, colind,
                         values) -> str:
    """Content hash of a semiring's stored entries (a stored 0 included)."""
    h = hashlib.sha256()
    h.update(repr((semiring, tuple(shape), values.dtype.str)).encode())
    for arr in (rowind.astype(np.int64), colind.astype(np.int64), values):
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()[:16]


class SparseMatrix:
    """A sparse matrix plus its stats, behind every SpMV entry point."""

    def __init__(self, *, dense=None, triplets=None, container=None,
                 shape: Tuple[int, int] = None, dtype=None,
                 stats_block: Tuple[int, int] = (8, 16)):
        if dense is None and triplets is None and container is None:
            raise ValueError("SparseMatrix needs a dense array, triplets or "
                             "a container; use the from_* constructors")
        self._dense = dense
        self._triplets = triplets  # (rowind, colind, values)
        self._containers: dict = {}
        if container is not None:
            self._containers[_FMT_OF[type(container)]] = container
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self._stats_block = stats_block
        self._stats: Optional[MatrixStats] = None
        self._fingerprints: dict = {}  # semiring -> fingerprint
        self._entries = None  # the triplets as entries (entries())

    # ------------------------------------------------------------ constructors

    @classmethod
    def from_dense(cls, a, dtype=None,
                   stats_block: Tuple[int, int] = (8, 16)) -> "SparseMatrix":
        """Wrap a dense (host) array.

        Args:
          a: 2D array-like; zeros define the sparsity structure.
          dtype: optionally convert values (e.g. to bfloat16) up front.
          stats_block: (r, c) blocking used for the block-format statistics.

        Returns:
          A SparseMatrix viewing ``a``.

        Raises:
          ValueError: if ``a`` is not 2D.
        """
        a = np.asarray(a)
        if a.ndim != 2:
            raise ValueError(f"expected a 2D matrix, got shape {a.shape}")
        if dtype is not None:
            a = a.astype(dtype)
        return cls(dense=a, shape=a.shape, dtype=a.dtype,
                   stats_block=stats_block)

    @classmethod
    def from_scipy(cls, m, dtype=None) -> "SparseMatrix":
        """Wrap anything with scipy.sparse's ``tocoo()`` protocol.

        Args:
          m: a scipy.sparse matrix (any format exposing ``tocoo()``).
          dtype: optionally convert values.

        Returns:
          A SparseMatrix over the matrix's COO triplets.

        Raises:
          TypeError: if ``m`` has no ``tocoo`` method.
        """
        if not hasattr(m, "tocoo"):
            raise TypeError(f"{type(m).__name__} has no .tocoo(); "
                            "expected a scipy.sparse matrix")
        coo = m.tocoo()
        return cls.from_parts(coo.row, coo.col, coo.data, coo.shape,
                              dtype=dtype)

    @classmethod
    def from_parts(cls, rowind, colind, values, shape,
                   dtype=None) -> "SparseMatrix":
        """Wrap raw COO triplets (duplicate coordinates are summed).

        Args:
          rowind/colind/values: equal-length 1D arrays of coordinates+values.
          shape: global (rows, cols).
          dtype: optionally convert values.

        Returns:
          A SparseMatrix over the triplets (densified lazily, on demand).

        Raises:
          ValueError: on length mismatches or out-of-range indices.
        """
        rowind = np.asarray(rowind, np.int64).ravel()
        colind = np.asarray(colind, np.int64).ravel()
        values = np.asarray(values).ravel()
        if dtype is not None:
            values = values.astype(dtype)
        if not (len(rowind) == len(colind) == len(values)):
            raise ValueError("rowind/colind/values lengths differ")
        rows, cols = shape
        if len(rowind) and (rowind.min() < 0 or rowind.max() >= rows
                            or colind.min() < 0 or colind.max() >= cols):
            raise ValueError(f"indices out of range for shape {tuple(shape)}")
        return cls(triplets=(rowind, colind, values), shape=(rows, cols),
                   dtype=values.dtype)

    @classmethod
    def from_format(cls, container) -> "SparseMatrix":
        """Wrap an existing CSR/COO/BCSR/BCOO container.

        Args:
          container: a :mod:`repro.core.formats` container instance; it is
            kept and reused when a plan requests the same format.

        Returns:
          A SparseMatrix over the container.

        Raises:
          TypeError: for any other container type.
        """
        if not isinstance(container, _CONTAINERS):
            raise TypeError(f"unknown container {type(container).__name__}")
        return cls(container=container, shape=container.shape,
                   dtype=np.dtype(container.dtype))

    # ------------------------------------------------------------ inspection

    @property
    def rows(self) -> int:
        return self.shape[0]

    @property
    def cols(self) -> int:
        return self.shape[1]

    def dense(self) -> np.ndarray:
        """Materialize (and cache) the dense host array."""
        if self._dense is None:
            if self._triplets is not None:
                ri, ci, vals = self._triplets
                a = np.zeros(self.shape, self.dtype)
                np.add.at(a, (ri, ci), vals)
            else:
                container = next(iter(self._containers.values()))
                a = np.asarray(F.to_dense(container))
            self._dense = a
        return self._dense

    @property
    def stats(self) -> MatrixStats:
        """Paper Table-4 statistics (drives the adaptive scheme selection)."""
        if self._stats is None:
            if self._dense is None and self._triplets is not None:
                ri, ci, _ = self._triplets
                self._stats = compute_stats((ri, ci, self.shape),
                                            block=self._stats_block)
            else:
                self._stats = compute_stats(self.dense(),
                                            block=self._stats_block)
        return self._stats

    @property
    def nnz(self) -> int:
        return self.stats.nnz

    def entries(self):
        """The triplets as stored entries: row-sorted ``(rowind, colind,
        values)``, each coordinate once, in the matrix dtype, never densified.

        Every triplet is an entry, a stored 0 included, and duplicate
        coordinates keep the least value: a (min, +) matrix's edges, where
        a 0 is an edge of weight 0 and of parallel edges only the lightest
        counts.

        Raises:
          ValueError: on a matrix not built from triplets (``from_parts`` /
            ``from_scipy``): a dense array cannot say which zeros are edges.
        """
        if self._triplets is None:
            raise ValueError(
                "a (min, +) matrix needs its triplets (SparseMatrix.from_parts "
                "or from_scipy): a dense array cannot say which zeros are "
                "edges")
        if self._entries is None:
            ri, ci, vals = self._triplets
            order = np.lexsort((vals.astype(np.float64), ci, ri))
            ri, ci, vals = ri[order], ci[order], vals[order]
            first = np.ones(len(ri), bool)
            first[1:] = (ri[1:] != ri[:-1]) | (ci[1:] != ci[:-1])
            # sorted by value within a coordinate: its first is its least
            self._entries = (ri[first], ci[first],
                             vals[first].astype(self.dtype, copy=False))
        return self._entries

    def fingerprint(self, semiring: str = "plus_times") -> str:
        """Content hash — the identity under which compiled plans are cached.

        Under ``plus_times`` it hashes the dense array's nonzeros
        (:func:`fingerprint_matrix`); under another semiring, the semiring's
        name and its stored entries (:meth:`entries`), so the same triplets
        under two semirings, or with and without a stored 0, never share a
        compiled plan.

        Raises:
          ValueError: for an unknown semiring, or another than (+, x) on a
            matrix not built from triplets (:meth:`entries`).
        """
        lookup(semiring)
        fp = self._fingerprints.get(semiring)
        if fp is None:
            if semiring == "plus_times":
                fp = fingerprint_matrix(self.dense())
            else:
                fp = _fingerprint_entries(semiring, self.shape,
                                          *self.entries())
            self._fingerprints[semiring] = fp
        return fp

    def container(self, fmt: str, block: Tuple[int, int] = (8, 16),
                  dtype=None):
        """Build (and cache) the requested container format.

        Args:
          fmt: "csr" | "coo" | "bcsr" | "bcoo".
          block: (r, c) tile shape for the block formats.
          dtype: value dtype of the built container (default: matrix dtype).

        Returns:
          The :mod:`repro.core.formats` container (cached per fmt/dtype).

        Raises:
          ValueError: for an unknown ``fmt``.
        """
        dtype = self.dtype if dtype is None else np.dtype(dtype)
        key = fmt if dtype == self.dtype else f"{fmt}:{dtype.str}"
        got = self._containers.get(key)
        if got is not None and (fmt not in ("bcsr", "bcoo")
                                or got.block == tuple(block)):
            return got
        a = self.dense()
        if a.dtype != dtype:
            a = a.astype(dtype)
        if fmt == "csr":
            built = F.dense_to_csr(a)
        elif fmt == "coo":
            built = F.dense_to_coo(a)
        elif fmt == "bcsr":
            built = F.dense_to_bcsr(a, block=tuple(block))
        elif fmt == "bcoo":
            built = F.dense_to_bcoo(a, block=tuple(block))
        else:
            raise ValueError(f"unknown format {fmt!r}")
        self._containers[key] = built
        return built

    def __repr__(self) -> str:
        return (f"SparseMatrix({self.rows}x{self.cols}, nnz={self.nnz}, "
                f"dtype={self.dtype.name})")

    # ------------------------------------------------------------ planning

    def plan(
        self,
        *,
        scheme="auto",
        impl: str = "xla",
        hw: Optional[HardwareModel] = None,
        mesh=None,
        devices=None,
        partitioning: Optional[str] = None,
        fmt: Optional[str] = None,
        merge: Optional[str] = None,
        grid: Optional[tuple] = None,
        block: Tuple[int, int] = (8, 16),
        fit: bool = True,
        tuner=None,
        tune_cache=None,
        batch: Optional[int] = None,
        topology=None,
        assignment=None,
        semiring: str = "plus_times",
    ) -> ExecutionPlan:
        """Resolve scheme + placement into an inspectable ExecutionPlan.

        Args:
          scheme: "auto" (paper Rec. #3 rules fitted to the pool), "tune"
            (measure candidates with :mod:`repro.tune` and return the
            empirically fastest), a string like "1d.nnz" /
            "2d.equally-sized", or an explicit adaptive.Plan.
          impl: "xla" (the jnp oracles; lower on every backend) or "pallas"
            (the TPU kernels: compiled on TPU, interpreted on CPU).  On TPU
            only the block formats compile, so ``"auto"`` picks one there.
            Both compose with ``mesh=``/``devices=``: distributed plans run
            the chosen impl as the per-shard tile kernel inside shard_map.
          hw: HardwareModel driving the analytic scheme selection/estimates.
          mesh/devices: give either to plan a distributed shard_map program;
            omit both for single-device execution.
          partitioning: force "1d"/"2d" over the adaptive choice.
          fmt/merge/grid: override single dimensions of the resolved scheme.
          block: (r, c) tile for the block formats and the stats blocking.
          fit: False inspects the paper plan for ``hw`` as-is, without
            fitting its grid to this pool (not compilable unless the pool
            happens to match).
          tuner: ``scheme="tune"`` only — a :class:`repro.tune.Tuner`
            override (bring your own generator/measurer/cache); the default
            tuner measures xla candidates of the requested ``impl`` with an
            in-memory cache.
          tune_cache: ``scheme="tune"`` only — a
            :class:`repro.tune.TuningCache` (or a path for one) so winners
            persist across processes; ignored when ``tuner`` is given.
          batch: ``scheme="tune"`` only — representative SpMM width B the
            candidates are measured at (part of the tuning-cache key).
          topology: a :class:`repro.topo.DeviceTopology` describing the
            physical axes behind the device pool.  2D grid fitting then
            ranks factorizations by modelled collective cost, the mesh is
            built with the contiguous-mesh device order of the cheapest
            axis assignment, and the plan records it (``topo_assignment``,
            ``describe()``, plan IR v2).  When neither ``mesh`` nor
            ``devices`` is given, the topology's own device grid implies
            the pool.  See docs/topology.md.
          assignment: force a specific axis assignment (an
            :class:`repro.topo.AxisAssignment` or its dict form) instead of
            the model's pick — how ``repro.tune`` measures one candidate
            per assignment.  Requires ``topology``.
          semiring: ``"plus_times"`` (y = A x) or ``"min_plus"``
            (y_i = min_j A_ij + x_j over the stored entries, a stored 0
            included).  ``min_plus`` needs a matrix built from triplets and
            runs on a mesh with a 1D scheme, a scalar format and
            impl="xla"; ``"auto"`` picks such a plan.

        Returns:
          An inspectable :class:`~repro.api.plan.ExecutionPlan`; call
          ``.compile()`` on it for an Executor.  For ``scheme="tune"`` the
          plan's ``measured`` dict (and ``describe()``) carry the measured
          winner-vs-analytic numbers.

        Raises:
          ValueError: unknown impl/scheme, both mesh= and devices= given,
            a user mesh whose shape the fitted plan cannot lay out on,
            impl="pallas" with a scalar format (coo/csr) on TPU, or a
            ``min_plus`` plan that is not a 1D scalar XLA plan on a mesh
            (:func:`~repro.api.plan.check_semiring`).
        """
        if impl not in ("xla", "pallas"):
            raise ValueError(f"unknown impl {impl!r}: 'xla' or 'pallas'")
        if mesh is not None and devices is not None:
            raise ValueError("pass mesh= or devices=, not both")
        if assignment is not None and topology is None:
            raise ValueError("assignment= requires topology=")
        if topology is not None and mesh is None and devices is None:
            # a topology with a bound device grid implies the pool
            devices = topology.flat_devices()
            if devices is None:
                raise ValueError(
                    "topology= is abstract (no devices); pass devices= too"
                )
        if scheme == "tune":
            if semiring != "plus_times":
                raise ValueError(f"semiring {semiring!r}: scheme='tune' "
                                 "measures (+, x) plans only")
            # measure-and-refine: delegate to repro.tune (lazy import — the
            # tuner itself plans through this very method)
            overrides = dict(partitioning=partitioning, fmt=fmt, merge=merge,
                             grid=grid)
            forced = [k for k, v in overrides.items() if v is not None]
            if forced:
                raise ValueError(
                    f"scheme='tune' searches {forced} itself; either drop "
                    "the override or constrain the search with a custom "
                    "tuner= (repro.tune.Tuner / CandidateGenerator)"
                )
            from repro.tune import CandidateGenerator, Tuner, TuningCache

            if tuner is None:
                cache = tune_cache
                if cache is not None and not isinstance(cache, TuningCache):
                    cache = TuningCache(path=cache)
                tuner = Tuner(
                    generator=CandidateGenerator(impls=(impl,)), cache=cache
                )
            return tuner.tune(
                self, devices=devices, mesh=mesh, block=block, hw=hw,
                batch=batch, topology=topology,
            ).best
        distributed = mesh is not None or devices is not None
        if mesh is not None:
            mesh_shape = tuple(mesh.devices.shape)
            n_devices = int(np.prod(mesh_shape))
            if grid is None and len(mesh_shape) == 2 \
                    and not isinstance(scheme, Plan):
                grid = mesh_shape  # prefer grids that match the given mesh
        elif devices is not None:
            devices = list(devices)
            n_devices = len(devices)
        else:
            n_devices = 1
        compiled_pallas = impl == "pallas" and not interpret_mode(
            mesh.devices.flat if mesh is not None else devices)
        plan = resolve_scheme(
            self.stats, self.shape, n_devices, scheme, hw=hw,
            partitioning=partitioning, fmt=fmt, merge=merge, grid=grid,
            block=block, fit=fit, topology=topology,
            dtype_bytes=self.dtype.itemsize, compiled_pallas=compiled_pallas,
            semiring=semiring,
        )
        if mesh is not None:
            # fail fast: the fitted plan must lay out on the given mesh, or
            # compile() would crash deep inside placement instead
            want = ((plan.grid[0],) if plan.partitioning == "1d"
                    else tuple(plan.grid))
            if mesh_shape != want:
                raise ValueError(
                    f"mesh shape {mesh_shape} does not match the "
                    f"{plan.partitioning} plan grid {tuple(plan.grid)}; "
                    "pass grid=/scheme= that fits the mesh, or use devices= "
                    "and let plan() build the mesh"
                )
        topo_assignment = None
        if mesh is None and distributed:
            mesh_shape = ((plan.grid[0],) if plan.partitioning == "1d"
                          else tuple(plan.grid))
            axes = (AXIS_1D,) if plan.partitioning == "1d" else AXES_2D
            n = int(np.prod(mesh_shape))
            if topology is not None:
                from repro import topo as _topo

                model = _topo.CollectiveCostModel(topology)
                chosen, price = assignment, None
                if chosen is None:
                    best = model.best(plan, self.shape, self.dtype.itemsize,
                                      axes)
                    if best is not None:
                        chosen, price = best
                mesh, chosen = _topo.build_mesh(
                    topology, mesh_shape, axes, assignment=chosen,
                    devices=devices[:n],
                )
                if chosen is not None:
                    if price is None:
                        price = model.price(plan, self.shape,
                                            self.dtype.itemsize, chosen)
                    topo_assignment = {
                        **chosen.to_dict(),
                        "topology": topology.name,
                        "transfer": {k: float(v) for k, v in price.items()},
                    }
            else:
                mesh = compat.make_mesh(mesh_shape, axes, devices=devices[:n])
        hw = hw if hw is not None else HardwareModel(chips=max(1, n_devices))
        try:
            est = estimate_time(self.stats, plan, hw,
                                dtype_bytes=self.dtype.itemsize)
        except Exception:
            est = {}
        if topo_assignment is not None:
            # expose the topology-priced transfer split next to the analytic
            # Fig.-4 numbers (describe() prints both; docs/topology.md)
            est = dict(est)
            est["topo_load_s"] = topo_assignment["transfer"]["load_s"]
            est["topo_merge_s"] = topo_assignment["transfer"]["merge_s"]
        return ExecutionPlan(
            matrix=self, scheme=plan, impl=impl,
            mesh=mesh if distributed else None, dtype=self.dtype,
            block=tuple(block), hw=hw, estimate=est,
            topo_assignment=topo_assignment, semiring=semiring,
        )

    def compile(self, **plan_kwargs):
        """Shorthand: ``.plan(**plan_kwargs).compile()``.

        Returns:
          An :class:`~repro.api.executor.Executor` ready to serve
          ``exe(x)`` / ``exe.batch(X)``.
        """
        return self.plan(**plan_kwargs).compile()
