"""ExecutionPlan — the first-class, inspectable middle of the pipeline.

``SparseMatrix.plan(...)`` resolves *what to run* (an adaptive
:class:`repro.core.adaptive.Plan`: partitioning, balancing scheme, format,
merge collective, grid), fits it to the actual device pool, and returns an
:class:`ExecutionPlan` that additionally pins *how to run it* (impl, mesh,
dtype) plus the analytic time estimate.  ``.compile()`` turns it
into an :class:`repro.api.executor.Executor`.

This subsumes the two plan notions that predate it: ``adaptive.Plan`` (the
paper-rule scheme choice) is carried as ``.scheme``; the engine's internal
plan dict became the compiled executor's fields.  The fitting rules
(divisibility of 2D grids, CSR row-granularity limits, block-format
downgrades) live here so the engine, the benchmarks and direct api users all
agree on them.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import distributed as D
from repro.core.adaptive import HardwareModel, Plan, select_scheme
from repro.core.partition import (
    BALANCE_1D,
    SCHEMES_2D,
    PartitionedMatrix,
    partition_1d,
    partition_1d_coo,
    partition_2d,
)
from repro.core.semiring import lookup
from repro.core.stats import MatrixStats
from repro.kernels.mode import interpret_mode

from .executor import AXES_2D, AXIS_1D, Executor, MeshExecutor, SingleDeviceExecutor

__all__ = [
    "ExecutionPlan",
    "check_semiring",
    "fit_plan",
    "resolve_scheme",
    "plan_from_partitioned",
    "plan_from_ir",
    "IR_VERSION",
]

FORMATS = ("coo", "csr", "bcoo", "bcsr")
_BLOCK_FORMAT_OF = {"coo": "bcoo", "csr": "bcsr"}
_SCALAR_FORMATS = tuple(_BLOCK_FORMAT_OF)

# Plan-IR format version.  Bump when the IR schema changes shape in a way an
# older reader cannot interpret; ``plan_from_ir`` rejects unknown versions
# instead of guessing (docs/cluster.md#ir-versioning).
# v2 added the optional "topo" axis-assignment record (docs/topology.md);
# v1 payloads simply carry no placement metadata and still load.
IR_VERSION = 2
_IR_READABLE = (1, 2)


# ---------------------------------------------------------------------------
# scheme resolution + device fitting (shared by api users and the engine)
# ---------------------------------------------------------------------------


def _plan_from_string(spec: str, n_devices: int, fmt: Optional[str],
                      merge: Optional[str]) -> Plan:
    """Parse "1d" / "1d.nnz" / "2d" / "2d.equally-sized" into a Plan.

    The grid is left empty — ``fit_plan`` picks one for the device pool
    (near-square for 2D when the caller expressed no preference).
    """
    head, _, tail = spec.partition(".")
    fmt = fmt or "coo"
    if head == "1d":
        balance = tail or "nnz"
        if balance not in BALANCE_1D:
            raise ValueError(f"unknown 1D balance {balance!r}; one of {BALANCE_1D}")
        return Plan("1d", balance, fmt, merge or "ppermute", (n_devices, 1),
                    f"user scheme {spec!r}")
    if head == "2d":
        scheme = tail or "equally-sized"
        if scheme not in SCHEMES_2D:
            raise ValueError(f"unknown 2D scheme {scheme!r}; one of {SCHEMES_2D}")
        default = "psum_scatter" if scheme == "equally-sized" else "global"
        return Plan("2d", scheme, fmt, merge or default, (), f"user scheme {spec!r}")
    raise ValueError(
        f"unknown scheme {spec!r}: expected 'auto', '1d[.balance]', "
        f"'2d[.scheme]' or an adaptive.Plan"
    )


def fit_plan(plan: Plan, shape: tuple, n_devices: int,
             block: Tuple[int, int], *, topology=None,
             dtype_bytes: int = 4) -> Plan:
    """Adapt a paper plan to the device pool + SPMD divisibility rules.

    2D equally-sized requires rows % R == 0 and cols % C == 0 (and
    psum_scatter additionally (rows/R) % C == 0, else downgrade to psum);
    when no factorization of the device count fits, fall back to the 1D
    element-balanced plan, which has no divisibility constraints.  An empty
    ``plan.grid`` means "no preference" — 2D then prefers near-square grids,
    unless a :class:`repro.topo.DeviceTopology` is given, in which case the
    fitting grids are ranked by the modelled collective cost of each grid's
    *best* axis assignment (x-broadcast bytes x rows-axis cost + merge bytes
    x cols-axis cost; see docs/topology.md) and the cheapest wins —
    near-square only breaks ties.
    """
    n = n_devices
    rows, cols = shape
    fmt = plan.fmt
    if fmt in ("bcoo", "bcsr") and not (
        rows % block[0] == 0 and cols % block[1] == 0
    ):
        fmt = "coo"  # block tiling must cover the matrix exactly
    if plan.partitioning == "1d":
        balance = plan.scheme if plan.scheme in BALANCE_1D else "nnz"
        if fmt in ("csr", "bcsr") and balance == "nnz":
            balance = "nnz-rgrn"
        return Plan("1d", balance, fmt, "ppermute", (n, 1), plan.reason)
    # 2D: search factorizations of n, preferring the requested C (or a
    # near-square grid when the plan carries no grid preference)
    scheme = plan.scheme if plan.scheme in SCHEMES_2D else "equally-sized"
    want_c = plan.grid[1] if len(plan.grid) == 2 else None
    cands = sorted((r, n // r) for r in range(1, n + 1) if n % r == 0)
    if scheme == "equally-sized":
        fits = [(r, c) for r, c in cands if rows % r == 0 and cols % c == 0]
    elif scheme == "equally-wide":
        fits = [(r, c) for r, c in cands if cols % c == 0]
    else:  # variable-sized: no alignment constraints
        fits = cands
    if not fits:
        # element-granular 1D needs a COO-family format (row-sorted
        # csr/bcsr only balance at row granularity)
        return Plan(
            "1d", "nnz", "coo" if fmt in ("csr", "coo") else "bcoo",
            "ppermute", (n, 1),
            plan.reason + " [2d grid unfit for shape; 1d fallback]",
        )
    def _norm_merge(r: int, c: int) -> str:
        if scheme == "equally-sized":
            # "global" stays honored (the paper's faithful retrieve path);
            # anything else normalizes to the aligned in-network merges
            valid = ("psum", "psum_scatter", "global")
            m = plan.merge if plan.merge in valid else "psum"
            if m == "psum_scatter" and (rows // r) % c != 0:
                m = "psum"
            return m
        return "global"  # unaligned rows can only merge via the paper path

    if want_c is not None:
        R, C = min(fits, key=lambda rc: abs(rc[1] - want_c))
    elif topology is not None:
        from repro.topo import CollectiveCostModel

        model = CollectiveCostModel(topology)

        def _cost(rc):
            r, c = rc
            cand = Plan("2d", scheme, fmt, _norm_merge(r, c), (r, c),
                        plan.reason)
            best = model.best(cand, shape, dtype_bytes, AXES_2D)
            total = best[1]["total_s"] if best else float("inf")
            return (total, abs(r - c), r)

        R, C = min(fits, key=_cost)
    else:
        R, C = min(fits, key=lambda rc: abs(rc[0] - rc[1]))
    return Plan("2d", scheme, fmt, _norm_merge(R, C), (R, C), plan.reason)


def check_compiled_pallas(fmt: str) -> None:
    """Refuse a format whose Pallas kernel Mosaic does not compile for TPU.

    The scalar formats' windowed kernel streams ``(1, CHUNK_E)`` chunk
    blocks; Mosaic wants a block's last two dims divisible by 8 and 128.

    Raises:
      ValueError: for ``coo`` and ``csr``.
    """
    if fmt in _BLOCK_FORMAT_OF:
        from repro.kernels.coo_spmv import CHUNK_E

        raise ValueError(
            f"impl='pallas' with fmt={fmt!r} does not compile for TPU: "
            f"Mosaic refuses the kernel's (1, {CHUNK_E}) chunk blocks (a "
            "block's last two dims must divide by 8 and 128); use "
            "impl='xla', or a block format (bcoo/bcsr) on a shape the "
            "block tiles"
        )


def check_semiring(semiring: str, plan: Plan, *, impl: str,
                   distributed: bool, ring: bool = False, dtype=None) -> None:
    """Refuse a plan whose kernel cannot run ``semiring``.

    (+, x) runs everywhere.  Another semiring runs only on the XLA
    row-slice kernel (:func:`repro.kernels.row_slices.row_slice_spmv`) of a
    scalar format (coo/csr) under a 1D scheme on a mesh, whose boundary
    merge knows it (:func:`repro.core.distributed.spmv_1d`), with float
    values (its zero is +inf).

    Raises:
      ValueError: for an unknown semiring, or naming the semiring and each
        part of the plan that refuses it.
    """
    if lookup(semiring).name == "plus_times":
        return
    refused = []
    if not distributed:
        refused.append("a single-device plan (pass devices= or mesh=)")
    if plan.partitioning != "1d":
        refused.append(f"a {plan.partitioning} partition")
    if ring:
        refused.append("the 1D ring schedule")
    if plan.fmt not in _SCALAR_FORMATS:
        refused.append(f"fmt={plan.fmt!r}")
    if impl != "xla":
        refused.append(f"impl={impl!r}")
    if dtype is not None and not jnp.issubdtype(dtype, jnp.floating):
        refused.append(f"dtype {np.dtype(dtype).name}")
    if refused:
        raise ValueError(
            f"semiring {semiring!r} runs only on 1D coo/csr plans with "
            f"impl='xla' on a mesh; refused: {', '.join(refused)}")


def resolve_scheme(
    stats: MatrixStats,
    shape: tuple,
    n_devices: int,
    scheme="auto",
    *,
    hw: Optional[HardwareModel] = None,
    partitioning: Optional[str] = None,
    fmt: Optional[str] = None,
    merge: Optional[str] = None,
    grid: Optional[tuple] = None,
    block: Tuple[int, int] = (8, 16),
    fit: bool = True,
    topology=None,
    dtype_bytes: int = 4,
    compiled_pallas: bool = False,
    semiring: str = "plus_times",
) -> Plan:
    """Turn "auto" / a scheme string / an adaptive.Plan into a fitted Plan.

    ``topology`` (a :class:`repro.topo.DeviceTopology`) makes the 2D grid
    fitting collective-cost-aware — see :func:`fit_plan`.
    ``compiled_pallas`` says the plan runs Pallas kernels compiled for TPU:
    ``"auto"`` then picks the block counterpart of a scalar format, and a
    fitted scalar format raises (:func:`check_compiled_pallas`).  Under a
    ``semiring`` other than (+, x), ``"auto"`` picks 1D element-balanced
    COO unless the rules already chose a 1D scalar plan
    (:func:`check_semiring`).
    """
    hw = hw if hw is not None else HardwareModel(chips=max(1, n_devices))
    if isinstance(scheme, Plan):
        plan = scheme
    elif scheme == "auto":
        plan = select_scheme(stats, hw)
        if semiring != "plus_times" and (plan.partitioning != "1d" or
                                         plan.fmt not in _SCALAR_FORMATS):
            plan = Plan("1d", "nnz", "coo", "ppermute", (n_devices, 1),
                        f"{semiring}: 1D scalar plans only")
        if compiled_pallas:
            plan = replace(plan, fmt=_BLOCK_FORMAT_OF.get(plan.fmt, plan.fmt))
        if partitioning is not None and plan.partitioning != partitioning:
            if partitioning == "1d":
                plan = Plan("1d", "nnz", plan.fmt, "ppermute",
                            (n_devices, 1), "forced 1d")
            else:
                plan = Plan("2d", "equally-sized", plan.fmt, "psum_scatter",
                            plan.grid, "forced 2d")
    elif isinstance(scheme, str):
        plan = _plan_from_string(scheme, n_devices, fmt, merge)
    else:
        raise TypeError(f"scheme must be 'auto', a string or a Plan; got {scheme!r}")
    # single-dimension overrides apply to every scheme source (idempotent for
    # the string branch, which already baked them in)
    if fmt is not None:
        plan = replace(plan, fmt=fmt)
    if merge is not None:
        plan = replace(plan, merge=merge)
    if plan.fmt not in FORMATS:
        raise ValueError(f"unknown format {plan.fmt!r}; one of {FORMATS}")
    if grid is not None:
        plan = replace(plan, grid=tuple(grid))
    if fit:
        plan = fit_plan(plan, shape, n_devices, block, topology=topology,
                        dtype_bytes=dtype_bytes)
    if compiled_pallas:
        check_compiled_pallas(plan.fmt)
    return plan


# ---------------------------------------------------------------------------
# ExecutionPlan
# ---------------------------------------------------------------------------


@dataclass
class ExecutionPlan:
    """Everything needed to compile one SpMV program, inspectable up front."""

    matrix: object  # repro.api.matrix.SparseMatrix
    scheme: Plan  # fitted adaptive plan: partitioning/balance/fmt/merge/grid
    impl: str  # "xla" | "pallas"
    mesh: object | None  # None => single-device execution
    dtype: np.dtype
    block: Tuple[int, int] = (8, 16)
    hw: Optional[HardwareModel] = None
    estimate: dict = field(default_factory=dict)  # analytic Fig.-4 step times
    part: Optional[PartitionedMatrix] = None  # prebuilt partition (optional)
    ring: bool = False  # 1D ring schedule (requires bucketed part)
    ring_counts: Optional[np.ndarray] = None
    measured: dict = field(default_factory=dict)  # repro.tune measured truth
    # topology-aware placement metadata (repro.topo; None = flat placement):
    # {"logical": [...], "physical": [[...], ...], "topology": name,
    #  "transfer": {"load_s", "merge_s", "total_s"}}
    topo_assignment: Optional[dict] = None
    # the XLA scalar tile kernel's row-slice layout, set by compile():
    # {"width", "padded_share", "merged"} (repro.kernels.row_slices)
    row_slices: Optional[dict] = None
    semiring: str = "plus_times"  # "plus_times" | "min_plus"

    def __post_init__(self):
        check_semiring(self.semiring, self.scheme, impl=self.impl,
                       distributed=self.mesh is not None, ring=self.ring,
                       dtype=self.dtype)

    # -- inspection --------------------------------------------------------

    @property
    def partitioning(self) -> str:
        return self.scheme.partitioning

    @property
    def fmt(self) -> str:
        return self.scheme.fmt

    @property
    def grid(self) -> tuple:
        return tuple(self.scheme.grid)

    @property
    def merge(self) -> str:
        return self.scheme.merge

    @property
    def is_distributed(self) -> bool:
        return self.mesh is not None

    @property
    def scheme_id(self) -> str:
        """Stable scheme identity (part of the engine's plan-cache key).

        Topology-placed plans carry their axis assignment as an ``@`` suffix
        (e.g. ``...@rows=host,cols=bank``) so two placements of the same
        scheme never collide in plan caches or tuning records.
        """
        sid = self.scheme.tag + (".ring" if self.ring else "")
        if self.topo_assignment:
            phys = self.topo_assignment.get("physical") or ()
            logical = self.topo_assignment.get("logical") or ()
            sid += "@" + ",".join(
                f"{l}={'*'.join(g) if g else '-'}"
                for l, g in zip(logical, phys)
            )
        return sid

    def describe(self) -> str:
        """Human-readable one-plan summary (scheme, impl, placement, reason,
        analytic Fig.-4 estimate).  The exact output format is shown in
        docs/architecture.md.

        Returns:
          A multi-line string; stable enough to grep in tooling.
        """
        s = self.scheme
        where = (f"mesh{tuple(self.mesh.devices.shape)}" if self.is_distributed
                 else "single-device")
        semiring = ("" if self.semiring == "plus_times"
                    else f" semiring={self.semiring}")
        lines = [
            f"ExecutionPlan[{s.partitioning}.{s.scheme} fmt={s.fmt} "
            f"merge={s.merge} grid={tuple(s.grid)} impl={self.impl} "
            f"dtype={np.dtype(self.dtype).name}{semiring} {where}]",
            f"  reason: {s.reason}",
        ]
        if self.estimate:
            est = ", ".join(f"{k}={v:.2e}" for k, v in self.estimate.items())
            lines.append(f"  model estimate: {est}")
        if self.topo_assignment:
            ta = self.topo_assignment
            axes = ", ".join(
                f"{l}->{'*'.join(g) if g else '-'}"
                for l, g in zip(ta.get("logical") or (),
                                ta.get("physical") or ())
            )
            line = f"  topo: {axes} on {ta.get('topology', '?')}"
            tr = ta.get("transfer") or {}
            if tr:
                line += (f" (load={tr.get('load_s', 0.0):.2e}s "
                         f"merge={tr.get('merge_s', 0.0):.2e}s)")
            lines.append(line)
        if self.measured:
            m = self.measured
            line = f"  measured: {m['mean_s']:.2e}s/call"
            if m.get("candidates"):
                line += f" over {m['candidates']} candidates"
            if m.get("from_cache"):
                line += " (TuningCache hit)"
            base = m.get("baseline_mean_s")
            if base is not None:
                line += (f"; analytic pick {m.get('baseline_scheme_id')} "
                         f"measured {base:.2e}s ({m.get('speedup', 1.0):.2f}x)")
            lines.append(line)
        if self.row_slices:
            rs = self.row_slices
            lines.append(f"  row slices: w={rs['width']} "
                         f"padded={rs['padded_share']:.2%} "
                         f"merged={rs['merged']}/SpMV")
        return "\n".join(lines)

    # -- serialization (plan IR) -------------------------------------------

    def to_ir(self) -> dict:
        """Serialize everything needed to *rebuild* this plan elsewhere.

        The IR is a plain JSON/msgpack-able dict — no device arrays, no
        mesh object, no matrix payload — capturing scheme, impl, dtype,
        grid, block, mesh spec (shape + axis names), ring
        chunk counts, the analytic estimate and the tuned ``measured``
        metadata.  A worker process rehydrates it against its own device
        pool with :func:`plan_from_ir` and compiles locally; this is how
        plans (and :class:`repro.tune.TuningCache` winners riding in
        ``measured``) ship across processes instead of being replanned per
        worker (docs/cluster.md).

        Returns:
          A dict with ``ir_version`` = :data:`IR_VERSION`; stable under
          ``json.dumps`` round-trips.

        Raises:
          ValueError: for a plan carrying a prebuilt partition (``part``),
            which has no host-independent serial form.
        """
        if self.part is not None:
            raise ValueError(
                "plans wrapping a prebuilt PartitionedMatrix (part=...) "
                "cannot be serialized; re-plan from the SparseMatrix instead"
            )
        mesh_spec = None
        if self.is_distributed:
            mesh_spec = {
                "shape": [int(n) for n in self.mesh.devices.shape],
                "axes": [str(a) for a in self.axes],
            }
        return {
            "ir_version": IR_VERSION,
            "scheme": {
                "partitioning": self.scheme.partitioning,
                "scheme": self.scheme.scheme,
                "fmt": self.scheme.fmt,
                "merge": self.scheme.merge,
                "grid": [int(g) for g in self.scheme.grid],
                "reason": self.scheme.reason,
            },
            "impl": self.impl,
            "dtype": np.dtype(self.dtype).name,
            "block": [int(b) for b in self.block],
            "ring": bool(self.ring),
            "ring_counts": (None if self.ring_counts is None
                            else np.asarray(self.ring_counts).tolist()),
            "mesh": mesh_spec,
            "estimate": {k: float(v) for k, v in self.estimate.items()},
            "measured": _jsonable(self.measured),
            "topo": _jsonable(self.topo_assignment),
            "semiring": self.semiring,
        }

    # -- axes / specs ------------------------------------------------------

    @property
    def axes(self) -> tuple:
        if not self.is_distributed:
            return ()
        names = getattr(self.mesh, "axis_names", None)
        if names:
            return tuple(names)
        return (AXIS_1D,) if self.partitioning == "1d" else AXES_2D

    def _x_spec(self):
        axes = self.axes
        return P(axes[0]) if self.partitioning == "1d" else P(axes[1])

    def _x_pad(self, part: PartitionedMatrix) -> int:
        cols = part.shape[1]
        if self.partitioning == "1d":
            parts = part.n_parts
            return -(-cols // parts) * parts
        C = part.grid[1]
        # variable-sized tiles don't align with the uniform x shards, so the
        # program all-gathers + re-slices internally; pad x so the uniform
        # placement divides (the aligned schemes require cols % C)
        return cols if self.scheme.scheme != "variable-sized" else -(-cols // C) * C

    # -- compilation -------------------------------------------------------

    def partition(self) -> PartitionedMatrix:
        """The host partition of the matrix: ``part`` where the plan holds
        one, else partitioned from the dense matrix, or under a semiring
        other than (+, x) from its entries (a stored 0 stays an entry)."""
        if self.part is not None:
            return self.part
        if self.semiring != "plus_times":
            ri, ci, vals = self.matrix.entries()
            return partition_1d_coo(
                ri, ci, vals.astype(self.dtype, copy=False),
                self.matrix.shape, self.scheme.grid[0], fmt=self.fmt,
                balance=self.scheme.scheme)
        a = self.matrix.dense()
        if a.dtype != self.dtype:
            a = a.astype(self.dtype)
        if self.partitioning == "1d":
            return partition_1d(a, self.scheme.grid[0], fmt=self.fmt,
                                balance=self.scheme.scheme, block=self.block)
        return partition_2d(a, tuple(self.scheme.grid), fmt=self.fmt,
                            scheme=self.scheme.scheme, block=self.block)

    def _program(self, part: PartitionedMatrix):
        axes = self.axes
        if self.partitioning == "1d":
            if self.ring:
                if self.ring_counts is None:
                    raise ValueError("ring plans need ring_counts "
                                     "(see distributed.bucket_by_source_shard)")
                if self.impl != "xla":
                    raise ValueError("the 1D ring schedule runs the XLA local "
                                     "kernel only (impl='xla')")
                return D.spmv_1d_ring(part, self.ring_counts, self.mesh, axes[0])
            return D.spmv_1d(part, self.mesh, axes[0], impl=self.impl,
                             semiring=self.semiring)
        return D.spmv_2d(part, self.mesh, axes, merge=self.merge,
                         impl=self.impl)

    def placed_extra(self, part: PartitionedMatrix) -> Optional[dict]:
        """Host arrays of the tile kernel's layout, placed with the matrix:
        the row slices (impl="xla") or chunk plans (impl="pallas") of the
        scalar formats.  Block formats and the ring schedule run on the
        partition arrays as-is.  Records the row-slice counts in
        ``row_slices``."""
        if self.ring or self.fmt not in ("coo", "csr"):
            return None
        if self.impl == "pallas":
            return D.pallas_chunk_arrays(part)
        layout = D.slice_arrays(part, self.semiring)
        self.row_slices = layout.summary()
        return layout.arrays

    def program(self, part: Optional[PartitionedMatrix] = None):
        """Build the shard_map call object (with ``.jitted``) WITHOUT placing
        the matrix — what the dry-run lowers against abstract avals.

        Raises:
          ValueError: for single-device plans (no shard_map program exists).
        """
        if not self.is_distributed:
            raise ValueError("single-device plans have no shard_map program; "
                             "call .compile() instead")
        return self._program(part if part is not None else self.partition())

    def compile(self) -> Executor:
        """Partition (if needed), place and trace — returns the Executor.

        Single-device plans wrap the chosen container format in a
        :class:`~repro.api.executor.SingleDeviceExecutor` (for impl="pallas"
        the host-side kernel plan is built here, once).  Distributed plans
        partition, build the shard_map program with the selected local tile
        kernel (XLA or Pallas), place the matrix — plus, for the scalar
        formats, the kernel's per-shard layout (:meth:`placed_extra`) — and
        return a :class:`~repro.api.executor.MeshExecutor`.
        """
        import time as _time

        if not self.is_distributed:
            container = self.matrix.container(self.fmt, block=self.block,
                                              dtype=self.dtype)
            return SingleDeviceExecutor(self, container, self.impl)
        t0 = _time.perf_counter()
        part = self.partition()
        axes = self.axes
        program = self._program(part)
        extra = self.placed_extra(part)
        if self.partitioning == "1d":
            placed = D.place_1d(part, self.mesh, axes[0], extra=extra)
        else:
            placed = D.place_2d(part, self.mesh, axes, extra=extra)
        exe = MeshExecutor(
            self, part, self.mesh, axes, program,
            x_spec=self._x_spec(), x_pad=self._x_pad(part), merge=self.merge,
        ).place_matrix(placed)
        exe.build_seconds = _time.perf_counter() - t0
        return exe


def plan_from_partitioned(
    part: PartitionedMatrix,
    mesh,
    *,
    impl: str = "xla",
    merge: Optional[str] = None,
    ring: bool = False,
    ring_counts: Optional[np.ndarray] = None,
    matrix=None,
) -> ExecutionPlan:
    """Wrap an already-partitioned matrix (e.g. synthetic, never dense) in an
    ExecutionPlan so it flows through the same program-building path."""
    partitioning = "1d" if part.grid[1] == 1 else "2d"
    scheme_name = part.scheme.split(".", 1)[-1].replace("+ring", "")
    if merge is None:
        if partitioning == "1d":
            merge = "ppermute"
        else:
            merge = "psum" if scheme_name == "equally-sized" else "global"
    plan = Plan(partitioning, scheme_name, part.fmt, merge,
                tuple(part.grid), "prebuilt partition")
    return ExecutionPlan(
        matrix=matrix, scheme=plan, impl=impl, mesh=mesh,
        dtype=np.dtype(part.dtype), block=part.block, part=part,
        ring=ring, ring_counts=ring_counts,
    )


def _jsonable(obj):
    """Deep-copy ``obj`` into plain JSON types (dict/list/str/float/int/
    bool/None).  numpy scalars and arrays are converted; anything else is
    rejected loudly — a plan IR must never smuggle live objects."""
    if obj is None or isinstance(obj, (str, bool, int)):
        return obj
    if isinstance(obj, float):
        return float(obj)
    if isinstance(obj, np.generic):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    raise TypeError(f"not IR-serializable: {type(obj).__name__}: {obj!r}")


def plan_from_ir(ir: dict, matrix, *, devices=None, mesh=None,
                 hw: Optional[HardwareModel] = None,
                 topology=None) -> ExecutionPlan:
    """Rehydrate an :meth:`ExecutionPlan.to_ir` record against this process.

    The inverse of ``to_ir``: rebuilds the fitted adaptive plan verbatim (no
    re-fitting, no re-tuning — the IR *is* the already-fitted decision), lays
    the recorded mesh spec out on this process's devices, and reattaches the
    tuned ``measured`` metadata, so ``plan_from_ir(ir, sm).compile()``
    reproduces the original executor bit for bit with zero re-measurements.

    Args:
      ir: a ``to_ir()`` dict (possibly JSON round-tripped).
      matrix: the :class:`~repro.api.matrix.SparseMatrix` the plan is for
        (matrix payloads ship separately from plans; see docs/cluster.md).
      devices: device pool to lay the recorded mesh on (default: all local
        devices).  Ignored for single-device plans.
      mesh: an existing mesh matching the recorded spec (skips building one).
      hw: optional HardwareModel to attach (cosmetic; estimates ride the IR).
      topology: optional :class:`repro.topo.DeviceTopology` of *this*
        process — a v2 IR carrying an axis assignment is then re-realized
        with the recorded placement (device order follows the assignment)
        instead of flat order.  Without it the assignment still rides along
        as metadata (``scheme_id``/``describe()`` stay faithful) but the
        mesh uses flat device order.

    Returns:
      An :class:`ExecutionPlan` whose ``scheme_id``/``describe()`` match the
      serialized plan exactly.

    Raises:
      ValueError: unknown ``ir_version``, malformed record, too few
        devices for the recorded mesh shape, or a scalar-format Pallas
        plan loaded onto TPU devices (:func:`check_compiled_pallas`).
    """
    version = ir.get("ir_version")
    if version not in _IR_READABLE:
        raise ValueError(
            f"unknown plan-IR version {version!r} (this reader speaks "
            f"{_IR_READABLE}); re-export the plan with a matching writer"
        )
    try:
        s = ir["scheme"]
        plan = Plan(
            partitioning=s["partitioning"],
            scheme=s["scheme"],
            fmt=s["fmt"],
            merge=s["merge"],
            grid=tuple(int(g) for g in s["grid"]),
            reason=s.get("reason", "rehydrated from plan IR"),
        )
        impl = ir["impl"]
        dtype = np.dtype(ir["dtype"])
        block = tuple(int(b) for b in ir.get("block", (8, 16)))
        mesh_spec = ir.get("mesh")
    except (KeyError, TypeError) as e:
        raise ValueError(f"malformed plan IR: {type(e).__name__}: {e}") from e
    if plan.fmt not in FORMATS:
        raise ValueError(f"plan IR carries unknown format {plan.fmt!r}")
    if impl not in ("xla", "pallas"):
        raise ValueError(f"plan IR carries unknown impl {impl!r}")
    topo_assignment = ir.get("topo") or None
    if mesh is None and mesh_spec is not None:
        shape = tuple(int(n) for n in mesh_spec["shape"])
        axes = tuple(str(a) for a in mesh_spec["axes"])
        n = int(np.prod(shape))
        if devices is None:
            import jax

            devices = jax.devices()
        devices = list(devices)
        if len(devices) < n:
            raise ValueError(
                f"plan IR needs a {shape} mesh ({n} devices); this process "
                f"has {len(devices)} — re-fit the plan instead of rehydrating"
            )
        if topology is not None and topo_assignment is not None:
            from repro.topo import build_mesh

            mesh, _ = build_mesh(
                topology, shape, axes, devices=devices[:n],
                assignment={k: topo_assignment[k]
                            for k in ("logical", "physical")},
            )
        else:
            from repro import compat

            mesh = compat.make_mesh(shape, axes, devices=devices[:n])
    if mesh_spec is None:
        mesh = None
    if impl == "pallas" and not interpret_mode(
            None if mesh is None else mesh.devices.flat):
        check_compiled_pallas(plan.fmt)
    ring_counts = ir.get("ring_counts")
    return ExecutionPlan(
        matrix=matrix,
        scheme=plan,
        impl=impl,
        mesh=mesh,
        dtype=dtype,
        block=block,
        hw=hw,
        estimate=dict(ir.get("estimate") or {}),
        ring=bool(ir.get("ring", False)),
        ring_counts=(None if ring_counts is None
                     else np.asarray(ring_counts, dtype=np.int64)),
        measured=dict(ir.get("measured") or {}),
        topo_assignment=topo_assignment,
        semiring=ir.get("semiring", "plus_times"),
    )
