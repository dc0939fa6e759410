"""SpmvEngine — register once, multiply many times.

The serving layer on top of the ``repro.api`` pipeline: ``register(name, a)``
runs ``SparseMatrix -> ExecutionPlan -> Executor`` a single time (stats ->
adaptive plan fitted to the device pool -> partition -> device placement ->
traced + jitted shard_map program) and parks the compiled executor in a
:class:`PlanCache`; ``multiply(name, x)`` afterwards only places x, runs the
cached executable and assembles the rows — zero re-partitioning, zero
re-tracing (per input shape), which is what makes repeated SpMV pay off
(paper §3.1, Gómez-Luna et al. §5 on amortizing DPU transfer cost).

The engine adapts the paper plan to the actual device pool: the adaptive
selector is asked for a scheme as if every local device were a PIM core, and
the resulting grid is fitted to the divisibility constraints of the 2D
schemes (falling back to 1D element-balanced COO, which always fits) — the
same ``repro.api.fit_plan`` rules every other entry point uses.
"""
from __future__ import annotations

import threading
from typing import Optional, Tuple

import numpy as np

from repro import compat
from repro.api import AXES_2D, AXIS_1D, SparseMatrix, resolve_scheme
from repro.api.plan import fit_plan
from repro.core.adaptive import HardwareModel, Plan
from repro.engine.plan_cache import CompiledPlan, PlanCache, PlanKey
from repro.engine.registry import MatrixRegistry, RegisteredMatrix
from repro.engine.telemetry import RequestRecord, Telemetry
from repro.kernels.mode import interpret_mode
from repro.obs import NULL_TRACE, phase

__all__ = ["SpmvEngine"]

_AXIS_1D = AXIS_1D
_AXES_2D = AXES_2D


class _PhaseSeconds(dict):
    """A Trace-shaped sink for :func:`repro.obs.phase`: keeps each phase's
    seconds under the last part of its name (``spmv.register.plan`` ->
    ``plan``)."""

    def add(self, name: str, start_s: float, end_s: float, **args) -> None:
        self[name.rsplit(".", 1)[1]] = end_s - start_s


class SpmvEngine:
    """Batched SpMV serving over a registry of named matrices."""

    def __init__(
        self,
        devices=None,
        cache_capacity: int = 8,
        telemetry: Optional[Telemetry] = None,
        block: Tuple[int, int] = (8, 16),
        hw: Optional[HardwareModel] = None,
        impl: str = "xla",
        tune: bool = False,
        tuner=None,
        tune_after: int = 8,
        tune_margin: float = 0.9,
        drift_factor: Optional[float] = 2.0,
        drift_alpha: float = 0.25,
        topology=None,
    ) -> None:
        """Create a serving engine over a device pool.

        Args:
          devices: JAX devices to serve from (default: all local devices).
          cache_capacity: max compiled plans held (LRU; placed matrices pin
            device memory, so this is the engine's memory bound).
          telemetry: a shared Telemetry sink (default: a fresh one).
          block: (r, c) block shape for the block formats and matrix stats.
          hw: HardwareModel driving adaptive scheme selection.
          impl: default local tile kernel for registered matrices — "xla"
            (oracles) or "pallas" (TPU kernels: compiled on TPU, where
            only the block formats compile, and interpreted on CPU).
            ``register(..., impl=...)`` overrides per matrix.
          tune: measure-and-refine plans in the background off live traffic
            (:mod:`repro.tune`): once a matrix has served ``tune_after``
            vectors, candidates are measured on its most recent input and
            the cached executor is atomically swapped when the winner beats
            the incumbent by the ``tune_margin`` factor.
          tuner: a :class:`repro.tune.Tuner` override (e.g. a persistent
            TuningCache, or a FakeMeasurer in tests).
          tune_after: vectors a matrix must serve before refinement starts.
          tune_margin: swap only when measured best < incumbent * margin
            (guards against measurement-noise flapping).
          drift_factor: re-tune a tuned entry when the EWMA of its served
            batch widths drifts this factor away (either direction) from
            the width it was tuned at — the serving-drift trigger.  None
            disables drift re-tuning (one refinement per entry, ever).
          drift_alpha: EWMA weight for the observed batch width.
          topology: a :class:`repro.topo.DeviceTopology` over the pool —
            2D grids are then fitted and placed by collective cost (mesh
            device order follows the cheapest axis assignment; see
            docs/topology.md) instead of flat device order.

        Raises:
          ValueError: for an unknown ``impl``, a ``tune_margin`` outside
            (0, 1], a ``drift_factor`` <= 1 or a ``drift_alpha`` outside
            (0, 1].
        """
        import jax

        if impl not in ("xla", "pallas"):
            raise ValueError(f"unknown impl {impl!r}: 'xla' or 'pallas'")
        if not 0.0 < tune_margin <= 1.0:
            raise ValueError(f"tune_margin must be in (0, 1]; got {tune_margin}")
        if drift_factor is not None and drift_factor <= 1.0:
            raise ValueError(
                f"drift_factor must be > 1 (or None to disable); "
                f"got {drift_factor}"
            )
        if not 0.0 < drift_alpha <= 1.0:
            raise ValueError(f"drift_alpha must be in (0, 1]; got {drift_alpha}")
        self.impl = impl
        self.topology = topology
        if devices is None and topology is not None:
            devices = topology.flat_devices()
        self.devices = list(devices) if devices is not None else jax.devices()
        self.cache = PlanCache(cache_capacity)
        self.registry = MatrixRegistry()
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.block = block
        self.hw = hw if hw is not None else HardwareModel(chips=len(self.devices))
        self.partition_count = 0  # host preprocessing runs (cache misses)
        self._meshes: dict = {}
        self.tune = tune
        self.tune_after = tune_after
        self.tune_margin = tune_margin
        self.drift_factor = drift_factor
        self.drift_alpha = drift_alpha
        self._tuner = tuner
        self.tune_events: list = []  # refinement outcomes, append-only
        self._swap_lock = threading.Lock()  # registry/cache swap atomicity
        self._tuning: set = set()  # names with a refinement in flight
        self._tune_threads: list = []
        # eviction spills the host-side partition to the registry entry so
        # reactivate() re-places without re-partitioning (let alone
        # rebuilding from dense)
        self.cache.on_evict = self._spill_evicted

    # ------------------------------------------------------------------ mesh

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def _mesh(self, shape: tuple, axes: tuple):
        key = (shape, axes)
        if key not in self._meshes:
            n = int(np.prod(shape))
            self._meshes[key] = compat.make_mesh(
                shape, axes, devices=self.devices[:n]
            )
        return self._meshes[key]

    # ------------------------------------------------------------ plan fitting

    def _fit_plan(self, plan: Plan, shape: tuple, dtype) -> Plan:
        """Adapt the paper plan to the device pool (api.fit_plan rules)."""
        return fit_plan(plan, shape, self.n_devices, self.block,
                        topology=self.topology,
                        dtype_bytes=np.dtype(dtype).itemsize)

    # -------------------------------------------------------------- building

    def _spill_evicted(self, compiled: CompiledPlan) -> None:
        """PlanCache eviction hook: keep the host-side PartitionedMatrix on
        every registry entry the evicted plan was serving, so reactivation
        replans with zero re-partitioning (the device arrays still go).
        Iterates a snapshot: register()/unregister() may mutate the registry
        from another thread while a background swap evicts."""
        for entry in list(self.registry):
            if entry.cache_key == compiled.key:
                entry.spill = compiled.part

    def _plan(self, sm: SparseMatrix, plan: Plan, impl: str, assignment,
              semiring: str):
        """The ExecutionPlan of ``plan`` on this engine's pool."""
        if self.topology is not None:
            # let plan() place the mesh by collective cost (device order
            # follows the cheapest axis assignment; docs/topology.md)
            return sm.plan(
                scheme=plan, devices=self.devices, topology=self.topology,
                impl=impl, block=self.block, hw=self.hw,
                assignment=assignment, semiring=semiring,
            )
        if plan.partitioning == "1d":
            mesh = self._mesh((plan.grid[0],), (_AXIS_1D,))
        else:
            mesh = self._mesh(tuple(plan.grid), _AXES_2D)
        return sm.plan(scheme=plan, mesh=mesh, impl=impl, block=self.block,
                       hw=self.hw, semiring=semiring)

    def _build(self, sm: SparseMatrix, plan: Plan, key: PlanKey,
               impl: str, part=None, assignment=None, trace=NULL_TRACE,
               scope: str = "spmv.compile",
               semiring: str = "plus_times") -> CompiledPlan:
        """Run the api chain once for ``plan`` and wrap the MeshExecutor.

        ``part`` short-circuits host partitioning with a spilled
        PartitionedMatrix (reactivation after eviction): the build then
        only re-places and re-traces.  ``assignment`` pins a measured axis
        assignment (tuned winners) instead of the cost model's pick.  The
        phases ``<scope>.partition`` (host partition) and ``<scope>.place``
        (the matrix put on the mesh and the program built; the transfer
        may still run after it) are recorded on ``trace``.
        """
        with phase(f"{scope}.partition", trace) as part_phase:
            ep = self._plan(sm, plan, impl, assignment, semiring)
            if part is not None:
                ep.part = part  # spilled host partition: skip re-partitioning
            else:
                ep.part = ep.partition()
                self.partition_count += 1
        with phase(f"{scope}.place", trace) as place_phase:
            exe = ep.compile()
        return CompiledPlan(
            key=key,
            impl=impl,
            plan=plan,
            part=exe.part,
            arrays=exe.arrays,
            run=exe.run,
            mesh=exe.mesh,
            axes=tuple(exe.axes),
            x_spec=exe.x_spec,
            x_pad=exe.x_pad,
            trace_count_fn=exe.trace_count_fn,
            build_seconds=place_phase.end_s - part_phase.start_s,
            assemble_meta=exe.assemble_meta,
            executor=exe,
        )

    # ------------------------------------------------------------ public API

    def register(
        self,
        name: str,
        a: Optional[np.ndarray] = None,
        *,
        dtype=None,
        plan: Optional[Plan] = None,
        partitioning: Optional[str] = None,
        warmup: bool = True,
        impl: Optional[str] = None,
        semiring: str = "plus_times",
    ) -> RegisteredMatrix:
        """Fingerprint, plan, partition, place and compile ``a`` under ``name``.

        Identical matrices (same fingerprint) registered again — under the
        same or another name — reuse the cached executable.

        Args:
          name: serving handle for :meth:`multiply`.
          a: dense host matrix (2D) — or None to re-register ``name`` from
            the host-side SparseMatrix the registry kept (the spill-cache
            path: stats, fingerprint and containers are already cached, so
            nothing is rebuilt from dense; an eviction-spilled partition
            additionally skips re-partitioning).
          dtype: optionally convert values before planning.
          plan: explicit adaptive.Plan override (still fitted to the pool).
          partitioning: force "1d"/"2d" over the adaptive choice.
          warmup: trace + compile the vector-shaped program now, off the
            request path.
          impl: local tile kernel override — "xla" or "pallas"; default is
            the engine-wide ``self.impl``.  Pallas plans carry their chunk
            plans in the cached placement, so the micro-batched SpMM path
            runs the lane-tiled Pallas kernels end to end.
          semiring: ``"plus_times"`` (y = A x) or ``"min_plus"``
            (y_i = min_j A_ij + x_j: shortest-path relaxation, with
            ``solve(combine="sssp")``), fixed for the registration.  It
            enters the fingerprint, so the plan-cache key.  A ``min_plus``
            matrix comes from triplets (``a=None`` over a registry entry
            holding a ``SparseMatrix.from_parts`` matrix); its stored zeros
            are edges, and it is never densified.

        Returns:
          The RegisteredMatrix registry entry; its ``register_s`` holds the
          seconds of each phase that ran (the ``spmv.register.<phase>``
          annotations: stats, fingerprint, plan, partition, place, compile).

        Raises:
          ValueError: for a non-2D matrix, an unknown ``impl``, ``a=None``
            without a prior registration holding the host-side matrix, a
            dense ``a`` with ``semiring="min_plus"``, or a plan that cannot
            run the semiring (:func:`repro.api.plan.check_semiring`).
        """
        seconds = _PhaseSeconds()
        with phase("spmv.register", matrix=name):
            entry = self._register(name, a, dtype, plan, partitioning, warmup,
                                   impl, semiring, seconds)
        entry.register_s = dict(seconds)
        return entry

    def _register(self, name, a, dtype, plan, partitioning, warmup, impl,
                  semiring, seconds: _PhaseSeconds) -> RegisteredMatrix:
        if semiring != "plus_times" and a is not None:
            raise ValueError(
                f"semiring {semiring!r} needs the matrix as triplets: a dense "
                "array cannot say which zeros are edges; stage a "
                "SparseMatrix.from_parts matrix and register(name, None)")
        prior = self.registry.find(name)
        with phase("spmv.register.stats", seconds):
            sm = self._host_matrix(name, a, dtype, prior, semiring)
            impl = self.impl if impl is None else impl
            if impl not in ("xla", "pallas"):
                raise ValueError(f"unknown impl {impl!r}: 'xla' or 'pallas'")
            stats = sm.stats
        with phase("spmv.register.fingerprint", seconds):
            fp = sm.fingerprint(semiring)
        with phase("spmv.register.plan", seconds):
            plan = resolve_scheme(
                stats, sm.shape, self.n_devices,
                plan if plan is not None else "auto",
                hw=self.hw, partitioning=partitioning, block=self.block,
                compiled_pallas=(impl == "pallas"
                                 and not interpret_mode(self.devices)),
                semiring=semiring,
            )
            key: PlanKey = (fp, tuple(plan.grid), sm.dtype.str, plan.tag,
                            impl)
            with self._swap_lock:
                compiled = self.cache.get(key)
        if compiled is None:
            # an eviction-spilled partition for this exact plan identity
            # short-circuits host partitioning
            part = (prior.spill
                    if prior is not None and prior.cache_key == key else None)
            compiled = self._build(sm, plan, key, impl, part=part,
                                   trace=seconds, scope="spmv.register",
                                   semiring=semiring)
            with self._swap_lock:
                self.cache.put(compiled)
        entry = RegisteredMatrix(
            name=name,
            fingerprint=fp,
            shape=sm.shape,
            dtype=sm.dtype.str,
            stats=stats,
            plan=compiled.plan,
            cache_key=key,
            matrix=sm,  # host-side; lets the tuner + reactivation re-plan
            row_slices=compiled.executor.plan.row_slices,
            semiring=semiring,
        )
        # overwriting a name must not strand the old plan in the cache
        self.registry.add(entry)
        if prior is not None and prior.cache_key != key and not any(
            e.cache_key == prior.cache_key for e in self.registry
        ):
            with self._swap_lock:
                self.cache.evict(prior.cache_key)
        if warmup:
            with phase("spmv.register.compile", seconds):
                compiled.executor.warmup()
        return entry

    def _host_matrix(self, name, a, dtype, prior,
                     semiring: str = "plus_times") -> SparseMatrix:
        """The host-side SparseMatrix to register: built from ``a``, or the
        one a prior registration of ``name`` holds."""
        if a is None:
            if prior is None or prior.matrix is None:
                raise ValueError(
                    f"register({name!r}) without a matrix needs a prior "
                    "registration holding its host-side SparseMatrix"
                )
            sm = prior.matrix
            if dtype is not None and np.dtype(dtype) != sm.dtype:
                if semiring != "plus_times":  # never densified
                    return SparseMatrix.from_parts(
                        *sm.entries(), sm.shape, dtype=dtype)
                sm = SparseMatrix.from_dense(
                    sm.dense().astype(dtype), stats_block=self.block
                )
            return sm
        a = np.asarray(a)
        if dtype is not None:
            a = a.astype(dtype)
        if a.ndim != 2:
            raise ValueError(f"expected a 2D matrix, got shape {a.shape}")
        return SparseMatrix.from_dense(a, stats_block=self.block)

    def _compiled(self, entry: RegisteredMatrix) -> CompiledPlan:
        # lock: the background refine thread mutates the cache (put can
        # LRU-evict), and OrderedDict move_to_end racing popitem corrupts
        with self._swap_lock:
            compiled = self.cache.get(entry.cache_key)
        if compiled is None:
            raise RuntimeError(
                f"plan for {entry.name!r} was evicted from the cache; "
                f"reactivate({entry.name!r}) rebuilds it from the host-side "
                "spill (or grow cache_capacity)"
            )
        return compiled

    def reactivate(self, name: str, warmup: bool = True) -> RegisteredMatrix:
        """Rebuild the compiled plan for an evicted entry — cheaply.

        The registry keeps each entry's host-side ``SparseMatrix`` (stats,
        fingerprint, containers all cached) and, after an eviction, the
        spilled ``PartitionedMatrix``; reactivation therefore only re-places
        the partitions on the mesh and re-traces — no dense rebuild, no
        re-partitioning.  A no-op when the plan is still cached.

        Args:
          name: a registered matrix whose plan may have been evicted.
          warmup: trace the vector-shaped program now (off the request path).

        Returns:
          The (unchanged) registry entry, its plan compiled again.

        Raises:
          KeyError: unknown ``name``.
          ValueError: the entry predates spill support and has no host-side
            matrix to rebuild from.
        """
        entry = self.registry.get(name)
        with self._swap_lock:
            if self.cache.get(entry.cache_key) is not None:
                return entry  # still live; nothing to do
        if entry.matrix is None:
            raise ValueError(
                f"{name!r} carries no host-side SparseMatrix to reactivate "
                "from; re-register it with the dense matrix"
            )
        with phase("spmv.compile", matrix=name):
            built = self._build(entry.matrix, entry.plan, entry.cache_key,
                                entry.cache_key[4], part=entry.spill,
                                semiring=entry.semiring)
            with self._swap_lock:
                if self.cache.peek(entry.cache_key) is not None:
                    built.release()  # lost a race; the cached build wins
                    self.cache.get(entry.cache_key)
                else:
                    self.cache.put(built)
            entry.spill = None  # the live CompiledPlan owns the partition
            if warmup:
                with phase("spmv.compile.warm"):
                    self.plan_for(name).executor.warmup()
        return entry

    def multiply(self, name: str, x, *, obs=None) -> np.ndarray:
        """y = A @ x for registered ``name``.

        Serves from the cached executor: place x -> run the jitted program ->
        assemble rows; the three phase times land in telemetry (Fig.-17
        load/kernel/retrieve split).

        Args:
          name: handle from :meth:`register`.
          x: (cols,) vector, or (cols, B) for a batched SpMM request.
          obs: optional :class:`repro.obs.Trace` handle — or a sequence of
            them, one per rider of a coalesced batch — on which the three
            request-lifecycle spans (load/kernel/retrieve) of THIS execution
            are recorded: the program phases ``spmv.multiply.place`` /
            ``.run`` / ``.fetch`` stamp them.  Riders share
            the batch's phase timestamps: the batch ran once, and that once
            is each rider's kernel time.

        Returns:
          Host rows (rows[, B]).

        Raises:
          KeyError: unknown ``name``.
          RuntimeError: the plan was evicted from the cache (re-register).
          TypeError/ValueError: dtype or shape mismatch with the matrix.
        """
        entry = self.registry.get(name)
        cp = self._compiled(entry)
        exe = cp.executor
        x = np.asarray(x)
        batch = x.shape[1] if x.ndim == 2 else 1

        traces_before = cp.trace_count
        with phase("spmv.multiply", matrix=name, batch=batch):
            # load: validate dtype/shape, pad, put on mesh
            with phase("spmv.multiply.place", obs, span="load") as load:
                xs = exe.place(x)
            # kernel: the cached jitted shard_map program
            with phase("spmv.multiply.run", obs, span="kernel",
                       batch=batch) as run:
                raw = exe.run_raw(xs)
            # retrieve: fetch + assemble global rows
            with phase("spmv.multiply.fetch", obs, span="retrieve") as fetch:
                y = exe.assemble(raw)

        entry.requests += batch
        warm = cp.requests_served > 0
        cp.requests_served += 1
        self.telemetry.record(RequestRecord(
            name=name,
            batch=batch,
            load_s=load.seconds,
            kernel_s=run.seconds,
            retrieve_s=fetch.seconds,
            cache_hit=warm,
            traced=cp.trace_count > traces_before,
        ))
        if self.tune:
            entry.batch_ewma = (
                float(batch) if entry.batch_ewma is None
                else (1.0 - self.drift_alpha) * entry.batch_ewma
                + self.drift_alpha * batch
            )
            if entry.tuned and self._batch_drifted(entry):
                # the serving batch width left the regime the last tuning
                # measured: re-qualify the entry for a background re-tune
                entry.tuned = False
            if not entry.tuned:
                self._maybe_refine(entry, x)
        return y

    def solve(
        self,
        name: str,
        x0,
        *,
        steps: Optional[int] = None,
        tol: Optional[float] = None,
        combine="plain",
        b=None,
        diag=None,
        omega: float = 1.0,
        max_steps: int = 1000,
        check_every: int = 8,
        obs=None,
    ):
        """Run an on-device solver session over registered ``name``.

        One plan lookup, one compiled-loop launch
        (:meth:`repro.api.Executor.iterate` — x stays on device across all
        SpMVs), one Telemetry record for the whole session (``kind="solve"``
        with the step count, so per-iteration cost is ``rec.per_iter_s``;
        :meth:`Telemetry.last` keeps reporting per-multiply times).  An
        evicted plan is reactivated transparently from the host-side spill —
        a session never fails just because the LRU rotated.

        Args:
          name: handle from :meth:`register` (square matrices only).
          x0: (n,) start vector.
          steps / tol / combine / b / diag / omega / max_steps /
            check_every: forwarded to ``Executor.iterate``.
          obs: optional :class:`repro.obs.Trace` — the session's
            load / kernel / retrieve lifecycle spans are recorded on it by
            the phases ``spmv.solve.place`` / ``.loop`` / ``.fetch`` (kernel
            is the whole loop).

        Returns:
          :class:`repro.api.IterateResult`.

        Raises:
          KeyError: unknown ``name``.
          ValueError: non-square matrix, bad steps/tol/combine params.
          TypeError: x0 dtype mismatch.
        """
        entry = self.registry.get(name)
        try:
            cp = self._compiled(entry)
        except RuntimeError:
            # evicted mid-lifetime: rebuild from the spilled partition and
            # carry on — the session contract is one lookup, not one prayer
            self.reactivate(name, warmup=False)
            cp = self._compiled(entry)
        traces_before = cp.trace_count
        with phase("spmv.solve", matrix=name):
            result = cp.executor.iterate(
                x0, steps=steps, tol=tol, combine=combine, b=b, diag=diag,
                omega=omega, max_steps=max_steps, check_every=check_every,
                trace=obs,
            )
        entry.requests += result.steps  # a session is `steps` SpMVs of traffic
        warm = cp.requests_served > 0
        cp.requests_served += 1
        self.telemetry.record(RequestRecord(
            name=name,
            batch=1,
            load_s=result.load_s,
            kernel_s=result.kernel_s,
            retrieve_s=result.retrieve_s,
            cache_hit=warm,
            traced=result.compiled or cp.trace_count > traces_before,
            kind="solve",
            steps=result.steps,
        ))
        return result

    # --------------------------------------------------- measure-and-refine

    def _make_tuner(self):
        """Default background tuner: same-impl candidates, in-memory cache."""
        if self._tuner is None:
            from repro.tune import CandidateGenerator, Measurer, Tuner

            self._tuner = Tuner(
                generator=CandidateGenerator(impls=(self.impl,)),
                measurer=Measurer(warmup=1, iters=3),
            )
        return self._tuner

    def _batch_drifted(self, entry: RegisteredMatrix) -> bool:
        """Has the served batch width drifted drift_factor x away (either
        direction) from the width the entry was last tuned at?"""
        if self.drift_factor is None or entry.tuned_batch is None \
                or entry.batch_ewma is None:
            return False
        hi = max(entry.batch_ewma, entry.tuned_batch)
        lo = max(1e-9, min(entry.batch_ewma, entry.tuned_batch))
        return hi / lo >= self.drift_factor

    def _maybe_refine(self, entry: RegisteredMatrix, x) -> None:
        """Kick one background refinement per entry once traffic qualifies."""
        if entry.tuned or entry.requests < self.tune_after \
                or entry.name in self._tuning:  # unlocked fast path
            return
        trigger = "drift" if entry.tuned_batch is not None else "traffic"
        thread = threading.Thread(
            target=self._refine_bg, args=(entry.name, trigger),
            name=f"spmv-tune-{entry.name}", daemon=True,
        )
        with self._swap_lock:
            if entry.name in self._tuning or entry.tuned:
                return
            self._tuning.add(entry.name)
            # prune+append under the lock: concurrent triggers must not
            # lose a live thread reference (drain_tuning joins these)
            self._tune_threads = [
                t for t in self._tune_threads if t.is_alive()
            ] + [thread]
        # snapshot the triggering request only — not every request in
        # flight while the (possibly long) refinement runs
        entry.last_x = np.array(x)
        thread.start()

    def _refine_bg(self, name: str, trigger: str = "traffic") -> None:
        try:
            self.refine(name, trigger=trigger)
        except Exception as e:  # background thread: record, never propagate
            self.tune_events.append({
                "name": name, "swapped": False, "trigger": trigger,
                "error": f"{type(e).__name__}: {e}",
            })
            # one shot per entry, success or not: a persistently failing
            # refinement must not re-spawn (and re-compile every candidate)
            # on each subsequent request — which requires disarming the
            # drift trigger too, by anchoring tuned_batch at the width that
            # failed (only a NEW drift regime re-arms it, once)
            entry = self.registry.find(name)
            if entry is not None:
                entry.tuned = True
                if entry.batch_ewma is not None:
                    entry.tuned_batch = entry.batch_ewma
        finally:
            self._tuning.discard(name)

    def refine(self, name: str, x=None, trigger: str = "manual") -> dict:
        """Measure candidate plans for ``name`` and swap in a faster one.

        The incumbent plan is always among the measured candidates, so the
        decision is apples-to-apples on the same representative input: the
        most recent live vector (``entry.last_x``), or ``x`` when given, or
        the tuner's seeded synthetic input.  The executor swap is atomic
        with respect to :meth:`multiply`'s plan lookup — a request resolves
        either the old plan or the new one — and the superseded plan is
        evicted (device arrays freed) unless another registered name still
        shares it.  A request already mid-flight on the old executor when
        the swap lands hits the cache's documented eviction contract
        (deleted-array error; see :meth:`CompiledPlan.release`).

        Args:
          name: a registered matrix.
          x: representative input override, (cols,) or (cols, B).
          trigger: provenance recorded on the tune event — "manual",
            "traffic" (first qualification) or "drift" (batch-width
            re-tune).

        Returns:
          The tune event dict (also appended to ``self.tune_events``):
          winner/incumbent scheme ids, measured times, whether it swapped.

        Raises:
          KeyError: unknown ``name``.
          RuntimeError: the entry was registered by a pre-tune engine and
            carries no matrix to re-plan from.
          ValueError: the entry runs another semiring than (+, x); the tuner
            measures (+, x) candidates only.
        """
        entry = self.registry.get(name)
        if entry.semiring != "plus_times":
            raise ValueError(f"refine measures (+, x) plans only; {name!r} "
                             f"is registered {entry.semiring}")
        if entry.matrix is None:
            raise RuntimeError(
                f"{name!r} has no host-side SparseMatrix to tune from"
            )
        if x is None:
            x = entry.last_x
        batch = None
        if x is not None and getattr(x, "ndim", 1) == 2:
            batch = int(x.shape[1])
        tuner = self._make_tuner()
        result = tuner.tune(
            entry.matrix,
            devices=self.devices,
            block=self.block,
            hw=self.hw,
            batch=batch,
            x=x,
            baseline=(entry.plan, entry.cache_key[4]),
            topology=self.topology,
        )
        best, incumbent = result.best_measurement, result.baseline
        event = {
            "name": name,
            "trigger": trigger,
            "batch": batch,
            "incumbent": incumbent.scheme_id,
            "incumbent_s": incumbent.mean_s,
            "winner": best.scheme_id,
            "winner_impl": result.best.impl,
            "winner_s": best.mean_s,
            "speedup": result.speedup,
            "from_cache": result.from_cache,
            "swapped": False,
        }
        plan, impl = result.best.scheme, result.best.impl
        # the ExecutionPlan's scheme_id carries the axis-assignment suffix,
        # so a tuned placement of the same scheme gets its own cache slot
        scheme_id = result.best.scheme_id
        winner_assignment = result.best.topo_assignment
        key: PlanKey = (entry.fingerprint, tuple(plan.grid),
                        entry.dtype, scheme_id, impl)
        beats = best.mean_s < incumbent.mean_s * self.tune_margin
        if key != entry.cache_key and beats:
            # fast path: the winner is already compiled — swap under ONE
            # lock acquisition so the peeked plan cannot be evicted (and
            # released) between the lookup and the swap
            with self._swap_lock:
                if self.cache.peek(key) is not None:
                    # mark MRU: it is about to serve
                    self._swap_entry(entry, key, plan, self.cache.get(key))
                    event["swapped"] = True
            if not event["swapped"]:
                built = self._build(entry.matrix, plan, key, impl,
                                    assignment=winner_assignment)
                built.executor.warmup()  # trace off the request path
                with self._swap_lock:
                    if self.cache.peek(key) is not None:
                        built.release()  # lost a race; the cached one wins
                        self._swap_entry(entry, key, plan,
                                         self.cache.get(key))
                    else:
                        # evict-old before put: net-zero occupancy when the
                        # old key was unshared (the common case); a shared
                        # old key falls back to the normal LRU capacity
                        # contract on insert
                        self._swap_entry(entry, key, plan, built)
                        self.cache.put(built)
                event["swapped"] = True
        entry.tuned = True
        # anchor the drift detector at the *observed width EWMA*, not the
        # width of the one representative request: under a stationary
        # mixed-width stream (ewma ~2.5, coalesced batches of 1 or 8) a
        # per-request anchor would re-trigger drift forever; only a real
        # shift of the traffic mix should re-arm _batch_drifted
        entry.tuned_batch = (entry.batch_ewma if entry.batch_ewma is not None
                             else (float(batch) if batch else 1.0))
        entry.batch_ewma = entry.tuned_batch
        self.tune_events.append(event)
        return event

    def _swap_entry(self, entry: RegisteredMatrix, key: PlanKey,
                    plan: Plan, compiled: CompiledPlan) -> None:
        """Point ``entry`` at the new compiled plan and evict its old plan
        unless another registered name still shares it — net-zero cache
        occupancy, so a background swap never pushes a *different* matrix's
        only executable out of the LRU.  Caller holds ``_swap_lock``."""
        old_key, entry.cache_key, entry.plan = entry.cache_key, key, plan
        entry.row_slices = compiled.executor.plan.row_slices
        if old_key != key and not any(
            e.cache_key == old_key for e in self.registry
        ):
            self.cache.evict(old_key)

    def drain_tuning(self, timeout: float = 30.0) -> None:
        """Block until in-flight background refinements finish (tests)."""
        for thread in list(self._tune_threads):
            thread.join(timeout)
        self._tune_threads = [t for t in self._tune_threads if t.is_alive()]

    # -------------------------------------------------------- introspection

    def trace_count(self, name: str) -> int:
        """Traces of the compiled program serving ``name`` (test hook)."""
        cp = self.cache.peek(self.registry.get(name).cache_key)
        return cp.trace_count if cp is not None else 0

    def plan_for(self, name: str) -> Optional[CompiledPlan]:
        """The CompiledPlan serving ``name`` (None if evicted); does not
        touch LRU order."""
        return self.cache.peek(self.registry.get(name).cache_key)

    def unregister(self, name: str) -> None:
        """Drop ``name``; evicts its compiled plan unless another registered
        name still shares it (same fingerprint/scheme/impl)."""
        entry = self.registry.remove(name)
        if entry is not None and not any(
            e.cache_key == entry.cache_key for e in self.registry
        ):
            with self._swap_lock:
                self.cache.evict(entry.cache_key)
