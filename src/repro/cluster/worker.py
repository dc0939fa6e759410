"""Engine worker — one process, one SpmvEngine, one AF_UNIX listener.

The process analogue of a PIM rank: it owns a private device pool (its own
JAX runtime), a private :class:`~repro.engine.SpmvEngine`, and serves a
small verb set over the length-prefixed protocol in
:mod:`repro.cluster.protocol`:

  ``ping / register / multiply / solve / drain / stats / dump_trace /
  unregister / shutdown``

Plans arrive as IR, never as live objects: ``register`` accepts an
``ExecutionPlan.to_ir()`` record and rehydrates it against the worker's own
devices with :func:`repro.api.plan_from_ir`, and/or a ``tune_record`` — an
exported :class:`~repro.tune.TuningCache` slice — which the worker ingests
and replays through :class:`~repro.tune.Tuner` so the cached winner is
rebuilt with **zero re-measurements** (``from_cache=True``; the cache's
``hits`` counter is the auditable proof, surfaced by ``stats``).

Workers are spawned with the ``spawn`` start method (never ``fork``: the
parent may hold a live JAX runtime, and forked XLA state is undefined), so
``worker_main`` re-imports everything fresh in the child.  The heavyweight
imports happen inside the function for the same reason — importing this
module stays cheap for processes (routers, load generators) that never run
a worker loop themselves.
"""

from __future__ import annotations

import os
import socket
import threading
import traceback
from dataclasses import dataclass, field
from typing import Optional

from repro.cluster.protocol import (
    ConnectionClosed,
    WorkerClient,
    recv_msg,
    send_msg,
)

__all__ = ["WorkerConfig", "WorkerHandle", "worker_main", "spawn_worker",
           "worker_platform"]


@dataclass(frozen=True)
class WorkerConfig:
    """Everything a worker needs to build its engine (picklable: crosses
    the spawn boundary as a Process arg)."""

    worker_id: str
    impl: str = "xla"  # engine-default tile kernel ("xla" | "pallas")
    cache_capacity: int = 8  # compiled plans held per worker (LRU)
    tune_cache_path: Optional[str] = None  # shared TuningCache file; the
    # multi-writer safety lives in tune/cache.py (file lock + merge-on-write)
    trace_capacity: int = 16384  # per-worker span ring size


class _WorkerState:
    """The server side of one worker process (verb handlers + accounting)."""

    def __init__(self, config: WorkerConfig):
        # deferred heavyweight imports: only the worker process pays them
        from repro.engine import SpmvEngine
        from repro.obs import MetricsRegistry, Tracer
        from repro.tune import TuningCache

        self.config = config
        self.engine = SpmvEngine(
            cache_capacity=config.cache_capacity, impl=config.impl
        )
        self.metrics = MetricsRegistry()
        self.tracer = Tracer(capacity=config.trace_capacity)
        self.tune_cache = TuningCache(path=config.tune_cache_path)
        self.served = 0  # multiply verbs completed
        self._inflight = 0  # multiply verbs between recv and reply
        self._cv = threading.Condition()
        self.stopping = threading.Event()

    # ------------------------------------------------------------- verbs

    def ping(self, msg) -> dict:
        return {"worker_id": self.config.worker_id, "pid": os.getpid()}

    def register(self, msg) -> dict:
        """Plan + partition + place + compile one matrix on this worker.

        Fields: ``name`` (str), ``a`` (dense ndarray), optional ``dtype``,
        and the plan's provenance — exactly one of:

          * ``tune_record``: ``{"entries": {key: record}, "impls": [...],
            "batch": int|None, "block": [r, c]}`` — the exported TuningCache
            slice; ingested, then replayed through a Tuner whose only legal
            outcome here is a cache hit (zero re-measurements).
          * ``ir``: an ``ExecutionPlan.to_ir()`` dict, rehydrated against
            this worker's devices.
          * neither: the worker plans adaptively (``scheme``/
            ``partitioning`` overrides pass through to the engine).

        The reply reports ``source`` ("tune_cache" | "ir" | "fresh"), the
        fitted ``scheme_id``, and — on the tune path — ``from_cache`` plus
        the cache hit counters, so callers can *assert* nothing was
        re-measured.
        """
        import numpy as np

        from repro.api import SparseMatrix, plan_from_ir
        from repro.tune import CandidateGenerator, Measurer, Tuner

        name = msg["name"]
        a = np.asarray(msg["a"])
        dtype = msg.get("dtype")
        if dtype is not None:
            a = a.astype(dtype)
        ir = msg.get("ir")
        tune_record = msg.get("tune_record")
        info: dict = {"worker_id": self.config.worker_id, "name": name}
        if tune_record is not None:
            sm = SparseMatrix.from_dense(a, stats_block=self.engine.block)
            self.tune_cache.ingest(dict(tune_record.get("entries", {})))
            block = tuple(tune_record.get("block", self.engine.block))
            tuner = Tuner(
                generator=CandidateGenerator(
                    impls=tuple(tune_record.get("impls", (self.config.impl,)))
                ),
                measurer=Measurer(),
                cache=self.tune_cache,
            )
            hits0 = self.tune_cache.hits
            result = tuner.tune(
                sm,
                devices=self.engine.devices,
                block=block,
                hw=self.engine.hw,
                batch=tune_record.get("batch"),
            )
            entry = self.engine.register(
                name, a, plan=result.best.scheme, impl=result.best.impl,
            )
            info.update(
                source="tune_cache",
                from_cache=bool(result.from_cache),
                measurements=len(result.measurements),
                tune_hits=self.tune_cache.hits - hits0,
            )
        elif ir is not None:
            sm = SparseMatrix.from_dense(a, stats_block=self.engine.block)
            ep = plan_from_ir(ir, sm, devices=self.engine.devices)
            entry = self.engine.register(
                name, a, plan=ep.scheme, impl=ep.impl,
            )
            info.update(source="ir")
        else:
            entry = self.engine.register(
                name,
                a,
                plan=msg.get("scheme"),
                partitioning=msg.get("partitioning"),
                impl=msg.get("impl"),
            )
            info.update(source="fresh")
        self.metrics.counter("cluster.worker.registered").inc()
        info.update(
            fingerprint=entry.fingerprint,
            scheme_id=entry.plan.tag,
            impl=entry.cache_key[4],
            shape=tuple(entry.shape),
            dtype=entry.dtype,
        )
        return info

    def multiply(self, msg) -> dict:
        """y = A @ x through the engine, traced (load/kernel/retrieve).

        An optional ``cls`` field (the caller's SLO class, forwarded by
        the router) labels the lifecycle span and the per-class served
        counter — absent for older callers, defaulting to ``standard``.
        """
        import numpy as np

        name = msg["name"]
        cls = msg.get("cls", "standard")
        tr = self.tracer.trace(label=f"{self.config.worker_id}:{name}")
        with tr.span("serve", cls=cls):
            y = self.engine.multiply(name, np.asarray(msg["x"]), obs=tr)
        self.served += 1
        self.metrics.counter("cluster.worker.served").inc()
        self.metrics.counter("cluster.worker.served", cls=cls).inc()
        return {"y": y, "worker_id": self.config.worker_id}

    def solve(self, msg) -> dict:
        """A whole solver session on this worker's engine.

        A session is *atomic*: its iteration state lives only in this
        process, so it either completes here or dies with the worker —
        the router must reject (never resume) a session whose worker was
        lost mid-run.  Fields mirror ``SpmvEngine.solve``: ``name``,
        ``x0``, and optionally ``steps`` / ``tol`` / ``combine`` /
        ``b`` / ``diag`` / ``omega`` / ``max_steps`` / ``check_every``;
        an optional ``cls`` (the session's SLO class) labels the span and
        the per-class solved counter.
        """
        import numpy as np

        name = msg["name"]
        cls = msg.get("cls", "standard")
        kwargs = {}
        for k in ("steps", "tol", "combine", "omega", "max_steps",
                  "check_every"):
            if msg.get(k) is not None:
                kwargs[k] = msg[k]
        for k in ("b", "diag"):
            if msg.get(k) is not None:
                kwargs[k] = np.asarray(msg[k])
        tr = self.tracer.trace(label=f"{self.config.worker_id}:{name}:solve")
        with tr.span("serve", cls=cls):
            result = self.engine.solve(
                name, np.asarray(msg["x0"]), obs=tr, **kwargs
            )
        self.served += 1
        self.metrics.counter("cluster.worker.solved").inc()
        self.metrics.counter("cluster.worker.solved", cls=cls).inc()
        return {
            "x": np.asarray(result.x),
            "steps": int(result.steps),
            "converged": bool(result.converged),
            "residual": float(result.residual),
            "seconds": float(result.seconds),
            "worker_id": self.config.worker_id,
        }

    def drain(self, msg) -> dict:
        """Block until every in-flight multiply (other than us) completes."""
        timeout = float(msg.get("timeout", 30.0))
        with self._cv:
            ok = self._cv.wait_for(
                lambda: self._inflight == 0, timeout=timeout
            )
        self.engine.drain_tuning()
        return {"drained": ok, "inflight": self._inflight}

    def stats(self, msg) -> dict:
        return {
            "worker_id": self.config.worker_id,
            "pid": os.getpid(),
            "served": self.served,
            "registered": sorted(e.name for e in self.engine.registry),
            "entries": {
                e.name: e.summary() for e in self.engine.registry
            },
            "partition_count": self.engine.partition_count,
            "telemetry": self.engine.telemetry.breakdown(),
            "metrics": self.metrics.snapshot(),
            "tune_cache": {
                "hits": self.tune_cache.hits,
                "misses": self.tune_cache.misses,
                "entries": len(self.tune_cache),
            },
        }

    def dump_trace(self, msg) -> dict:
        """This worker's span buffer as one Chrome/Perfetto document."""
        from repro.obs import chrome_trace

        return chrome_trace(self.tracer.spans())

    def unregister(self, msg) -> dict:
        self.engine.unregister(msg["name"])
        return {"unregistered": msg["name"]}

    def shutdown(self, msg) -> dict:
        self.stopping.set()
        return {"stopping": True}

    # ----------------------------------------------------------- dispatch

    def handle(self, msg) -> dict:
        verb = msg.get("verb")
        handler = getattr(self, verb, None) if verb and not \
            verb.startswith("_") else None
        if handler is None or verb in ("handle", "serve_connection"):
            raise ValueError(f"unknown verb {verb!r}")
        if verb in ("multiply", "solve"):
            with self._cv:
                self._inflight += 1
            try:
                return handler(msg)
            finally:
                with self._cv:
                    self._inflight -= 1
                    self._cv.notify_all()
        return handler(msg)

    def serve_connection(self, conn: socket.socket) -> None:
        """Thread body: request/reply loop for one peer connection."""
        try:
            while not self.stopping.is_set():
                try:
                    msg = recv_msg(conn)
                except (ConnectionClosed, ValueError, OSError):
                    return  # peer hung up (or corrupted the stream): done
                try:
                    result = self.handle(msg)
                    reply = {"ok": True, "result": result}
                except Exception as e:  # verb failed; worker stays up
                    reply = {
                        "ok": False,
                        "error_type": type(e).__name__,
                        "error": str(e),
                        "traceback": traceback.format_exc(),
                    }
                try:
                    send_msg(conn, reply)
                except OSError:
                    return
        finally:
            try:
                conn.close()
            except OSError:
                pass


def worker_main(address: str, config: WorkerConfig) -> None:
    """Worker process entry point: bind, accept, serve until ``shutdown``.

    Runs in the spawned child.  One thread per connection (the router, each
    load generator and each chaos probe hold their own); ``shutdown`` stops
    the accept loop after the current replies flush.
    """
    state = _WorkerState(config)
    listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        os.unlink(address)
    except OSError:
        pass
    listener.bind(address)
    listener.listen(64)
    listener.settimeout(0.2)  # poll stopping between accepts
    threads = []
    try:
        while not state.stopping.is_set():
            try:
                conn, _ = listener.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            t = threading.Thread(
                target=state.serve_connection, args=(conn,), daemon=True
            )
            t.start()
            threads.append(t)
    finally:
        listener.close()
        try:
            os.unlink(address)
        except OSError:
            pass
        for t in threads:
            t.join(timeout=1.0)


@dataclass
class WorkerHandle:
    """Router-side handle: the child process + a control-plane client."""

    worker_id: str
    address: str
    process: object  # multiprocessing.Process (spawn context)
    client: WorkerClient
    lost: bool = False  # marked by the router on failover
    extra_clients: list = field(default_factory=list)

    def alive(self) -> bool:
        return self.process.is_alive()

    def connect(self, **kw) -> WorkerClient:
        """An additional data-plane connection (per-thread concurrency)."""
        c = WorkerClient(self.address, worker_id=self.worker_id, **kw)
        self.extra_clients.append(c)
        return c

    def kill(self) -> None:
        """SIGKILL the worker — the chaos hook behind the failover tests."""
        self.process.kill()
        self.process.join(timeout=10.0)

    def close(self, graceful: bool = True) -> None:
        if graceful and self.alive():
            try:
                self.client.request("shutdown")
            except Exception:
                pass
        for c in [self.client] + self.extra_clients:
            c.close()
        self.process.join(timeout=10.0)
        if self.process.is_alive():
            self.process.kill()
            self.process.join(timeout=10.0)
        try:
            os.unlink(self.address)
        except OSError:
            pass


def _report_platform(conn) -> None:
    """Probe-process body: send back the default backend and its device
    count, then exit (which releases every chip the probe opened)."""
    import jax

    platform = jax.default_backend()
    conn.send((platform, None if platform == "cpu" else len(jax.devices())))
    conn.close()


def worker_platform() -> tuple:
    """(platform, chips) a newly spawned worker would open.

    Answered without opening a device in this process: from this process's
    own backend when it is already initialised, from ``JAX_PLATFORMS`` when
    that names only the CPU, and otherwise by a short-lived ``spawn``-ed
    probe process that initialises JAX and exits.  ``chips`` is None on
    the CPU, which has no per-process limit, and 0 on an accelerator whose
    chips this process already holds.
    """
    import multiprocessing

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        import jax

        platform = jax.default_backend()
        return platform, (None if platform == "cpu" else 0)
    requested = os.environ.get("JAX_PLATFORMS", "")
    if requested and set(requested.split(",")) == {"cpu"}:
        return "cpu", None
    ctx = multiprocessing.get_context("spawn")
    recv, send = ctx.Pipe(duplex=False)
    probe = ctx.Process(target=_report_platform, args=(send,), daemon=True)
    probe.start()
    send.close()
    try:
        if not recv.poll(300.0):
            raise RuntimeError("the JAX platform probe did not answer")
        return recv.recv()
    finally:
        recv.close()
        probe.join(timeout=30.0)
        if probe.is_alive():
            probe.kill()
            probe.join(timeout=10.0)


def spawn_worker(
    worker_id: str,
    *,
    socket_dir: Optional[str] = None,
    connect_timeout: float = 120.0,
    **config_kw,
) -> WorkerHandle:
    """Spawn one engine worker and wait until it answers ``ping``.

    Uses the ``spawn`` start method: safe with a JAX-initialized parent,
    and the child inherits the parent's ``sys.path`` and environment (so
    ``XLA_FLAGS`` device forcing applies to every worker identically —
    which also keeps :func:`repro.tune.topology_key` consistent across the
    cluster, a prerequisite for shipped tune records to hit).

    Args:
      worker_id: cluster-unique identity (also the trace ``pid`` label).
      socket_dir: directory for the AF_UNIX socket (default: a fresh
        mkdtemp; AF_UNIX paths have a ~100-char limit, keep it short).
      connect_timeout: seconds to wait for the worker's first ping (the
        child pays a full JAX import before binding).
      **config_kw: WorkerConfig fields (impl, cache_capacity,
        tune_cache_path, trace_capacity).

    Returns:
      A live WorkerHandle (ping verified).
    """
    import multiprocessing
    import tempfile

    if socket_dir is None:
        socket_dir = tempfile.mkdtemp(prefix="repro-cluster-")
    address = os.path.join(socket_dir, f"{worker_id}.sock")
    config = WorkerConfig(worker_id=worker_id, **config_kw)
    ctx = multiprocessing.get_context("spawn")
    process = ctx.Process(
        target=worker_main, args=(address, config),
        name=f"repro-worker-{worker_id}", daemon=True,
    )
    process.start()
    client = WorkerClient(
        address, connect_timeout=connect_timeout, worker_id=worker_id
    )
    client.request("ping")
    return WorkerHandle(
        worker_id=worker_id, address=address, process=process, client=client
    )
