"""One run of one cell: set-up, a measured window, the check, one result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Standard output: first a line naming the platform, device kind and device
count, then a line each on set-up and on the window, and last one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(with ``--trace 1`` also ``breakdown``) and, as its last key, ``checks``:
each compared number beside its limit.  The same numbers are the last lines
of standard error.  With ``--trace 0`` the metrics are the cell's
end-to-end metrics; with ``--trace 1`` a short window runs under the
profiler and the metrics are the cell's per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import resource
import shutil
import sys
import time
from dataclasses import dataclass

import numpy as np

from . import manifest as M
from . import program, reference
from .drivers import Data
from .trace import WINDOW_SPAN, TraceSummary, find_xplane, reduce_trace

TRACE_DIR = M.ROOT / "bench" / ".out" / "trace"
TRACE_SECONDS = 3.0  # the traced window, at most
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class RunRecord:
    """What the metric readers read (``bench/metrics/<name>.py``)."""

    cell: str
    nnz: int
    shape: tuple
    itemsize: int
    device_kind: str
    setup: dict
    window: dict
    trace: TraceSummary = None


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_for(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if require_chip and (platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {platform} device(s)")
    return devs, devs[:chips]


class CompileCounter:
    """Counts JAX traces and backend compiles while ``on``."""

    def __init__(self):
        import jax.monitoring

        self.on = False
        self.count = 0

        def listen(event, duration, **kw):
            if self.on and event in COMPILE_EVENTS:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def enable_cache() -> str:
    import jax
    from repro.runtime.cache import enable_compilation_cache

    path = enable_compilation_cache()
    # keep every program, however quick to compile, so a second run of a
    # cell in this checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def measure(driver, seconds: float, trace: bool, counter: CompileCounter):
    """Run the window; under the profiler when ``trace``."""
    import jax

    counter.on = True
    try:
        if not trace:
            return driver.window(seconds), None
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only, no Python frames
        with jax.profiler.trace(str(TRACE_DIR), profiler_options=options):
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                window = driver.window(seconds)
        return window, reduce_trace(find_xplane(str(TRACE_DIR)))
    finally:
        counter.on = False


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def run(args, t_start: float, *, require_chip: bool = True,
        config_params: dict = None, dtype=None, compile_cache: bool = True,
        out=None, err=None) -> dict:
    """Run one cell; print and return the result dict.

    ``config_params`` overrides the configuration's sizes and ``dtype`` its
    precision (tests run small cells on the CPU, and the control runs the
    program in a lower precision); the benchmark itself passes neither.
    Tests also turn the persistent compilation cache off.

    Raises:
      NoChip: when ``require_chip`` and JAX finds no TPU or too few chips.
    """
    out = sys.stdout if out is None else out
    err = sys.stderr if err is None else err

    def emit(obj, stream=out):
        print(json.dumps(obj), file=stream, flush=True)

    cell = M.resolve(args.workload)
    devs, used = devices_for(cell.chips, require_chip)
    emit({"platform": devs[0].platform, "device_kind": devs[0].device_kind,
          "device_count": len(devs), "chips_used": len(used)})
    cache_dir = enable_cache() if compile_cache else None
    counter = CompileCounter()

    spans = {}
    t = time.perf_counter()
    params = dict(cell.config["params"], **(config_params or {}))
    gen = M.generator(cell.config["generator"])
    rows, cols, vals, shape = gen.triplets(params, args.seed)
    data = Data(rows, cols, vals, shape, dtype or cell.config["dtype"],
                args.seed, gen, params)
    driver = M.driver(cell.traffic["driver"])(cell.traffic, data, used)
    sm = program.sparse_matrix(rows, cols, vals, shape, data.dtype)
    spans["data_s"] = time.perf_counter() - t
    driver.setup(spans, sm)
    del sm
    # a full collection inside the window would traverse every object
    # set-up left (about 0.1 s); the window's own objects are still counted
    gc.collect()
    gc.freeze()
    spans["setup_s"] = time.perf_counter() - t_start
    emit({"setup": spans, "cache_dir": cache_dir})

    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, TRACE_SECONDS)
    window, trace = measure(driver, seconds, bool(args.trace), counter)
    mem = peak_bytes(used)
    driver.close()
    gc.collect()
    info = {k: v for k, v in window.items()
            if not isinstance(v, (list, np.ndarray))}
    info["compiles_in_window"] = counter.count
    info["host_peak_rss_bytes"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024
    emit({"window": info})

    ref = reference.Reference(rows, cols, vals, shape)
    numbers = driver.check(ref)
    limits = cell.spec["limits"]
    # a unit that raised, or broke the configuration's promise (a solve
    # that did not converge), makes the run not correct
    checks = {"failed": {"value": int(window["failed"]), "limit": 0}}
    checks.update({k: {"value": v, "limit": limits[k]}
                   for k, v in numbers.items()})
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    record = RunRecord(cell=cell.name, nnz=data.nnz, shape=data.shape,
                       itemsize=data.dtype.itemsize,
                       device_kind=devs[0].device_kind, setup=spans,
                       window=window, trace=trace)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = M.metric_reader(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": int(window["units"]),
              "failed": int(window["failed"]), "metrics": metrics,
              "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.device_ops,
                               "idle_gaps": trace.idle_gaps}
    result["checks"] = checks
    for e in driver.errors[:5]:
        print(f"error: {e}", file=err)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    emit(result)
    return result


def main(argv=None, t_start: float = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    try:
        run(args, t_start)
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 3
    return 0
