"""Benchmark harness — one module per paper table/figure family.

    PYTHONPATH=src python -m benchmarks.run [--scale N] [--quick] [--smoke]
                                            [--tune] [--json PATH]

Prints ``name,us_per_call,derived`` CSV rows (one block per figure).
Mapping to the paper:
  fig9_single_core   Fig. 9/10/31: balancing & formats inside one core
  fig11_16_1d        Figs. 11-16: 1D schemes, kernel skew, e2e breakdown
  fig17_24_2d        Figs. 17-24: 2D padding/vertical-partition/format studies
  fig25_29_compare   Figs. 25-29: 1D-vs-2D winners + fraction-of-peak
  spmv_distributed   end-to-end distributed SpMV timings (8 fake devices,
                     subprocess, routed through repro.api; the LM-side
                     numbers live in §Roofline)

``--smoke`` is the CI wiring check: imports every benchmark module, runs the
single-core block on the Table-3 miniatures and one tiny api-routed
distributed matrix, all on CPU in a few minutes.

``--json PATH`` additionally writes the emitted CSV rows as machine-readable
JSON — the file CI uploads as an artifact and ``tools/check_bench.py``
compares against the committed ``BENCH_smoke.json`` baseline, so the perf
trajectory is recorded instead of scrolling away in logs.

``--tune`` runs the measure-and-refine loop (``repro.tune``) over the paper
suite instead of the figure blocks and writes ``BENCH_autotune.json``
(per matrix: the analytic pick, the measured-best pick, and the speedup).
"""
import argparse
import contextlib
import io
import json
import os
import subprocess
import sys

# The distributed block runs in a subprocess (fake-device forcing must happen
# before jax initializes) and goes through the repro.api pipeline — the same
# SparseMatrix -> plan -> compile chain users and the engine run.
_DISTRIBUTED_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import numpy as np, jax
from repro.api import SparseMatrix
from repro.data import paper_large_suite, paper_small_suite

smoke = os.environ.get("BENCH_SMOKE") == "1"
specs = paper_small_suite(1)[:1] if smoke \
    else paper_large_suite(1)[:4] + paper_large_suite(1)[-3:]
for spec in specs:
    sm = SparseMatrix.from_dense(spec.build())
    x = np.random.default_rng(0).standard_normal(sm.cols).astype(np.float32)
    for scheme, grid in [("1d.nnz", None), ("2d.equally-sized", (4, 2))]:
        exe = sm.plan(scheme=scheme, grid=grid,
                      devices=jax.devices()).compile()
        exe(x)  # warm the vector-shaped trace
        ts = []
        for _ in range(5):
            t0 = time.perf_counter(); exe(x)
            ts.append(time.perf_counter() - t0)
        # label from the FITTED plan: a non-divisible matrix may have fallen
        # back to 1D, and the row must say what actually ran
        derived = f"grid={'x'.join(map(str, exe.plan.grid))}"
        print(f"dist.{spec.name}.{exe.plan.scheme_id},"
              f"{np.median(ts)*1e6:.1f},{derived}")
"""


# The topo block prices + runs the SAME 2D plan under the model-picked and
# the worst axis assignment on a host-simulated two-axis PIM-like topology
# (repro.topo.FakeTopology.pim_like: a fast "bank" axis, a slow
# through-host-DRAM "host" axis).  CPU fake devices execute the kernel but
# not the interconnect, so each row's wall-clock is the measured kernel
# time PLUS the cost model's deterministic simulated transfer for that
# placement — the placement delta the row exists to track.  Runs in a
# subprocess: the 2x2 topology needs exactly 4 forced host devices.
_TOPO_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import time
import numpy as np, jax
from repro.api import SparseMatrix
from repro.data.matrices import regular_matrix
from repro.topo import CollectiveCostModel, FakeTopology

topo = FakeTopology.pim_like((2, 2), devices=jax.devices()[:4])
model = CollectiveCostModel(topo)

def wall(exe, x):
    exe(x)  # warm the trace
    ts = []
    for _ in range(7):
        t0 = time.perf_counter(); exe(x)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))

ratios = []
# tall: the partial-merge bytes dominate; wide: the x-broadcast bytes do —
# the model must steer each matrix's heavy direction onto the fast axis
# (they pick OPPOSITE assignments on the same topology)
for name, (rows, cols) in (("tall", (2048, 128)), ("wide", (128, 2048))):
    a = regular_matrix(rows, cols, 5, seed=3)
    sm = SparseMatrix.from_dense(a)
    x = np.random.default_rng(0).standard_normal(cols).astype(np.float32)
    ref = sm.plan(scheme="2d.equally-sized", grid=(2, 2), topology=topo)
    ranked = model.rank(ref.scheme, sm.shape, sm.dtype.itemsize, ref.axes)
    picks = (("model_pick",) + ranked[0], ("worst_axis",) + ranked[-1])
    totals = {}
    for label, assign, price in picks:
        plan = sm.plan(scheme="2d.equally-sized", grid=(2, 2),
                       topology=topo, assignment=assign)
        exe = plan.compile()
        y = np.asarray(exe(x))
        assert np.allclose(y, a @ x, rtol=1e-4, atol=1e-4), (name, label)
        kern_s = wall(exe, x)
        totals[label] = kern_s + price["total_s"]
        base = plan.scheme_id.split("@", 1)[0]
        print(f"topo.{name}.{base}.{label},{totals[label]*1e6:.1f},"
              f"assign={assign.tag} sim_us={price['total_s']*1e6:.1f} "
              f"kern_us={kern_s*1e6:.1f}")
    assert totals["model_pick"] <= totals["worst_axis"], (name, totals)
    ratios.append(totals["worst_axis"] / totals["model_pick"])
assert max(ratios) >= 1.2, f"placement indistinct: {ratios}"
print(f"# topo: model pick beats worst axis up to {max(ratios):.2f}x")
"""


def _topo_block():
    """Model-picked vs worst-axis placement rows on the fake PIM topology."""
    print("# --- topo: axis-assignment placement on FakeTopology.pim_like "
          "(repro.topo)")
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    proc = subprocess.run([sys.executable, "-c", _TOPO_CODE], env=env,
                          capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-2000:])
        raise SystemExit("topo benchmark failed")


def _distributed_block(smoke: bool = False):
    """Run the 8-device distributed api-pipeline timing in a subprocess."""
    print("# --- distributed: 1D/2D end-to-end on 8 fake devices (repro.api)")
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    if smoke:
        env["BENCH_SMOKE"] = "1"
    proc = subprocess.run([sys.executable, "-c", _DISTRIBUTED_CODE], env=env,
                          capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-2000:])
        raise SystemExit("distributed benchmark failed")


def _smoke() -> None:
    """CI wiring check: every module imports, two blocks actually run."""
    from . import (  # noqa: F401  (import = the wiring under test)
        common,
        engine_throughput,
        fig9_single_core,
        fig11_16_1d,
        fig17_24_2d,
        fig25_29_compare,
    )

    print("name,us_per_call,derived")
    fig9_single_core.run(1)
    _distributed_block(smoke=True)
    print("# smoke OK")


class _Tee(io.TextIOBase):
    """Mirror writes to the real stdout while keeping a copy for --json."""

    def __init__(self, real, copy):
        self.real, self.copy = real, copy

    def write(self, s):
        self.copy.write(s)
        return self.real.write(s)

    def flush(self):
        self.real.flush()


def _parse_rows(text: str) -> list:
    """``name,us_per_call,derived`` CSV lines -> row dicts (comments and the
    header are skipped; derived may itself contain commas)."""
    rows = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#") or line.startswith("name,"):
            continue
        parts = line.split(",", 2)
        if len(parts) < 2:
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows.append({
            "name": parts[0],
            "us_per_call": us,
            "derived": parts[2] if len(parts) > 2 else "",
        })
    return rows


def _write_json(path: str, mode: str, rows: list, extra: dict = None) -> None:
    doc = {"version": 1, "mode": mode, "rows": rows}
    if extra:
        doc.update(extra)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
    print(f"# wrote {path} ({len(rows)} rows)", file=sys.stderr)


def _tune_block(smoke: bool, json_path: str) -> None:
    """Measure-and-refine over the paper suite -> BENCH_autotune.json.

    Per matrix: the analytic ``scheme="auto"`` pick and the measured-best
    ``scheme="tune"`` pick, both with measured wall times, plus the speedup
    — the machine-readable proof that the tuner never does worse than the
    analytic model on this machine.
    """
    from repro.api import SparseMatrix
    from repro.data import paper_small_suite
    from repro.tune import Measurer, Tuner

    from .common import row

    specs = paper_small_suite(1)
    if smoke:
        specs = specs[:2]
    measurer = Measurer(warmup=1, iters=3) if smoke else Measurer()
    print("name,us_per_call,derived")
    print("# --- autotune: analytic pick vs measured winner (repro.tune)")
    results = []
    for spec in specs:
        sm = SparseMatrix.from_dense(spec.build())
        tuner = Tuner(measurer=measurer)
        res = tuner.tune(sm)
        best, base = res.best_measurement, res.baseline
        row(f"tune.{spec.name}.analytic.{base.scheme_id}",
            base.mean_s * 1e6, "analytic pick")
        row(f"tune.{spec.name}.tuned.{best.scheme_id}",
            best.mean_s * 1e6, f"speedup={res.speedup:.2f}x")
        results.append({
            "matrix": spec.name,
            "shape": list(sm.shape),
            "nnz": sm.nnz,
            "analytic": {
                "scheme_id": base.scheme_id,
                "mean_us": base.mean_s * 1e6,
            },
            "tuned": {
                "scheme_id": best.scheme_id,
                "impl": best.impl,
                "grid": list(best.grid),
                "mean_us": best.mean_s * 1e6,
                "compile_s": best.compile_s,
            },
            "speedup": res.speedup,
            "candidates": len(res.measurements),
        })
        assert best.mean_s <= base.mean_s, (
            f"tuned pick slower than the measured analytic pick on "
            f"{spec.name} — the argmin is broken"
        )
    _write_json(json_path, "tune", results)
    print("# tune OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=1)
    ap.add_argument("--quick", action="store_true",
                    help="skip the slower distributed block")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape CPU wiring check (CI)")
    ap.add_argument("--tune", action="store_true",
                    help="run the repro.tune measure-and-refine loop and "
                         "write BENCH_autotune.json")
    ap.add_argument("--topo", action="store_true",
                    help="also run the topology-placement block (topo.* "
                         "rows: model-picked vs worst axis assignment on "
                         "the host-simulated PIM topology)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the CSV rows as machine-readable JSON "
                         "(the CI perf artifact)")
    args = ap.parse_args()
    from repro.runtime.cache import enable_compilation_cache

    enable_compilation_cache()

    if args.tune:
        _tune_block(args.smoke, args.json or "BENCH_autotune.json")
        return

    if args.smoke:
        if args.json:
            copy = io.StringIO()
            with contextlib.redirect_stdout(_Tee(sys.stdout, copy)):
                _smoke()
                if args.topo:
                    _topo_block()
            _write_json(args.json, "smoke", _parse_rows(copy.getvalue()))
        else:
            _smoke()
            if args.topo:
                _topo_block()
        return

    from . import fig9_single_core, fig11_16_1d, fig17_24_2d, fig25_29_compare

    print("name,us_per_call,derived")
    fig9_single_core.run(args.scale)
    fig11_16_1d.run(args.scale)
    fig11_16_1d.run_scaling(scale=args.scale)
    fig17_24_2d.run(args.scale)
    fig25_29_compare.run(args.scale)
    if args.topo:
        _topo_block()
    if not args.quick:
        _distributed_block()


if __name__ == "__main__":
    main()
