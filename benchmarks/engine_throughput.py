"""Steady-state serving throughput: engine vs one-shot SpMV.

Measures requests/sec for three request paths on the paper_small_suite
matrix classes:

  * one-shot   — the pre-engine pipeline: stats + partition + place + trace
                 on EVERY request (what examples/spmv_end_to_end.py does),
  * engine     — SpmvEngine steady state: cached plan, one vector per call,
  * engine+B   — the micro-batched path: B requests coalesced into one SpMM.

``--impl pallas`` serves every request through the Pallas tile kernels
(interpret mode off-TPU) and adds an explicit batched-SpMM vs per-column-
SpMV comparison: the same B right-hand sides issued as one lane-tiled SpMM
versus B single-vector kernel calls — the win the multi-RHS kernel grid
exists for (matrix traffic paid once per batch, Gómez-Luna et al. §5).

Prints the usual ``name,us_per_call,derived`` CSV rows plus the Fig.-17-style
load/kernel/retrieve split the telemetry records for each matrix.

    PYTHONPATH=src python -m benchmarks.engine_throughput [--batch 8]
        [--iters 20] [--impl {xla,pallas}] [--scale 1]
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from benchmarks.common import header, row
from repro.data.matrices import paper_small_suite
from repro.engine import SpmvEngine


def one_shot(a: np.ndarray, x: np.ndarray, impl: str = "xla") -> np.ndarray:
    """The full per-request pipeline the engine exists to amortize."""
    eng = SpmvEngine(cache_capacity=1, impl=impl)  # fresh: no reuse
    eng.register("m", a, warmup=False)
    return eng.multiply("m", x)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=None,
                    help="timed iterations (default 20; 3 for pallas "
                         "interpret mode)")
    ap.add_argument("--oneshot-iters", type=int, default=3)
    ap.add_argument("--impl", choices=("xla", "pallas"), default="xla",
                    help="local tile kernel the engine serves with")
    ap.add_argument("--scale", type=int, default=None,
                    help="suite scale factor (default 1; pallas interpret "
                         "uses smaller shapes unless overridden)")
    args = ap.parse_args(argv)
    from repro.runtime.cache import enable_compilation_cache

    enable_compilation_cache()
    pallas = args.impl == "pallas"
    iters = args.iters if args.iters is not None else (3 if pallas else 20)
    specs = paper_small_suite(args.scale or 1)
    if pallas and args.scale is None:
        # interpret-mode kernels are Python-stepped: shrink the matrices so
        # the sweep finishes in CI-friendly time (the *ratios* still hold)
        specs = [dataclasses.replace(s, rows=s.rows // 4, cols=s.cols // 4)
                 for s in specs]

    header(f"engine_throughput impl={args.impl} "
           "(requests/sec; higher is better)")
    eng = SpmvEngine(cache_capacity=16, impl=args.impl)
    rng = np.random.default_rng(0)

    for spec in specs:
        a = spec.build()
        x = rng.standard_normal(a.shape[1]).astype(np.float32)
        X = rng.standard_normal((a.shape[1], args.batch)).astype(np.float32)
        entry = eng.register(spec.name, a)
        eng.multiply(spec.name, X)  # warm the batched shape too

        t0 = time.perf_counter()
        for _ in range(args.oneshot_iters):
            one_shot(a, x, impl=args.impl)
        oneshot_rps = args.oneshot_iters / (time.perf_counter() - t0)

        t0 = time.perf_counter()
        for _ in range(iters):
            eng.multiply(spec.name, x)
        engine_rps = iters / (time.perf_counter() - t0)

        t0 = time.perf_counter()
        for _ in range(iters):
            eng.multiply(spec.name, X)
        batched_rps = iters * args.batch / (time.perf_counter() - t0)

        plan = f"{entry.plan.partitioning}.{entry.plan.scheme}.{entry.plan.fmt}"
        row(f"oneshot.{spec.name}", 1e6 / oneshot_rps, f"rps={oneshot_rps:.1f}")
        row(f"engine.{spec.name}", 1e6 / engine_rps,
            f"rps={engine_rps:.1f} plan={plan} x{engine_rps / oneshot_rps:.0f}")
        row(f"engine.b{args.batch}.{spec.name}", 1e6 / batched_rps,
            f"rps={batched_rps:.1f} x{batched_rps / oneshot_rps:.0f}")
        # batched SpMM vs per-column SpMV on the *same* served kernels:
        # one (cols, B) request vs B (cols,) requests, steady state
        spmm_s = percol_s = 0.0
        for _ in range(iters):
            t0 = time.perf_counter()
            eng.multiply(spec.name, X)
            spmm_s += time.perf_counter() - t0
            t0 = time.perf_counter()
            for j in range(args.batch):
                eng.multiply(spec.name, X[:, j])
            percol_s += time.perf_counter() - t0
        row(f"spmm_vs_percol.{spec.name}", 1e6 * spmm_s / iters,
            f"percol_us={1e6 * percol_s / iters:.0f} "
            f"speedup=x{percol_s / spmm_s:.2f}")

    header("fig17-style request breakdown (fractions of request time)")
    for spec in specs:
        bd = eng.telemetry.breakdown(spec.name)
        if bd.get("load") is None:  # zero-total breakdowns carry no fractions
            print(f"{spec.name}: no measurable phase time "
                  f"(requests={bd.get('requests', 0)})")
            continue
        print(f"{spec.name}: load={bd['load']:.2f} kernel={bd['kernel']:.2f} "
              f"retrieve={bd['retrieve']:.2f} requests={bd['requests']} "
              f"vectors={bd['vectors']} traces={bd['traces']}")
    st = eng.cache.stats
    print(f"# cache: hits={st.hits} misses={st.misses} evictions={st.evictions} "
          f"hit_rate={st.hit_rate:.3f}")


if __name__ == "__main__":
    main()
