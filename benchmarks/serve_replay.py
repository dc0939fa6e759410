"""Serving SLO benchmark — replay a seeded workload, emit BENCH_serve.json.

    PYTHONPATH=src python -m benchmarks.serve_replay [--smoke]
                                                     [--json BENCH_serve.json]
                                                     [--trace OUT.json]
                                                     [--requests N]
                                                     [--workers N]

Fires a seeded Zipfian/bursty trace (two tenants, mixed vector/batch
requests) at an :class:`~repro.serve.AsyncSpmvService` and prints
``name,us_per_call,derived`` CSV rows — p50/p95/p99/mean serving latency,
a queue-wait p95 row, a reject-rate row, plus shed-by-reason count rows
(``"kind": "count"``; exempt from the wall-clock gate) — the same row shape
every other benchmark emits, so ``tools/check_bench.py`` can gate a fresh
run against the committed ``BENCH_serve.json`` baseline and CI can upload
the JSON as the perf trajectory.

``--workers N`` additionally runs the **cluster scaling replay**: the same
integer-valued workload is blasted through a
:class:`~repro.cluster.ClusterRouter` at worker counts {1, N} by spawned
load-generator processes, every reply verified bit-exactly against the
dense oracle, and ``serve.cluster.w<K>.us_per_req`` rows are emitted with
``gate_factor: 8.0`` (cross-process wall-clock folds in process scheduling
and socket round-trips — far noisier than one process's kernel loop, so
those rows gate looser without touching the single-process gates).  The
run FAILS if any request is lost or any reply mismatches; on machines
with >= 2 CPUs it also fails if N workers do not beat 1 worker on
accepted requests/s — the scaling claim the tier exists for.  With
``--trace`` the per-worker span buffers are merged into one cluster
timeline (one Perfetto ``pid`` per worker).

``--trace OUT.json`` dumps the final measured replay's request spans as
Chrome/Perfetto trace JSON (load it at https://ui.perfetto.dev or
``chrome://tracing``) — every accepted request decomposes into
admit/queue_wait/batch_form/load/kernel/retrieve/deliver spans.

A warmup replay (same matrices, different seed) runs first and is
discarded: it pays the per-bucket trace/compile costs so the measured
percentiles describe steady-state serving, not compilation.

Every run (smoke included) also replays the **SLO-class workload**: one
``rt`` tenant sharing the service with five ``batch`` tenants, fired as a
burst so deep queues form, once against a class-aware service and once
against a classless (all-``standard``) twin of the same trace.  The
``serve.class.<name>.p99`` rows carry per-row ``gate_factor`` (queue-order
noise), and two ``kind=count`` rows encode the SLO-class acceptance
criteria: ``serve.class.rt.speedup_x`` (classless rt p99 over classed rt
p99 — the run FAILS below 2.0) and ``serve.class.batch.reject_permille``
(FAILS above the 250 budget documented in docs/slo.md).

``--smoke`` shrinks the trace for the CI perf job.  The smoke workload has
no deadlines, so its reject-rate row is structurally 0.0 — the gate then
fails if admission control ever starts shedding a workload it fully
admitted before (that *is* a serving regression).
"""
import argparse
import json
import os
import sys

import numpy as np


def build_service():
    from repro.data.matrices import regular_matrix, scale_free_matrix
    from repro.engine import SpmvEngine
    from repro.serve import AsyncSpmvService, TenantConfig

    mats = {
        "social": scale_free_matrix(96, 128, 700, seed=0),
        "mesh": regular_matrix(96, 128, 5, seed=1),
    }
    service = AsyncSpmvService(
        SpmvEngine(cache_capacity=8),
        tenants={"tenant-a": TenantConfig(max_pending=128),
                 "tenant-b": TenantConfig(max_pending=128)},
    )
    for name, a in mats.items():
        service.register(None, name, a)  # global: both tenants share plans
    return service, mats


def run_classes(args, n: int, row) -> int:
    """The SLO-class replay: classed vs classless service on one trace.

    One ``rt`` tenant and five ``batch`` tenants fire the same bursty
    single-vector trace (``time_scale=0.0`` — everything arrives at once,
    so a deep queue forms and batch-formation *order* is what decides the
    rt tail).  The classed service sorts claims by effective rank; the
    classless twin serves FIFO.  Rows are medians over ``--repeats``.

    Returns 0 on success; 1 if any request is lost/errored, if the rt-class
    p99 speedup lands below 2.0, or if the batch-class reject rate exceeds
    the 250-permille budget documented in docs/slo.md.
    """
    import asyncio

    from repro.data.matrices import regular_matrix
    from repro.engine import SpmvEngine
    from repro.obs import Tracer
    from repro.serve import (
        TenantConfig,
        WorkloadSpec,
        generate_trace,
        replay,
        tenant_configs,
    )
    from repro.serve import AsyncSpmvService

    bulk = tuple(f"bulk-{i}" for i in range(5))
    # floor of 192: the classless rt p99 tracks total drain time (grows
    # with n) while the classed rt p99 tracks the in-progress claim (does
    # not) — below ~4 dozen chunks the two are not separable from noise
    n = max(192, n)
    spec = WorkloadSpec(
        names=("mesh",), tenants=("rt-api",) + bulk,
        n_requests=n, seed=args.seed + 7, rate_rps=5000.0,
        arrivals="bursty", batch_mix={1: 1.0},  # width-1 only: every request
        # rides the priority queue, none bypasses it as a pre-formed batch
        tenant_classes={"rt-api": "rt", **{t: "batch" for t in bulk}},
    )
    trace = generate_trace(spec)
    warm = generate_trace(WorkloadSpec(
        names=spec.names, tenants=spec.tenants,
        n_requests=max(16, n // 4), seed=args.seed + 8,
        batch_mix=spec.batch_mix))
    # a heavier matrix than the SLO section's: per-chunk kernel time has to
    # dominate request-submission overhead, or the drain keeps pace with
    # the burst, the queue stays shallow, and claim ORDER decides nothing
    mesh = regular_matrix(1024, 512, 12, seed=1)

    def build(classed: bool) -> AsyncSpmvService:
        # max_batch=4 keeps many claim rounds in flight: preemption decides
        # the order chunk by chunk instead of one giant batch hiding it.
        # workers=2 keeps a server free for late rt arrivals while a bulk
        # claim drains, and the disabled tracer keeps submission fast —
        # both services get the identical configuration, only the tenant
        # classes differ.
        tenants = (tenant_configs(spec, max_pending=4 * n) if classed
                   else {t: TenantConfig(max_pending=4 * n)
                         for t in spec.tenants})
        svc = AsyncSpmvService(SpmvEngine(cache_capacity=4),
                               tenants=tenants, max_batch=4, buckets=(1, 4),
                               workers=2, tracer=Tracer(enabled=False))
        svc.register(None, "mesh", mesh)
        return svc

    async def measure():
        """Interleaved A/B replays: classed and classless alternate repeat
        by repeat so process-level warmup (dispatch caches, allocator) hits
        both sides equally instead of flattering whichever runs last."""
        svc_classed, svc_classless = build(True), build(False)
        classed, classless = [], []
        async with svc_classed:
            async with svc_classless:
                for svc in (svc_classed, svc_classless):
                    # two discarded warmups each: the seeded warm trace pays
                    # the compile costs, one throwaway replay of the measured
                    # trace pays first-touch dispatch (2-3x cold percentiles)
                    await replay(svc, warm, time_scale=0.0)
                    await replay(svc, trace, time_scale=0.0)
                for _ in range(max(5, args.repeats)):
                    classed.append(
                        await replay(svc_classed, trace, time_scale=0.0))
                    classless.append(
                        await replay(svc_classless, trace, time_scale=0.0))
        return classed, classless, svc_classed.stats()

    classed_reports, classless_reports, classed_stats = asyncio.run(measure())

    def med(reports, pick) -> float:
        return float(np.median([pick(r) for r in reports]))

    fails = []
    for rep in classed_reports + classless_reports:
        if rep.lost or rep.errors:
            fails.append(f"lost={rep.lost} errors={rep.errors}")
    final = classed_reports[-1]
    rt_p99 = med(classed_reports, lambda r: r.per_class["rt"]["p99_ms"])
    batch_p99 = med(classed_reports, lambda r: r.per_class["batch"]["p99_ms"])
    # the classless twin has no classes: score its rt *tenant* instead
    rt_p99_classless = med(classless_reports,
                           lambda r: r.per_tenant["rt-api"]["p99_ms"])
    speedup = rt_p99_classless / rt_p99 if rt_p99 > 0 else 0.0
    batch_total = (final.per_class["batch"]["completed"]
                   + final.per_class["batch"]["rejected"])
    reject_pm = med(
        classed_reports,
        lambda r: 1000.0 * r.per_class["batch"]["rejected"]
        / max(1, r.per_class["batch"]["completed"]
              + r.per_class["batch"]["rejected"]))

    print(f"# --- serve.class: SLO-class replay ({len(trace)} reqs, "
          f"1 rt + {len(bulk)} batch tenants, median over "
          f"{len(classed_reports)})")
    derived = (f"completed={final.completed}/{final.requests} "
               f"fairness_by_class={final.fairness_by_class}")
    # queue-order noise on a 1-row class can be large: gate these loose,
    # the hard acceptance bar is the speedup count row below
    row("serve.class.rt.p99", rt_p99 * 1e3, derived, gate_factor=8.0)
    row("serve.class.batch.p99", batch_p99 * 1e3, derived, gate_factor=8.0)
    row("serve.class.rt.classless_p99", rt_p99_classless * 1e3,
        "same trace, all-standard service", gate_factor=8.0)
    row("serve.class.rt.speedup_x", speedup,
        "classless rt p99 / classed rt p99; FAILS < 2.0", kind="count")
    row("serve.class.batch.reject_permille", reject_pm,
        f"of {batch_total} batch-class requests; budget <= 250 "
        "(docs/slo.md)", kind="count")
    row("serve.class.preemptions", float(classed_stats["preemptions"]),
        "claims reordered by class rank (final service)", kind="count")
    row("serve.class.promotions", float(classed_stats["promotions"]),
        "starvation-guard rank promotions (final service)", kind="count")
    if speedup < 2.0:
        fails.append(f"rt p99 speedup {speedup:.2f}x < 2.0x "
                     f"({rt_p99_classless:.2f} -> {rt_p99:.2f} ms)")
    if reject_pm > 250.0:
        fails.append(f"batch reject {reject_pm:.0f} permille > 250 budget")
    if fails:
        print(f"FAIL (classes): {'; '.join(sorted(set(fails)))}",
              file=sys.stderr)
        return 1
    return 0


def run_cluster(args, n: int, row, trace_path=None) -> int:
    """The ``--workers N`` cluster scaling replay (rows appended via ``row``).

    Returns 0 on success, 1 on lost/mismatched requests or (on multi-CPU
    machines) a failed scaling claim.
    """
    from repro.cluster import ClusterRouter
    from repro.cluster.replay import replay_generators
    from repro.data.matrices import regular_matrix, scale_free_matrix
    from repro.serve import WorkloadSpec, generate_trace

    # integer-valued matrices: float32 SpMV over small integers is exact in
    # any summation order, so "accepted" can mean "bit-exact vs the oracle"
    mats = {
        "social": np.round(scale_free_matrix(96, 128, 700, seed=0) * 2.0),
        "mesh": np.round(regular_matrix(96, 128, 5, seed=1) * 2.0),
    }
    spec = WorkloadSpec(
        names=tuple(mats), tenants=("tenant-a", "tenant-b"),
        n_requests=n, seed=args.seed, zipf_alpha=1.2, rate_rps=2000.0,
        arrivals="bursty", batch_mix={1: 0.85, 4: 0.1, 8: 0.05},
        integer_values=True,
    )
    warm = generate_trace(WorkloadSpec(
        names=spec.names, n_requests=max(8, n // 4), seed=args.seed + 1,
        batch_mix=spec.batch_mix, integer_values=True,
    ))
    trace = generate_trace(spec)
    counts = sorted({1, args.workers})
    rps, fails = {}, []
    print(f"# --- serve.cluster: {counts} worker replays "
          f"({len(trace)} reqs, {args.cluster_generators} generators)")
    for w in counts:
        with ClusterRouter(workers=w) as router:
            for name, a in mats.items():
                # both names absorb ~all traffic (a 2-name Zipf head is all
                # head): replicate to every worker so round-robin spreads
                # load — the placement the popularity policy converges to
                router.register(name, a, replicas=w)
            replay_generators(router, warm, mats,
                              generators=args.cluster_generators)  # discarded
            best = None
            for _ in range(max(1, args.cluster_repeats)):
                rep = replay_generators(
                    router, trace, mats, generators=args.cluster_generators,
                )
                if rep.lost or not rep.bit_exact:
                    fails.append(f"w{w}: lost={rep.lost} "
                                 f"mismatched={rep.mismatched}")
                if best is None or rep.accepted_rps > best.accepted_rps:
                    best = rep
            if trace_path is not None and w == max(counts):
                merged = router.dump_traces()
                with open(trace_path, "w", encoding="utf-8") as fh:
                    json.dump(merged, fh)
                print(f"# wrote {trace_path} "
                      f"({len(merged['traceEvents'])} events, {w} workers)",
                      file=sys.stderr)
        rps[w] = best.accepted_rps
        derived = (f"accepted={best.accepted}/{best.requests} "
                   f"rps={best.accepted_rps:.0f} "
                   f"per_worker={best.per_worker}")
        # gate_factor 8.0: see module docstring — cross-process rows gate
        # looser in the committed baseline without touching other gates
        row(f"serve.cluster.w{w}.us_per_req",
            best.wall_s / max(1, best.accepted) * 1e6, derived,
            gate_factor=8.0)
        row(f"serve.cluster.w{w}.lost", float(best.lost),
            "requests neither answered nor shed", kind="count")
        row(f"serve.cluster.w{w}.shed", float(len(best.shed)),
            f"reasons={sorted({s['reason'] for s in best.shed})}",
            kind="count")
    hi = max(counts)
    if hi > 1:
        speedup = rps[hi] / rps[1] if rps[1] > 0 else 0.0
        row(f"serve.cluster.w{hi}.speedup_x", speedup,
            f"accepted-rps vs 1 worker ({os.cpu_count()} CPUs)",
            kind="count")
        if os.cpu_count() and os.cpu_count() >= 2 and speedup <= 1.0:
            fails.append(
                f"w{hi} did not beat w1: {rps[hi]:.0f} vs {rps[1]:.0f} rps"
            )
    if fails:
        print(f"FAIL (cluster): {'; '.join(fails)}", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for the CI perf job")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the rows as machine-readable JSON")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write the final replay's spans as Chrome/Perfetto "
                         "trace JSON")
    ap.add_argument("--requests", type=int, default=None,
                    help="trace length (default: 48 smoke / 160 full)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="measured replays; rows are row-wise medians")
    ap.add_argument("--workers", type=int, default=0,
                    help="also run the cluster scaling replay at worker "
                         "counts {1, N} and emit serve.cluster.* rows")
    ap.add_argument("--cluster-generators", type=int, default=2,
                    help="spawned load-generator processes per cluster run")
    ap.add_argument("--cluster-repeats", type=int, default=2,
                    help="measured cluster replays; the best rps repeat is "
                         "reported (cross-process noise floor)")
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args(argv)

    if args.workers:
        # the in-process replays below take the chip, and a worker spawned
        # after them could not open it: refuse before touching a device
        from repro.cluster.worker import worker_platform

        platform, _ = worker_platform()
        if platform != "cpu":
            print(f"FAIL: --workers needs one chip per worker; on {platform} "
                  "this process holds the chip its workers would need",
                  file=sys.stderr)
            return 2

    from repro.runtime.cache import enable_compilation_cache
    from repro.serve import WorkloadSpec, generate_trace, replay

    enable_compilation_cache()

    n = args.requests if args.requests is not None else (48 if args.smoke
                                                         else 160)

    import asyncio

    async def measured():
        """One warmup replay, then ``repeats`` measured replays.

        Queue-drain ordering makes any single replay's percentile latencies
        noisy (the same trace can land p50 2x apart back to back); the
        row-wise *median over repeats* is what the gate compares.
        """
        service, _ = build_service()
        spec = WorkloadSpec(
            names=("social", "mesh"),
            tenants=("tenant-a", "tenant-b"),
            n_requests=n,
            seed=args.seed,
            zipf_alpha=1.2,
            rate_rps=2000.0,
            arrivals="bursty",
            batch_mix={1: 0.85, 4: 0.1, 8: 0.05},
        )
        warm = generate_trace(WorkloadSpec(
            names=spec.names, tenants=spec.tenants,
            n_requests=max(16, n // 4), seed=args.seed + 1,
            batch_mix=spec.batch_mix,
        ))
        trace = generate_trace(spec)
        reports = []
        async with service:
            await replay(service, warm, time_scale=0.0)  # discarded
            for _ in range(args.repeats):
                service.engine.telemetry.clear()
                service.tracer.clear()  # keep only the last repeat's spans
                reports.append(await replay(service, trace, time_scale=0.0))
            spans = service.tracer.spans()
        return reports, spans

    reports, spans = asyncio.run(measured())

    def med(pick) -> float:
        return float(np.median([pick(r) for r in reports]))

    report = reports[-1]  # counters/accounting are identical across repeats
    derived = (f"completed={report.completed}/{report.requests} "
               f"fairness={report.fairness:.3f} repeats={len(reports)}")
    print("name,us_per_call,derived")
    print("# --- serve: asyncio replay SLO (2 tenants, Zipfian bursty; "
          "median over repeats)")
    rows = []

    def row(name: str, us: float, extra: str = "", kind: str = None,
            gate_factor: float = None) -> None:
        r = {"name": name, "us_per_call": round(us, 1), "derived": extra}
        if kind is not None:
            r["kind"] = kind  # count rows are exempt from the perf gate
        if gate_factor is not None:
            r["gate_factor"] = gate_factor  # per-row override of the
            # check_bench threshold (committed baseline side only)
        rows.append(r)
        print(f"{name},{us:.1f},{extra}")

    row("serve.latency.p50", med(lambda r: r.latency["p50_ms"]) * 1e3, derived)
    row("serve.latency.p95", med(lambda r: r.latency["p95_ms"]) * 1e3, derived)
    row("serve.latency.p99", med(lambda r: r.latency["p99_ms"]) * 1e3, derived)
    row("serve.latency.mean", med(lambda r: r.latency["mean_ms"]) * 1e3,
        derived)
    # whole-trace drain time per completed request: the throughput inverse,
    # much steadier than any percentile (queue order cancels out)
    row("serve.drain.us_per_req",
        med(lambda r: r.wall_s / max(1, r.completed)) * 1e6, derived)
    # queue wait at the p95: where a deep backlog shows up first; 0.0 when
    # the tracer recorded no queue_wait spans (tracing disabled)
    row("serve.queue_wait.p95",
        med(lambda r: r.queue_wait.get("p95_ms", 0.0)) * 1e3,
        f"coverage={report.span_coverage:.3f}")
    # reject-rate as permille in the us_per_call slot: 0.0 for this
    # deadline-free workload, so any future shedding fails the gate
    row("serve.reject.permille",
        med(lambda r: 1000.0 * r.reject_rate),
        f"reasons={report.reject_reasons or {}}")
    # shed-by-reason counts (final repeat): kind=count rows ride in the JSON
    # for trajectory tracking but are exempt from the wall-clock gate
    from repro.serve.admission import REJECT_REASONS
    for reason in REJECT_REASONS:
        row(f"serve.shed.{reason}",
            float(report.reject_reasons.get(reason, 0)),
            "per-replay shed count", kind="count")
    print(f"# lost={report.lost} errors={report.errors} "
          f"throughput={report.throughput_rps:.0f}/s "
          f"span_coverage={report.span_coverage:.3f}")

    lost = sum(r.lost for r in reports)
    errors = sum(r.errors for r in reports)
    if lost or errors:
        print(f"FAIL: lost={lost} errors={errors}", file=sys.stderr)
        return 1

    classes_rc = run_classes(args, n, row)

    cluster_rc = 0
    if args.workers:
        # cluster mode owns --trace: the artifact becomes the merged
        # per-worker timeline instead of the single-process span dump
        cluster_rc = run_cluster(args, n, row, trace_path=args.trace)

    if args.json:
        doc = {
            "version": 1,
            "mode": "serve-smoke" if args.smoke else "serve",
            "rows": rows,
            "report": report.to_dict(),
        }
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
        print(f"# wrote {args.json} ({len(rows)} rows)", file=sys.stderr)

    if args.trace and not args.workers:
        from repro.obs import chrome_trace
        with open(args.trace, "w", encoding="utf-8") as fh:
            json.dump(chrome_trace(spans), fh)
        print(f"# wrote {args.trace} ({len(spans)} spans, "
              f"coverage={report.span_coverage:.3f})", file=sys.stderr)
    return classes_rc or cluster_rc


if __name__ == "__main__":
    np.random.seed(0)  # belt and braces; all real draws are generator-seeded
    sys.exit(main())
