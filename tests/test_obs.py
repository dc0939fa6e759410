"""repro.obs — span tracing, metrics registry, queue-aware admission.

Unit tests pin down the tracing/metrics primitives (ring buffers, numpy-
exact percentiles, the zero-allocation disabled path); integration tests
replay against a live AsyncSpmvService and assert the acceptance contract:
every accepted request decomposes into lifecycle spans whose durations sum
to its end-to-end latency within 5%.
"""

import asyncio
import json

import numpy as np
import pytest

from repro.data.matrices import regular_matrix, scale_free_matrix
from repro.engine import SpmvEngine
from repro.engine.telemetry import RequestRecord, Telemetry
from repro.obs import (
    NULL_TRACE,
    PHASES,
    MetricsRegistry,
    Tracer,
    chrome_trace,
    trace_summary,
)
from repro.obs import profile as obs_profile
from repro.serve import (
    AdmissionController,
    AsyncSpmvService,
    RequestRejected,
    TenantConfig,
    WorkloadSpec,
    generate_trace,
    replay,
)

# ------------------------------------------------------------------ tracing


def test_tracer_records_spans_in_order():
    tr = Tracer()
    t = tr.trace("tenant-a/reg")
    t.add("admit", 1.0, 1.5, outcome="admitted")
    t.add("queue_wait", 1.5, 2.0)
    t.add("kernel", 2.0, 3.0, batch=4)
    spans = tr.spans()
    assert [s.name for s in spans] == ["admit", "queue_wait", "kernel"]
    assert all(s.trace_id == t.trace_id for s in spans)
    assert all(s.label == "tenant-a/reg" for s in spans)
    assert spans[2].args == {"batch": 4}
    assert spans[2].duration_s == pytest.approx(1.0)
    assert t.first_start == 1.0 and t.last_end == 3.0
    # filters
    assert [s.name for s in tr.spans(name="kernel")] == ["kernel"]
    assert tr.spans(trace_id=t.trace_id + 1) == []


def test_trace_span_context_manager():
    tr = Tracer()
    t = tr.trace()
    with t.span("load", stage=1):
        pass
    (s,) = tr.spans()
    assert s.name == "load" and s.args == {"stage": 1}
    assert s.end_s >= s.start_s


def test_tracer_ring_buffer_evicts_oldest():
    tr = Tracer(capacity=8)
    t = tr.trace()
    for i in range(20):
        t.add("kernel", float(i), float(i) + 0.5)
    spans = tr.spans()
    assert len(spans) == 8
    assert spans[0].start_s == 12.0  # oldest 12 evicted
    assert tr.dropped == 12
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_distinct_trace_ids():
    tr = Tracer()
    ids = {tr.trace().trace_id for _ in range(100)}
    assert len(ids) == 100


def test_disabled_tracer_is_allocation_free():
    tr = Tracer(enabled=False)
    # the disabled path hands out the SAME shared singletons every time —
    # object identity is the no-allocation guarantee
    a, b = tr.trace("x"), tr.trace("y")
    assert a is NULL_TRACE and b is NULL_TRACE
    assert not a.enabled
    assert a.span("kernel") is b.span("load")  # shared null context
    with a.span("kernel"):
        a.add("kernel", 0.0, 1.0)
    assert len(tr) == 0  # nothing was ever recorded


def test_chrome_trace_format():
    tr = Tracer()
    t1 = tr.trace("tenant-a/reg")
    t1.add("admit", 10.0, 10.001)
    t1.add("kernel", 10.001, 10.005, batch=2)
    t2 = tr.trace("tenant-b/sf")
    t2.add("kernel", 10.002, 10.004)
    doc = tr.chrome_trace()
    assert json.loads(json.dumps(doc)) == doc  # JSON-safe end to end
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 3
    assert min(e["ts"] for e in xs) == 0.0  # rebased to the earliest span
    k = next(e for e in xs if e["tid"] == t1.trace_id and e["name"] == "kernel")
    assert k["dur"] == pytest.approx(4000.0)  # 4ms in us
    assert k["args"] == {"batch": 2}
    names = {e["tid"]: e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names[t1.trace_id] == "tenant-a/reg"
    assert names[t2.trace_id] == "tenant-b/sf"
    assert chrome_trace([]) == {"traceEvents": [], "displayTimeUnit": "ms"}


def test_trace_summary_coverage():
    tr = Tracer()
    t = tr.trace("r")
    t.add("admit", 0.0, 1.0)
    t.add("kernel", 1.0, 3.0)
    t.add("deliver", 3.0, 4.0)  # gapless: coverage 1.0
    u = tr.trace("gappy")
    u.add("admit", 0.0, 1.0)
    u.add("kernel", 3.0, 4.0)  # 2s hole: coverage 0.5
    summ = trace_summary(tr.spans())
    assert summ[t.trace_id]["coverage"] == pytest.approx(1.0)
    assert summ[t.trace_id]["total_s"] == pytest.approx(4.0)
    assert summ[t.trace_id]["phases"]["kernel"] == pytest.approx(2.0)
    assert summ[u.trace_id]["coverage"] == pytest.approx(0.5)


def test_concurrent_tracing_threads():
    import threading

    tr = Tracer(capacity=100_000)

    def worker(n):
        t = tr.trace(f"w{n}")
        for i in range(200):
            t.add("kernel", float(i), float(i) + 0.5, worker=n)

    threads = [threading.Thread(target=worker, args=(n,)) for n in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    spans = tr.spans()
    assert len(spans) == 8 * 200
    per_trace = trace_summary(spans)
    assert len(per_trace) == 8  # no cross-thread id collisions


# ------------------------------------------------------------------ metrics


def test_counter_and_gauge():
    m = MetricsRegistry()
    c = m.counter("serve.shed", reason="queue_full")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    assert m.counter("serve.shed", reason="queue_full") is c  # same identity
    assert m.counter("serve.shed", reason="rate_limited") is not c
    g = m.gauge("serve.queue.depth", matrix="reg")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5


def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=0.0, sigma=1.5, size=1500)
    m = MetricsRegistry()
    h = m.histogram("serve.latency.e2e_ms")
    for v in samples:
        h.observe(float(v))
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(samples, q)), rel=1e-12)
    s = h.summary()
    assert s["count"] == 1500
    assert s["sum"] == pytest.approx(float(samples.sum()))
    assert s["mean"] == pytest.approx(float(samples.mean()))
    assert s["max"] == pytest.approx(float(samples.max()))
    assert s["p95"] == pytest.approx(float(np.percentile(samples, 95)))


def test_histogram_window_slides_but_lifetime_counts():
    m = MetricsRegistry()
    h = m.histogram("x", window=10)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100  # lifetime
    # window holds the last 10 (90..99): the p50 reflects only those
    assert h.percentile(50) == pytest.approx(94.5)
    assert m.histogram("empty").summary()["p50"] == 0.0


def test_registry_type_mismatch_raises():
    m = MetricsRegistry()
    m.counter("serve.shed")
    with pytest.raises(TypeError):
        m.gauge("serve.shed")


def test_snapshot_rendering():
    m = MetricsRegistry()
    m.counter("hits").inc(2)
    m.gauge("depth", matrix="reg").set(3)
    m.histogram("lat").observe(1.0)
    snap = m.snapshot()
    assert snap["hits"] == 2.0
    assert snap["depth{matrix=reg}"] == 3.0
    assert snap["lat"]["count"] == 1
    assert json.loads(json.dumps(snap)) == snap


# ------------------------------------------------------------------ profile


def test_profile_annotations_degrade_to_noop():
    was = obs_profile.set_enabled(True)
    try:
        with obs_profile.annotate("spmv.multiply.run", matrix="reg"):
            pass
        with obs_profile.phase("spmv.multiply.run", matrix="reg") as p:
            pass
        assert p._annotation is not obs_profile._NULL and p.seconds >= 0
        assert obs_profile.set_enabled(False) is False
        # disabled: the SAME shared no-op object, never a per-call allocation
        a = obs_profile.annotate("x")
        assert a is obs_profile.annotate("y", matrix="reg")
        with a:
            pass
        # a phase is still timed, behind the shared no-op annotation
        with obs_profile.phase("spmv.solve.loop") as p:
            pass
        assert p._annotation is a and p.end_s >= p.start_s > 0
    finally:
        obs_profile.set_enabled(was)


# -------------------------------------------------------- telemetry ring


def _rec(name="reg", load=1.0, kernel=2.0, retrieve=1.0, batch=1):
    return RequestRecord(name=name, batch=batch, load_s=load, kernel_s=kernel,
                         retrieve_s=retrieve, cache_hit=True, traced=False)


def test_telemetry_ring_caps_records_but_aggregates_stay_exact():
    t = Telemetry(max_records=5)
    for _ in range(37):
        t.record(_rec())
    assert len(t.records) == 5  # ring capped
    assert t.records[-1].name == "reg"
    bd = t.breakdown("reg")
    assert bd["requests"] == 37  # aggregates span the full lifetime
    assert bd["total_s"] == pytest.approx(37 * 4.0)
    assert bd["kernel"] == pytest.approx(0.5)
    assert Telemetry(max_records=None)._records.maxlen is None  # legacy
    with pytest.raises(ValueError):
        Telemetry(max_records=0)


def test_telemetry_records_support_slicing():
    t = Telemetry(max_records=100)
    for i in range(10):
        t.record(_rec(load=float(i)))
    tail = t.records[-3:]  # the property returns a list copy of the ring
    assert [r.load_s for r in tail] == [7.0, 8.0, 9.0]


def test_breakdown_none_fractions_for_zero_total():
    t = Telemetry()
    t.record(_rec(name="mock", load=0.0, kernel=0.0, retrieve=0.0))
    bd = t.breakdown("mock")
    assert bd["total_s"] == 0.0
    assert bd["load"] is None and bd["kernel"] is None
    assert bd["retrieve"] is None
    assert bd["requests"] == 1


# ------------------------------------------------- queue-aware admission


def test_queue_wait_infeasible_sheds_on_backlog():
    m = MetricsRegistry()
    ctrl = AdmissionController(metrics=m)
    # bare service fits the deadline: admitted at an empty queue
    ctrl.admit("t", deadline_s=0.05, estimate_s=0.02, queue_depth=0)
    # behind 10 queued vectors the same request cannot finish in time
    with pytest.raises(RequestRejected) as ei:
        ctrl.admit("t", deadline_s=0.05, estimate_s=0.02, queue_depth=10)
    assert ei.value.reason == "queue_wait_infeasible"
    assert ctrl.state("t").rejected["queue_wait_infeasible"] == 1
    assert m.counter("serve.shed", reason="queue_wait_infeasible").value == 1
    # no estimate yet -> feasibility (incl. queue-aware) is skipped
    ctrl.admit("t", deadline_s=0.05, estimate_s=None, queue_depth=50)
    # deep deadline clears even a deep queue
    ctrl.admit("t", deadline_s=10.0, estimate_s=0.02, queue_depth=50)


def test_queue_wait_respects_safety_margin():
    ctrl = AdmissionController(safety=2.0)
    # (4 + 1) * 0.01 = 0.05 expected; deadline 0.08 clears it at safety 1
    AdmissionController().admit("t", deadline_s=0.08, estimate_s=0.01,
                                queue_depth=4)
    # but not at safety 2.0 (needs >= 0.1)
    with pytest.raises(RequestRejected) as ei:
        ctrl.admit("t", deadline_s=0.08, estimate_s=0.01, queue_depth=4)
    assert ei.value.reason == "queue_wait_infeasible"


# --------------------------------------------------- service integration


def _service(**kwargs):
    kwargs.setdefault("tenants", {"tenant-a": TenantConfig(max_pending=64),
                                  "tenant-b": TenantConfig(max_pending=64)})
    svc = AsyncSpmvService(SpmvEngine(cache_capacity=8), **kwargs)
    svc.register(None, "reg", regular_matrix(48, 64, 5, seed=1))
    svc.register(None, "sf", scale_free_matrix(48, 64, 300, seed=2))
    return svc


def test_request_lifecycle_spans_tile_the_e2e_latency():
    svc = _service()

    async def main():
        async with svc:
            rng = np.random.default_rng(0)
            xs = [rng.standard_normal(64).astype(np.float32)
                  for _ in range(12)]
            await asyncio.gather(*[
                svc.multiply("tenant-a", "reg", x) for x in xs[:6]
            ])
            await asyncio.gather(*[
                svc.multiply("tenant-b", "sf", x) for x in xs[6:]
            ])

    asyncio.run(main())
    spans = svc.tracer.spans()
    assert spans, "tracing is on by default"
    per_trace = trace_summary(spans)
    assert len(per_trace) == 12
    for t in per_trace.values():
        # every accepted request decomposes into the full lifecycle...
        assert set(t["phases"]) == set(PHASES)
        # ...with phase durations summing to e2e within 5% (the acceptance
        # contract; spans tile the timeline by construction)
        assert t["coverage"] >= 0.95
        assert t["coverage"] <= 1.0 + 1e-6


def test_span_ordering_and_single_occurrence_per_request():
    svc = _service()

    async def main():
        async with svc:
            rng = np.random.default_rng(1)
            await asyncio.gather(*[
                svc.multiply("tenant-a", "reg",
                             rng.standard_normal(64).astype(np.float32))
                for _ in range(8)
            ])

    asyncio.run(main())
    order = {name: i for i, name in enumerate(PHASES)}
    by_trace = {}
    for s in svc.tracer.spans():
        by_trace.setdefault(s.trace_id, []).append(s)
    for spans in by_trace.values():
        names = [s.name for s in spans]
        assert sorted(names, key=order.__getitem__) == list(PHASES)
        assert len(set(names)) == len(names)  # each phase exactly once
        by_name = {s.name: s for s in spans}
        for earlier, later in zip(PHASES, PHASES[1:]):
            # phases cannot END before the previous phase ended
            assert by_name[later].end_s >= by_name[earlier].end_s


def test_rejected_request_traces_admit_with_reason():
    svc = _service(tenants={"t": TenantConfig(max_pending=0)})

    async def main():
        async with svc:
            with pytest.raises(RequestRejected):
                await svc.multiply("t", "reg", np.zeros(64, np.float32))

    asyncio.run(main())
    (s,) = svc.tracer.spans()
    assert s.name == "admit"
    assert s.args["outcome"] == "queue_full"
    assert svc.metrics.counter("serve.shed", reason="queue_full").value == 1


def test_disabled_tracer_serves_identically():
    svc = _service(tracer=Tracer(enabled=False))

    async def main():
        async with svc:
            rng = np.random.default_rng(2)
            x = rng.standard_normal(64).astype(np.float32)
            y = await svc.multiply("tenant-a", "reg", x)
            return np.asarray(y)

    y = asyncio.run(main())
    assert y.shape == (48,)
    assert svc.tracer.spans() == []  # nothing recorded, nothing broken


def test_service_metrics_snapshot_populated():
    svc = _service()

    async def main():
        async with svc:
            rng = np.random.default_rng(3)
            await asyncio.gather(*[
                svc.multiply("tenant-a", "reg",
                             rng.standard_normal(64).astype(np.float32))
                for _ in range(4)
            ])
            return svc.stats()

    stats = asyncio.run(main())
    snap = stats["metrics"]
    assert snap["serve.latency.e2e_ms"]["count"] == 4
    assert snap["serve.phase.kernel_ms"]["count"] == 4
    assert "serve.batch.width" in snap
    assert "engine.plan_cache.misses" in snap
    assert snap["serve.queue.depth{matrix=reg}"] == 0.0  # drained


def test_replay_report_carries_phase_attribution():
    svc = _service()
    trace = generate_trace(WorkloadSpec(
        names=("reg", "sf"), tenants=("tenant-a", "tenant-b"),
        n_requests=24, seed=5, batch_mix={1: 0.9, 4: 0.1},
    ))

    async def main():
        async with svc:
            return await replay(svc, trace, time_scale=0.0)

    report = asyncio.run(main())
    assert report.lost == 0 and report.completed == 24
    assert set(report.phase_latency) == set(PHASES)
    for d in report.phase_latency.values():
        assert d["count"] > 0 and d["p95_ms"] >= d["p50_ms"]
    assert report.queue_wait["count"] > 0
    assert report.queue_wait["max_ms"] >= report.queue_wait["p50_ms"]
    assert report.span_coverage >= 0.95
    doc = report.to_dict()
    assert json.loads(json.dumps(doc))["span_coverage"] == pytest.approx(
        report.span_coverage)
    assert "queue wait ms" in report.describe()
    assert "per-phase attribution" in report.describe()


def test_replay_with_disabled_tracer_reports_empty_attribution():
    svc = _service(tracer=Tracer(enabled=False))
    trace = generate_trace(WorkloadSpec(
        names=("reg",), tenants=("tenant-a",), n_requests=6, seed=6,
    ))

    async def main():
        async with svc:
            return await replay(svc, trace, time_scale=0.0)

    report = asyncio.run(main())
    assert report.completed == 6
    assert report.phase_latency == {}
    assert report.queue_wait == {}
    assert report.span_coverage == 0.0
