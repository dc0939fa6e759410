"""Program phases: one span vocabulary in repro.obs and the profiler.

A solve and a multiply stamp their phases (``repro.obs.phase``) on the
caller's request trace as the lifecycle spans load / kernel / retrieve, from
the same boundaries as the seconds they report; a register keeps its
phases' seconds on the registry entry; under ``jax.profiler.trace`` the same
phases appear as host events of their stable names, nested the same way.
"""
import glob
import os

import numpy as np
import pytest

from repro.data.matrices import regular_matrix
from repro.engine import SpmvEngine
from repro.obs import NULL_TRACE, Tracer, phase
from repro.obs.tracing import clock

SOLVE = ("spmv.solve.place", "spmv.solve.loop", "spmv.solve.fetch")
LIFECYCLE = ("load", "kernel", "retrieve")
REGISTER = ("stats", "fingerprint", "plan", "partition", "place", "compile")


def _spd(n: int = 2048) -> np.ndarray:
    a = regular_matrix(n, n, 9, seed=3).astype(np.float32)
    return a + a.T + 40.0 * np.eye(n, dtype=np.float32)


@pytest.fixture(scope="module")
def engine():
    eng, a = SpmvEngine(), _spd()
    t0 = clock()
    entry = eng.register("A", a)
    return eng, entry, clock() - t0


def _lifecycle(tracer, trace) -> list:
    spans = tracer.spans(trace_id=trace.trace_id)
    assert [s.name for s in spans] == list(LIFECYCLE)
    return spans


def test_solve_phases_cover_the_session_and_are_its_seconds(engine):
    eng, _, _ = engine
    b = np.ones(eng.registry.get("A").shape[0], np.float32)
    tracer = Tracer()
    for _ in range(2):  # the first compiles the loop, the second is warm
        obs = tracer.trace("A")
        t0 = clock()
        r = eng.solve("A", b, steps=400, combine="power", obs=obs)
        whole = clock() - t0
        kids = _lifecycle(tracer, obs)
        # in order, inside the call, and tiling their extent within 5% (the
        # profiler test below holds them to the spmv.solve phase itself)
        assert all(a.end_s <= b.start_s for a, b in zip(kids, kids[1:]))
        assert t0 <= kids[0].start_s and kids[-1].end_s <= t0 + whole
        assert sum(k.duration_s for k in kids) >= 0.95 * (
            kids[-1].end_s - kids[0].start_s)
        # the result's phase times are the spans' own boundaries
        assert [k.duration_s for k in kids] == [r.load_s, r.kernel_s,
                                                r.retrieve_s]
        rec = eng.telemetry.records[-1]
        assert rec.kind == "solve"
        assert (rec.load_s, rec.kernel_s, rec.retrieve_s) == (
            r.load_s, r.kernel_s, r.retrieve_s)


def test_multiply_phases_are_its_telemetry(engine):
    eng, _, _ = engine
    n = eng.registry.get("A").shape[1]
    tracer = Tracer()
    riders = [tracer.trace("A"), tracer.trace("A")]
    eng.multiply("A", np.ones(n, np.float32), obs=riders)
    rec = eng.telemetry.records[-1]
    for obs in riders:  # riders share the batch's phases
        spans = _lifecycle(tracer, obs)
        assert spans[1].args == {"batch": 1}
        assert [s.duration_s for s in spans] == [
            rec.load_s, rec.kernel_s, rec.retrieve_s]


def test_register_phases_cover_register(engine):
    _, entry, whole = engine
    assert set(entry.register_s) == set(REGISTER)
    assert sum(entry.register_s.values()) >= 0.95 * whole
    assert sum(entry.register_s.values()) <= whole


def test_disabled_tracing_hands_out_null_trace_and_records_nothing(engine):
    eng, _, _ = engine
    tracer = Tracer(enabled=False)
    obs = tracer.trace("A")
    assert obs is NULL_TRACE
    r = eng.solve("A", np.zeros(eng.registry.get("A").shape[0], np.float32),
                  steps=3, combine="plain", obs=obs)
    assert len(tracer) == 0 and tracer.spans() == []
    assert r.load_s > 0 and r.kernel_s > 0 and r.retrieve_s > 0
    with phase("spmv.solve.loop") as p:  # default trace: the shared no-op
        pass
    assert p.traces == (NULL_TRACE,) and p.seconds >= 0
    with phase("spmv.solve.loop", None) as p:  # no trace at all
        pass
    assert p.traces == ()


def test_phases_are_profiler_host_events_on_the_cpu(engine, tmp_path):
    import jax

    from repro.obs import profile

    eng, _, _ = engine
    n = eng.registry.get("A").shape[0]
    tracer = Tracer()
    obs = tracer.trace("A")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host spans only, as the benchmark
    was = profile.set_enabled(True)
    try:
        with jax.profiler.trace(str(tmp_path), profiler_options=options):
            eng.solve("A", np.ones(n, np.float32), steps=400,
                      combine="power", obs=obs)
    finally:
        profile.set_enabled(was)
    spans = dict(zip(SOLVE, _lifecycle(tracer, obs)))
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    events = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("spmv.solve",) + SOLVE:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns))
    assert {k: len(v) for k, v in events.items()} == {
        k: 1 for k in ("spmv.solve",) + SOLVE}
    (lo, hi), = events["spmv.solve"]
    kids = [events[k][0] for k in SOLVE]
    # nested in the session, in the same order as the repro.obs spans, and
    # covering it within 5%
    assert lo <= kids[0][0] and kids[-1][1] <= hi
    assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))
    assert sum(e - s for s, e in kids) >= 0.95 * (hi - lo)
    for name, (s, e) in zip(SOLVE, kids):
        assert (e - s) * 1e-9 == pytest.approx(spans[name].duration_s,
                                               abs=50e-6), name
