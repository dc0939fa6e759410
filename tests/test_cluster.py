"""repro.cluster — protocol framing, hash ring, multi-process cache safety,
trace merging, the gate_factor tooling, and live worker/router integration.

The integration tests spawn real worker processes (spawn start method, each
with its own JAX runtime) — a module-scoped router keeps that to one fleet
for the happy-path tests; the kill-mid-replay failover test builds its own
disposable fleet.  Every multiply result is checked bit-exactly against the
dense oracle (integer-valued matrices + integer payloads make float32 SpMV
exact in any summation order).
"""
import json
import os
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.cluster import HashRing
from repro.cluster.protocol import (
    MAX_FRAME,
    ConnectionClosed,
    recv_msg,
    send_msg,
)
from repro.obs import merge_chrome_traces
from repro.tune import TuneKey, TuningCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ protocol


def test_protocol_roundtrip():
    a, b = socket.socketpair()
    try:
        msg = {"verb": "multiply", "x": np.arange(5.0), "name": "m"}
        send_msg(a, msg)
        got = recv_msg(b)
        assert got["verb"] == "multiply"
        np.testing.assert_array_equal(got["x"], msg["x"])
    finally:
        a.close()
        b.close()


def test_protocol_eof_is_connection_closed():
    a, b = socket.socketpair()
    a.close()
    try:
        with pytest.raises(ConnectionClosed):
            recv_msg(b)
    finally:
        b.close()


def test_protocol_bad_magic_rejected():
    a, b = socket.socketpair()
    try:
        a.sendall(b"XXXX" + (0).to_bytes(4, "big"))
        with pytest.raises(ValueError, match="magic"):
            recv_msg(b)
    finally:
        a.close()
        b.close()


def test_protocol_oversized_length_rejected():
    a, b = socket.socketpair()
    try:
        a.sendall(b"SPRP" + (MAX_FRAME + 1).to_bytes(4, "big"))
        with pytest.raises(ValueError, match="length"):
            recv_msg(b)
    finally:
        a.close()
        b.close()


# ------------------------------------------------------------ hash ring


def test_ring_lookup_deterministic_and_total():
    ring = HashRing()
    for w in ("w0", "w1", "w2"):
        ring.add(w)
    keys = [f"fp{i}" for i in range(200)]
    owners = {k: ring.lookup(k) for k in keys}
    assert owners == {k: ring.lookup(k) for k in keys}  # stable
    assert set(owners.values()) == {"w0", "w1", "w2"}  # all nodes used


def test_ring_removal_only_remaps_the_dead_node():
    ring = HashRing()
    for w in ("w0", "w1", "w2"):
        ring.add(w)
    keys = [f"fp{i}" for i in range(200)]
    before = {k: ring.lookup(k) for k in keys}
    ring.remove("w1")
    after = {k: ring.lookup(k) for k in keys}
    for k in keys:
        if before[k] != "w1":
            assert after[k] == before[k]  # survivors' keys stay put
        else:
            assert after[k] in ("w0", "w2")


def test_ring_successors_distinct_and_ordered():
    ring = HashRing()
    for w in ("w0", "w1", "w2"):
        ring.add(w)
    succ = ring.successors("some-key", 3)
    assert len(succ) == 3 and len(set(succ)) == 3
    assert succ[0] == ring.lookup("some-key")
    assert ring.successors("some-key", 5) == succ  # only 3 nodes exist


def test_ring_empty_lookup_raises():
    with pytest.raises(LookupError):
        HashRing().lookup("fp")


# ----------------------------------------- TuningCache multi-process safety


def _rec(tag: str) -> dict:
    return {"scheme": {"partitioning": "1d", "scheme": "nnz", "fmt": "coo",
                       "merge": "ppermute", "grid": [1, 1], "reason": tag},
            "impl": "xla", "mean_s": 1.0}


def _key(name: str) -> TuneKey:
    return TuneKey(fingerprint=name, topology="cpu:1", dtype="float32")


def test_cache_hit_miss_counters():
    cache = TuningCache()
    assert cache.get(_key("a")) is None
    cache.put(_key("a"), _rec("a"))
    assert cache.get(_key("a")) is not None
    assert (cache.hits, cache.misses) == (1, 1)
    assert _key("a") in cache  # __contains__ counts too
    assert cache.hits == 2


def test_cache_export_ingest_roundtrip(tmp_path):
    src = TuningCache()
    src.put(_key("a"), _rec("a"))
    dst = TuningCache()
    assert dst.ingest(src.export(_key("a"))) == 1
    assert dst.get(_key("a"))["scheme"]["reason"] == "a"


def test_cache_refresh_sees_other_writers(tmp_path):
    path = str(tmp_path / "tune.json")
    ours, theirs = TuningCache(path), TuningCache(path)
    theirs.put(_key("theirs"), _rec("theirs"))
    assert ours.get(_key("theirs")) is None  # loaded before their write
    ours.put(_key("ours"), _rec("ours"))  # save merges but keeps our view
    ours.refresh()
    assert ours.get(_key("theirs")) is not None
    assert ours.get(_key("ours")) is not None


def test_cache_two_processes_hammer_one_path(tmp_path):
    """Two concurrent writer processes, one cache file: merge-on-write must
    keep BOTH writers' disjoint keys (a naive tmp+rename would clobber the
    loser's) and converge shared keys to one writer's value."""
    path = str(tmp_path / "tune.json")
    script = r"""
import sys
sys.path.insert(0, {src!r})
from repro.tune import TuneKey, TuningCache
who, path = sys.argv[1], sys.argv[2]
cache = TuningCache(path)
rec = lambda tag: {{"scheme": {{"partitioning": "1d", "scheme": "nnz",
                   "fmt": "coo", "merge": "ppermute", "grid": [1, 1],
                   "reason": tag}}, "impl": "xla", "mean_s": 1.0}}
for i in range(25):
    cache.put(TuneKey(fingerprint=f"{{who}}-{{i}}", topology="cpu:1",
                      dtype="float32"), rec(who))
for i in range(5):
    cache.put(TuneKey(fingerprint=f"shared-{{i}}", topology="cpu:1",
                      dtype="float32"), rec(who))
""".format(src=os.path.join(ROOT, "src"))
    procs = [
        subprocess.Popen([sys.executable, "-c", script, who, path],
                         stderr=subprocess.PIPE)
        for who in ("alpha", "beta")
    ]
    for p in procs:
        _, err = p.communicate(timeout=120)
        assert p.returncode == 0, err.decode()
    merged = TuningCache(path)
    assert merged.load_error is None
    assert len(merged) == 55  # 2 x 25 disjoint + 5 shared
    for who in ("alpha", "beta"):
        for i in range(25):
            rec = merged.get(_key(f"{who}-{i}"))
            assert rec is not None and rec["scheme"]["reason"] == who
    for i in range(5):
        rec = merged.get(_key(f"shared-{i}"))
        assert rec["scheme"]["reason"] in ("alpha", "beta")  # one winner


def test_cache_corrupt_file_degrades(tmp_path):
    path = str(tmp_path / "tune.json")
    with open(path, "w") as fh:
        fh.write("{not json")
    cache = TuningCache(path)
    assert cache.load_error is not None and len(cache) == 0
    cache.put(_key("a"), _rec("a"))  # save must recover the file
    assert TuningCache(path).get(_key("a")) is not None


# ------------------------------------------------------------ trace merge


def test_merge_chrome_traces_repids_and_labels():
    doc = {"traceEvents": [
        {"name": "kernel", "ph": "X", "pid": 1, "tid": 7, "ts": 0.0,
         "dur": 5.0, "args": {}},
        {"name": "process_name", "ph": "M", "pid": 1,
         "args": {"name": "repro.serve replay"}},
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 7,
         "args": {"name": "req"}},
    ]}
    merged = merge_chrome_traces([doc, doc], labels=["w0", "w1"])
    pids = {ev["pid"] for ev in merged["traceEvents"]}
    assert pids == {1, 2}  # one Perfetto process row per worker
    names = {(ev["pid"], ev["args"]["name"])
             for ev in merged["traceEvents"]
             if ev.get("ph") == "M" and ev["name"] == "process_name"}
    assert names == {(1, "w0"), (2, "w1")}  # old process_name replaced
    # the original documents were not mutated
    assert doc["traceEvents"][0]["pid"] == 1


def test_merge_chrome_traces_defaults_and_empty_docs():
    merged = merge_chrome_traces([{"traceEvents": []}, {}])
    names = [ev["args"]["name"] for ev in merged["traceEvents"]
             if ev["name"] == "process_name"]
    assert names == ["worker-0", "worker-1"]  # empty docs keep their pid


# --------------------------------------------------- check_bench gate_factor


def _check_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_bench", os.path.join(ROOT, "tools", "check_bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gate_factor_loosens_only_its_row(tmp_path):
    cb = _check_bench()
    base_doc = {"rows": [
        {"name": "tight", "us_per_call": 100.0, "derived": ""},
        {"name": "loose", "us_per_call": 100.0, "derived": "",
         "gate_factor": 8.0},
    ]}
    p = tmp_path / "base.json"
    p.write_text(json.dumps(base_doc))
    base, gates = cb.load_rows(str(p))
    assert gates == {"loose": 8.0}
    cur = {"tight": 400.0, "loose": 400.0}  # both 4x slower
    regressions, missing, new = cb.compare(base, cur, 2.5, gates)
    assert [r[0] for r in regressions] == ["tight"]  # loose passed at 8x
    # and the loose row still regresses past ITS gate
    regressions, _, _ = cb.compare(base, {"tight": 100.0, "loose": 900.0},
                                   2.5, gates)
    assert [r[0] for r in regressions] == ["loose"]


def test_gate_factor_invalid_values_fail_loudly(tmp_path):
    """A present-but-broken gate_factor must name its row and fail, never
    coerce: True would otherwise become a silent 1.0x gate."""
    cb = _check_bench()
    for bad in ("8x", True, False, 0, -2.5, [8.0]):
        doc = {"rows": [{"name": "r", "us_per_call": 100.0, "derived": "",
                         "gate_factor": bad}]}
        p = tmp_path / "bad.json"
        p.write_text(json.dumps(doc))
        with pytest.raises(ValueError, match="'r'.*gate_factor"):
            cb.load_rows(str(p))
    # an int gate (valid JSON spelling of a number) still loads
    doc = {"rows": [{"name": "r", "us_per_call": 100.0, "derived": "",
                     "gate_factor": 8}]}
    p = tmp_path / "ok.json"
    p.write_text(json.dumps(doc))
    _, gates = cb.load_rows(str(p))
    assert gates == {"r": 8.0}


def test_gate_factor_from_current_run_never_applies(tmp_path):
    cb = _check_bench()
    base_doc = {"rows": [{"name": "r", "us_per_call": 100.0, "derived": ""}]}
    cur_doc = {"rows": [{"name": "r", "us_per_call": 900.0, "derived": "",
                         "gate_factor": 100.0}]}
    pb, pc = tmp_path / "b.json", tmp_path / "c.json"
    pb.write_text(json.dumps(base_doc))
    pc.write_text(json.dumps(cur_doc))
    base, gates = cb.load_rows(str(pb))
    cur, _ = cb.load_rows(str(pc))  # current-side gates are dropped
    regressions, _, _ = cb.compare(base, cur, 2.5, gates)
    assert [r[0] for r in regressions] == ["r"]


# ------------------------------------------------- worker/router integration


def _cluster_mats():
    rng = np.random.default_rng(3)
    mats = {}
    for name in ("hot", "warm", "cold"):
        a = np.round(rng.standard_normal((48, 40)) * 2.0).astype(np.float32)
        a[np.abs(a) < 1] = 0.0
        mats[name] = a
    return mats


def test_router_refuses_more_workers_than_free_chips(monkeypatch):
    """On an accelerator a worker opens every chip it sees, so the router
    refuses a second worker, and any worker while this process holds the
    chips — before it spawns anything."""
    from repro.cluster import router as R

    def no_spawn(*args, **kwargs):
        raise AssertionError("a worker was spawned")

    monkeypatch.setattr(R, "spawn_worker", no_spawn)
    monkeypatch.setattr(R, "worker_platform", lambda: ("tpu", 4))
    with pytest.raises(ValueError, match="at most 1 worker"):
        R.ClusterRouter(workers=2)
    monkeypatch.setattr(R, "worker_platform", lambda: ("tpu", 0))
    with pytest.raises(ValueError, match="at most 0 worker"):
        R.ClusterRouter(workers=1)


def test_router_process_never_opens_a_device():
    """The router's own process imports JAX but never initialises a
    backend, so on a chip host its one worker can open the chip."""
    script = r"""
import sys
sys.path.insert(0, {src!r})
import numpy as np
from jax._src import xla_bridge
from repro.cluster import ClusterRouter

a = np.eye(16, dtype=np.float32) * 3.0
x = np.arange(16, dtype=np.float32)
with ClusterRouter(workers=1, connect_timeout=300.0) as router:
    router.register("a", a)
    assert np.array_equal(router.multiply("a", x), a @ x)
print("BACKEND_INITIALISED", xla_bridge.backends_are_initialized())
""".format(src=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "BACKEND_INITIALISED False" in proc.stdout


@pytest.fixture(scope="module")
def cluster():
    from repro.cluster import ClusterRouter

    mats = _cluster_mats()
    router = ClusterRouter(workers=2, replicate_share=0.6,
                           replicate_check=4, connect_timeout=300.0)
    try:
        yield router, mats
    finally:
        router.close()


def _request(mats, name, seed, batch=1):
    rng = np.random.default_rng(seed)
    cols = mats[name].shape[1]
    shape = (cols,) if batch == 1 else (cols, batch)
    return rng.integers(-3, 4, size=shape).astype(np.float32)


def test_cluster_register_and_bit_exact_multiply(cluster):
    router, mats = cluster
    for name, a in mats.items():
        info = router.register(name, a)
        assert info["placements"], info
    for name, a in mats.items():
        for seed, batch in ((1, 1), (2, 4)):
            x = _request(mats, name, seed, batch)
            y = router.multiply(name, x)
            assert np.array_equal(y, (a @ x).astype(np.float32))


def test_cluster_tuned_rehydration_zero_measurements(cluster):
    """A worker receiving a tune record rebuilds the winner purely from its
    TuningCache: from_cache=True, zero measurements, hits counter moved —
    the acceptance criterion's auditable no-re-measurement proof."""
    import jax

    from repro.api import SparseMatrix
    from repro.tune import CandidateGenerator, FakeMeasurer, Tuner

    router, mats = cluster
    a = mats["hot"]
    tuner = Tuner(generator=CandidateGenerator(impls=("xla",)),
                  measurer=FakeMeasurer(), cache=TuningCache())
    result = tuner.tune(SparseMatrix.from_dense(a), devices=jax.devices())
    record = {"entries": tuner.cache.export(result.key), "impls": ["xla"],
              "batch": None, "block": [8, 16]}
    info = router.register("hot-tuned", a, tune_record=record)
    assert info["source"] == "tune_cache"
    assert info["from_cache"] is True
    assert info["measurements"] == 0  # nothing was re-measured
    assert info["tune_hits"] >= 1  # the cache answered
    assert info["scheme_id"] == result.best.scheme_id
    x = _request(mats, "hot", 5)
    y = router.multiply("hot-tuned", x)
    assert np.array_equal(y, (a @ x).astype(np.float32))


def test_cluster_ir_registration_preserves_scheme(cluster):
    from repro.api import SparseMatrix

    router, mats = cluster
    a = mats["warm"]
    ep = SparseMatrix.from_dense(a).plan(scheme="1d.nnz", fmt="csr")
    info = router.register("warm-ir", a, ir=ep.to_ir())
    assert info["source"] == "ir"
    assert info["scheme_id"] == ep.scheme_id
    x = _request(mats, "warm", 6)
    y = router.multiply("warm-ir", x)
    assert np.array_equal(y, (a @ x).astype(np.float32))


def test_cluster_popularity_replicates_hot_matrix(cluster):
    router, mats = cluster
    entry = router.entries["hot"]
    for seed in range(40):  # all traffic to one name clears the threshold
        router.multiply("hot", _request(mats, "hot", 100 + seed))
    assert len(entry.placements) == 2, router.stats()["entries"]["hot"]


def test_cluster_drain_and_stats(cluster):
    router, mats = cluster
    drained = router.drain()
    assert drained and all(d["drained"] for d in drained.values())
    st = router.stats()
    assert set(st["workers"]) == {"w0", "w1"}
    served = sum(w.get("served", 0) for w in st["workers"].values())
    assert served >= st["routed"] / 8  # batches count once served
    for w in st["workers"].values():
        if "entries" in w:
            for e in w["entries"].values():
                assert {"scheme_id", "fingerprint", "requests"} <= set(e)


def test_cluster_trace_merge_has_one_pid_per_worker(cluster):
    router, mats = cluster
    merged = router.dump_traces()
    by_pid = {}
    for ev in merged["traceEvents"]:
        if ev.get("ph") == "M" and ev["name"] == "process_name":
            by_pid[ev["pid"]] = ev["args"]["name"]
    assert sorted(by_pid.values()) == ["w0", "w1"]
    span_pids = {ev["pid"] for ev in merged["traceEvents"]
                 if ev.get("ph") == "X"}
    assert span_pids  # worker spans actually made it across


def test_cluster_kill_worker_mid_replay_loses_nothing():
    """The headline failover guarantee: SIGKILL one worker while a replay
    is in flight — every request either completes bit-exactly (re-routed)
    or sheds with reason worker_lost; none are lost, none are wrong."""
    from repro.cluster import ClusterRouter
    from repro.cluster.replay import replay_cluster
    from repro.serve.workload import WorkloadSpec, generate_trace

    mats = _cluster_mats()
    spec = WorkloadSpec(names=tuple(mats), n_requests=40, seed=11,
                        rate_rps=500.0, integer_values=True,
                        batch_mix={1: 0.8, 4: 0.2})
    trace = generate_trace(spec)
    with ClusterRouter(workers=2, connect_timeout=300.0) as router:
        for name, a in mats.items():
            router.register(name, a, replicas=2)
        report = replay_cluster(router, trace, mats, threads=2,
                                kill_after=8, kill_worker="w0")
        assert report.lost == 0, report.summary()
        assert report.bit_exact, report.summary()
        assert {s["reason"] for s in report.shed} <= {"worker_lost"}
        assert report.accepted + len(report.shed) == len(trace)
        assert report.failovers >= 1  # the kill was actually observed
        assert router.workers["w1"].alive()
        # the surviving worker answered everything accepted after the kill
        y = router.multiply("hot", _request(mats, "hot", 99))
        assert np.array_equal(
            y, (mats["hot"] @ _request(mats, "hot", 99)).astype(np.float32)
        )


def test_cluster_concurrent_multiplies_are_safe(cluster):
    router, mats = cluster
    errors = []

    def worker_thread(seed):
        try:
            for i in range(5):
                name = ("hot", "warm", "cold")[i % 3]
                x = _request(mats, name, seed * 100 + i)
                y = router.multiply(name, x)
                assert np.array_equal(
                    y, (mats[name] @ x).astype(np.float32)
                )
        except Exception as e:  # surfaced below; threads must not die silent
            errors.append(e)

    threads = [threading.Thread(target=worker_thread, args=(s,))
               for s in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
