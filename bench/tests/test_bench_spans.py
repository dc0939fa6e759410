"""Program spans and kernel names read from the trace (bench/harness/spans.py)."""
import types

import pytest

from bench.harness import manifest as M
from bench.harness import spans as S
from bench.harness import trace as T

DATA = M.BENCH / "tests" / "data"
OLD_CG = str(DATA / "tpu_v5e_cg_small.xplane.pb.gz")

LOOP = "jit(loop_tol)/while/body/while/body"
KERNEL = LOOP + "/iterate.step/jit(run)/shard_map/spmv.coo.xla/scatter-add"
GATHER = LOOP + "/iterate.step/jit(run)/shard_map/spmv.coo.xla/gather"
VECTOR = LOOP + "/iterate.step/add"
R0 = "jit(loop_tol)/jit(run)/shard_map/spmv.coo.xla/scatter-add"
WHILE = "jit(loop_tol)/while"


# ------------------------------------------------------- a hand-made XSpace


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(number: int, value: int) -> bytes:
    return _varint(number << 3) + _varint(value)


def _len(number: int, payload) -> bytes:
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _fixed64(number: int) -> bytes:
    return _varint(number << 3 | 1) + bytes(8)


def _plane(name, lines, names, stats=None):
    """An XPlane: ``lines`` as {line: [(metadata id, offset_ps, dur_ps)]},
    ``names`` as {metadata id: name}, ``stats`` as {metadata id: tf_op}."""
    out = _len(2, name)
    for line, events in lines.items():
        body = _len(2, line) + _int(3, 0)
        for mid, off, dur in events:
            body += _len(4, _int(1, mid) + _int(2, off) + _int(3, dur)
                         + _fixed64(9))  # a field the reader skips
        out += _len(3, body)
    for mid, name_ in names.items():
        meta = _int(1, mid) + _len(2, name_)
        if stats and mid in stats:
            meta += _len(5, _int(1, 7) + _len(5, stats[mid]))
        out += _len(4, _int(1, mid) + _len(2, meta))
    out += _len(5, _int(1, 7) + _len(2, _int(1, 7) + _len(2, "tf_op")))
    return _len(1, out)


def _xspace():
    us = 1_000_000  # ps
    device = _plane("/device:TPU:0", {
        "XLA Modules": [(1, 10 * us, 70 * us)],
        "XLA Ops": [(2, 10 * us, 70 * us),  # while, holding the steps
                    (3, 12 * us, 10 * us),  # r0's product: not a step
                    (4, 30 * us, 20 * us),  # the step's scatter
                    (5, 50 * us, 5 * us),  # the step's vector op
                    (6, 160 * us, 10 * us),  # outside any program
                    ]}, {1: "jit_loop_tol(7)", 2: "%while.1", 3: "%fusion.3",
                         4: "%fusion.17", 5: "%add.2", 6: "%copy"},
        {2: WHILE + ":while", 3: R0 + ":", 4: KERNEL + ":", 5: VECTOR})
    host = _plane("/host:CPU", {"main": [
        (1, 0, 200 * us),  # the window
        (2, 2 * us, 148 * us),  # a session
        (3, 2 * us, 6 * us),  # place
        (4, 8 * us, 72 * us),  # loop
        (5, 80 * us, 70 * us)]},  # fetch
        {1: T.WINDOW_SPAN, 2: "spmv.solve", 3: "spmv.solve.place",
         4: "spmv.solve.loop", 5: "spmv.solve.fetch"})
    return b"".join([device, _len(2, "an error"), host])


def test_parse_reads_a_hand_encoded_xspace():
    planes = S.parse(_xspace())
    assert len(planes.devices) == 1
    assert [(m, st) for _, _, m, st in planes.devices[0]] == [
        ("jit_loop_tol", WHILE), ("jit_loop_tol", R0),
        ("jit_loop_tol", KERNEL), ("jit_loop_tol", VECTOR), ("", "")]
    assert planes.devices[0][2][:2] == (30_000_000, 50_000_000)
    assert [n for _, _, n in planes.host][:2] == [T.WINDOW_SPAN, "spmv.solve"]


def test_summary_of_a_hand_encoded_xspace():
    s = S.summarize_planes(S.parse(_xspace()))
    us = 1e-6
    assert s.window_s == pytest.approx(200 * us)
    assert s.sessions == 1 and s.devices == 1
    assert s.kernel_s == pytest.approx(20 * us)
    # the while's self time (70 - 10 - 20 - 5) and the vector op; r0's
    # product sits in a spmv. scope outside the step, the copy in no
    # program: neither
    assert s.loop_other_s == pytest.approx(40 * us)
    # idle 0-10, 80-160 and 170-200 us, cut at the phases' boundaries
    assert s.idle_by_phase == {
        "spmv.solve.place": pytest.approx(6 * us),  # 2-8
        "spmv.solve.loop": pytest.approx(2 * us),  # 8-10
        "spmv.solve.fetch": pytest.approx(70 * us),  # 80-150
        T.NO_HOST_SPAN: pytest.approx(42 * us)}  # 0-2, 150-160, 170-200


def test_fields_refuses_an_undefined_wire_type():
    with pytest.raises(ValueError, match="wire type"):
        list(S.fields(_varint(1 << 3 | 3) + b"\x00"))


# --------------------------------------------------------------- the rules

WINDOW = (0, 1000, T.WINDOW_SPAN)
SESSION = [(100, 900, "spmv.solve"), (100, 200, "spmv.solve.place"),
           (210, 800, "spmv.solve.loop"), (800, 900, "spmv.solve.fetch"),
           (820, 840, "np.asarray(jax.Array)")]


@pytest.mark.parametrize("idle, charged", [
    ((120, 180), {"spmv.solve.place": 60}),  # inside place
    # from place, through the parent alone, into the loop
    ((190, 226), {"spmv.solve.place": 10, "spmv.solve": 10,
                  "spmv.solve.loop": 16}),
    ((201, 209), {"spmv.solve": 8}),  # between two children
    ((300, 400), {"spmv.solve.loop": 100}),
    # across loop and fetch; a runtime event is not a phase
    ((790, 850), {"spmv.solve.loop": 10, "spmv.solve.fetch": 50}),
    ((920, 980), {T.NO_HOST_SPAN: 60}),  # between sessions
    ((50, 140), {T.NO_HOST_SPAN: 50, "spmv.solve.place": 40}),
])
def test_an_idle_gap_is_split_among_the_innermost_phases_it_overlaps(
        idle, charged):
    busy = [(0, idle[0], "a", ""), (idle[1], 1000, "b", "")]
    s = S.summarize_planes(S.Planes(devices=[busy],
                                    host=[WINDOW] + SESSION))
    assert s.idle_by_phase == {k: pytest.approx(v * 1e-12)
                               for k, v in charged.items()}
    assert s.sessions == 1


@pytest.mark.parametrize("ops, kernel, other", [
    # a step's kernel op under the while: the while keeps its own time
    ([(0, 100, "m", WHILE), (10, 60, "m", KERNEL)], 50, 50),
    # two scopes of one kernel, back to back, and a vector op
    ([(0, 100, "m", WHILE), (10, 30, "m", KERNEL), (30, 50, "m", GATHER),
      (60, 70, "m", VECTOR)], 40, 60),
    # an op nested in a kernel op is charged to its own stack
    ([(0, 100, "m", KERNEL), (20, 30, "m", VECTOR)], 90, 10),
    # r0's product is a spmv. op outside the step: neither
    ([(0, 100, "m", WHILE), (0, 40, "m", R0), (50, 60, "m", KERNEL)], 10, 50),
    # a program with no step is not a loop program
    ([(0, 10, "m", KERNEL), (20, 40, "other", "jit(f)/add")], 10, 0),
])
def test_self_time_under_nested_scopes(ops, kernel, other):
    s = S.summarize_planes(S.Planes(devices=[ops], host=[WINDOW]))
    assert s.kernel_s == pytest.approx(kernel * 1e-12)
    assert s.loop_other_s == pytest.approx(other * 1e-12)


def test_kernel_needs_a_spmv_scope_under_the_step():
    assert S.in_kernel(KERNEL) and S.in_kernel(GATHER)
    assert not S.in_kernel(R0) and not S.in_kernel(VECTOR)
    assert not S.in_kernel("spmv.coo.xla/iterate.step/add")


def test_window_span_is_required():
    with pytest.raises(ValueError, match="bench.window"):
        S.summarize_planes(S.Planes(devices=[[]], host=[(0, 1, "x")]))


# ----------------------------------------------------------- recorded traces


def test_recorded_trace_tf_op_names_the_scatter_add():
    planes = S.read_planes(OLD_CG)
    devices, host = T.read_planes(OLD_CG)
    ops = planes.devices[0]
    assert len(ops) == len(devices[0]) and len(planes.host) == len(host)
    # the same events on the same clock (ProfileData truncates start and
    # duration to the ns)
    assert all(abs(s / 1000 - t[0]) < 1 and abs(e / 1000 - t[1]) < 2
               for (s, e, _, _), t in zip(ops, devices[0]))
    stacks = {st for _, _, _, st in ops}
    assert any(st.endswith("jit(run)/scatter-add") for st in stacks)
    assert "jit(loop_tol)/while/body/while/body/jit(run)/scatter-add" in stacks
    assert {m for _, _, m, _ in ops} <= {"jit_loop_tol",
                                         "jit_convert_element_type", ""}


def _run(trace):
    return types.SimpleNamespace(
        trace=trace, nnz=4096, shape=(216, 216), itemsize=4,
        device_kind="TPU v5 lite", window={"spmv_calls": 80})


NEW = ("kernel.spmv_roofline", "solve.vector_us", "solve.idle_ms.place",
       "solve.idle_ms.fetch", "solve.idle_ms.loop")


@pytest.mark.parametrize("name", NEW)
def test_readers_report_nothing_for_a_program_that_names_nothing(
        name, monkeypatch):
    monkeypatch.setattr(S, "of_run", lambda run: S.summarize(OLD_CG))
    reader = M.metric_reader(name)
    assert reader.read(_run(trace=object())) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_report_nothing_for_an_untraced_run(name):
    assert M.metric_reader(name).read(_run(trace=None)) is None


def test_a_parse_is_cached_per_file():
    S._summarize_cached.cache_clear()
    first = S.summarize(OLD_CG)
    assert S.summarize(OLD_CG) is first
    assert S._summarize_cached.cache_info().hits == 1


# ------------------------------------------ a trace of the named program

NAMED = str(DATA / "tpu_v5e_cg_named_small.xplane.pb.gz")  # one TPU v5e,
# bench/run.py --trace 1 of hpcg32.cg at a 6x6x6 stencil, 0.06 s window


def _hand_count():
    """The recorded trace's numbers, counted op by op and gap by gap with
    none of the reader's helpers: quadratic loops over its events."""
    planes = S.read_planes(NAMED)
    lo, hi = [(s, e) for s, e, n in planes.host if n == T.WINDOW_SPAN][0]
    ops = [(max(s, lo), min(e, hi), m, st) for s, e, m, st in planes.devices[0]
           if e > lo and s < hi]
    loops = {m for _, _, m, st in ops if "iterate.step" in st.split("/")}
    kernel = other = 0
    for i, (s, e, m, st) in enumerate(ops):
        inside = [o for j, o in enumerate(ops) if j != i and s <= o[0]
                  and o[1] <= e and (o[0], -o[1]) > (s, -e)]
        direct = [o for o in inside if not any(
            p is not o and p[0] <= o[0] and o[1] <= p[1] for p in inside)]
        own = (e - s) - sum(o[1] - o[0] for o in direct)
        parts = st.split("/")
        if "iterate.step" in parts and any(
                p.startswith("spmv.")
                for p in parts[parts.index("iterate.step"):]):
            kernel += own
        elif m in loops and not any(p.startswith("spmv.") for p in parts):
            other += own
    phases = [(s, e, n) for s, e, n in planes.host
              if n.startswith("spmv.solve")]
    idle, t = {}, lo
    for s, e, _, _ in sorted(ops) + [(hi, hi, "", "")]:
        if s > t:  # a gap from t to s, cut at each phase boundary in it
            cuts = sorted({t, s} | {b for ps, pe, _ in phases
                                    for b in (ps, pe) if t < b < s})
            for a, b in zip(cuts, cuts[1:]):
                holding = [(pe - ps, n) for ps, pe, n in phases
                           if ps <= a and b <= pe]
                name = min(holding)[1] if holding else T.NO_HOST_SPAN
                idle[name] = idle.get(name, 0) + (b - a)
        t = max(t, e)
    sessions = sum(1 for s, e, n in phases
                   if n == "spmv.solve" and lo <= s and e <= hi)
    return {"kernel_s": kernel * 1e-12, "loop_other_s": other * 1e-12,
            "idle": {k: v * 1e-12 for k, v in idle.items()},
            "sessions": sessions, "window_s": (hi - lo) * 1e-12,
            "busy_s": T.reduce_trace(NAMED).busy_s}


def test_recorded_named_trace_through_the_accepted_reader():
    """trace.py reads the named program's trace as it reads the unnamed one:
    the scopes and phase spans change no busy, idle or op-label number."""
    devices, host = T.read_planes(NAMED)
    assert len(devices) == 1 and len(devices[0]) > 100
    (lo, hi), = [(s, e) for s, e, n in host if n == T.WINDOW_SPAN]
    s = T.reduce_events(devices, host)
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0 < s.busy_s < s.window_s and 0 < s.idle_pct < 100
    assert sum(v for _, v in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9
    assert sum(v for _, v in s.device_ops) <= s.busy_s * (1 + 1e-9)
    assert all(n.startswith("jit_loop_tol:") for n, _ in s.device_ops)


def test_recorded_named_trace_matches_a_hand_count():
    want = _hand_count()
    s = S.summarize(NAMED)
    assert s.sessions == want["sessions"] == 11
    assert s.kernel_s == pytest.approx(want["kernel_s"], rel=1e-9)
    assert s.loop_other_s == pytest.approx(want["loop_other_s"], rel=1e-9)
    assert s.idle_by_phase == pytest.approx(want["idle"], rel=1e-9)
    # every idle ps is charged once (trace.py's busy time comes through
    # ProfileData, which truncates each op to the ns)
    assert sum(s.idle_by_phase.values()) == pytest.approx(
        want["window_s"] - want["busy_s"], abs=2e-9 * 1500)
    phases = sum(s.idle_by_phase[f"spmv.solve.{p}"]
                 for p in ("place", "loop", "fetch"))
    assert phases >= 0.9 * (want["window_s"] - want["busy_s"])
    assert 0 < s.kernel_s < want["busy_s"]


def test_recorded_named_trace_readers(monkeypatch):
    want = _hand_count()
    monkeypatch.setattr(S, "of_run", lambda run: S.summarize(NAMED))
    steps = 16 * want["sessions"]  # every session of the 6x6x6 CG: 16 steps
    run = types.SimpleNamespace(
        trace=object(), nnz=4096, shape=(216, 216), itemsize=4,
        device_kind="TPU v5 lite", window={"spmv_calls": steps})
    read = {n: M.metric_reader(n).read(run) for n in NEW}
    least = 4 * (4096 + 216 + 216) * steps / 819e9
    assert read["kernel.spmv_roofline"] == pytest.approx(
        100 * least / want["kernel_s"])
    assert read["kernel.spmv_roofline"] > M.metric_reader(
        "spmv_roofline").read(types.SimpleNamespace(
            trace=types.SimpleNamespace(busy_s=want["busy_s"]), **{
                k: getattr(run, k) for k in ("nnz", "shape", "itemsize",
                                             "device_kind", "window")}))
    assert read["solve.vector_us"] == pytest.approx(
        1e6 * want["loop_other_s"] / steps)
    for phase in ("place", "fetch", "loop"):
        assert read[f"solve.idle_ms.{phase}"] == pytest.approx(
            1e3 * want["idle"][f"spmv.solve.{phase}"] / want["sessions"])
