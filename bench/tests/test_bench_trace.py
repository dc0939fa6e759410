"""Trace reduction: busy/idle union, top device ops, idle gaps by host span."""
import numpy as np
import pytest

from bench.harness import manifest as M
from bench.harness import trace as T

MS = 1_000_000  # ns


def test_union_merges_overlaps_and_nesting():
    got = T.union([(5, 9), (0, 2), (1, 3), (6, 7), (9, 10)])
    assert got == [[0, 3], [5, 10]]


def test_gaps_are_the_complement_inside_the_window():
    busy = [[2, 4], [6, 7]]
    assert T.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert T.gaps(busy, 3, 6.5) == [(4, 6)]
    assert T.gaps([], 0, 5) == [(0, 5)]


def test_gap_named_by_innermost_host_span():
    host = [(0, 100, "outer"), (10, 20, "inner"), (15, 18, "innermost"),
            (50, 60, "other")]
    gaps = [(16, 17), (11, 13), (30, 40), (55, 56), (200, 210)]
    names = T.name_gaps(gaps, host)
    assert names == ["innermost", "inner", "outer", "other", T.NO_HOST_SPAN]


def test_reduce_events_busy_idle_and_breakdown():
    host = [(0, 100 * MS, T.WINDOW_SPAN),
            (0, 100 * MS, "session"),
            (40 * MS, 60 * MS, "fetch x")]
    ops = [(-5 * MS, 10 * MS, "scatter"),  # clipped at the window's start
           (5 * MS, 30 * MS, "scatter"),  # overlaps the first
           (30 * MS, 40 * MS, "fusion"),
           (70 * MS, 90 * MS, "scatter"),
           (95 * MS, 130 * MS, "fusion")]  # clipped at the end
    s = T.reduce_events([ops], host)
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.065)  # 0-40, 70-90, 95-100 ms
    assert s.idle_pct == pytest.approx(35.0)
    # the two overlapping scatters count their shared 5 ms once
    assert s.device_ops[0] == ["scatter", pytest.approx(0.050)]
    assert s.device_ops[1] == ["fusion", pytest.approx(0.015)]
    names = dict(s.idle_gaps)
    # the 40-70 ms gap's midpoint falls in "fetch x", the 90-95 ms one's not
    assert names == {"fetch x": pytest.approx(0.030),
                     "session": pytest.approx(0.005)}


def test_reduce_events_averages_over_devices():
    host = [(0, 10 * MS, T.WINDOW_SPAN)]
    s = T.reduce_events([[(0, 10 * MS, "a")], []], host)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(0.005)


def test_window_span_is_required():
    with pytest.raises(ValueError, match="bench.window"):
        T.reduce_events([[]], [(0, 1, "other")])


def test_op_label_keeps_name_and_array_shape():
    hlo = ("%fusion.1 = f32[512,8]{1,0:T(8,128)S(1)} fusion(s32[9616]{0} "
           "%fusion.10), kind=kCustom")
    assert T.op_label(hlo) == "fusion.1 f32[512,8]"
    loop = "%while.35 = (f32[512]{0}, s32[]{:T(128)}) while((f32[512]{0})"
    assert T.op_label(loop) == "while.35"
    assert T.module_label("jit_loop_tol(8123)") == "jit_loop_tol"


def test_nested_ops_count_their_self_time_once():
    host = [(0, 100, T.WINDOW_SPAN)]
    ops = [(0, 100, "while"), (10, 30, "scatter"), (40, 50, "scatter"),
           (60, 70, "fusion")]
    s = T.reduce_events([ops], host)
    assert s.busy_s == pytest.approx(100e-9)
    assert dict(s.device_ops) == {"while": pytest.approx(60e-9),
                                  "scatter": pytest.approx(30e-9),
                                  "fusion": pytest.approx(10e-9)}
    assert sum(v for _, v in s.device_ops) == pytest.approx(s.busy_s)


DATA = M.BENCH / "tests" / "data"
RECORDED = {
    # one TPU v5e, bench/run.py --trace 1 at a small size (a 6x6x6 stencil
    # for CG, a scale-8 Kronecker graph for serving)
    "tpu_v5e_cg_small.xplane.pb.gz": "jit_loop_tol:",
    "tpu_v5e_serve_small.xplane.pb.gz": "jit_run:",
}


def brute_force_busy_ns(ops, lo, hi):
    """Busy time by marking 1-us bins: independent of the interval union."""
    bins = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for s, e, _ in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            bins[int((s - lo) // 1000):int((e - lo) // 1000) + 1] = True
    return bins.sum() * 1000.0


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_tpu_trace(name):
    devices, host = T.read_planes(str(DATA / name))
    assert len(devices) == 1 and len(devices[0]) > 100
    (lo, hi), = [(s, e) for s, e, n in host if n == T.WINDOW_SPAN]
    s = T.reduce_events(devices, host)
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    # each op can shift the 1-us bin count by at most two bins
    slack = 2e-6 * len(devices[0])
    assert s.busy_s == pytest.approx(
        brute_force_busy_ns(devices[0], lo, hi) * 1e-9, abs=slack)
    assert 0 < s.busy_s < s.window_s and 0 < s.idle_pct < 100
    idle = sum(v for _, v in s.idle_gaps)
    ops = sum(v for _, v in s.device_ops)
    assert idle <= s.window_s - s.busy_s + 1e-9
    assert ops <= s.busy_s * (1 + 1e-9)
    assert all(n.startswith(RECORDED[name]) for n, _ in s.device_ops)


def test_recorded_cg_trace_names_gaps_and_kernels():
    s = T.reduce_trace(str(DATA / "tpu_v5e_cg_small.xplane.pb.gz"))
    top_two = [n for n, _ in s.device_ops[:2]]
    # the scatter-add's output (rows) and the gather over the nonzeros
    assert top_two == ["jit_loop_tol:fusion.17 f32[216]",
                       "jit_loop_tol:fusion.16 f32[4096]"]
    named = [n for n, _ in s.idle_gaps if n != T.NO_HOST_SPAN]
    assert "np.asarray(jax.Array)" in named
