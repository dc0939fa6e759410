"""Metric arithmetic: roofline bytes and the table of peaks."""
import numpy as np
import pytest

from bench.harness import peaks, roofline


def test_roofline_bytes_same_for_every_container():
    from repro.core import formats as F

    rng = np.random.default_rng(0)
    a = np.zeros((64, 80), np.float32)
    a[rng.integers(0, 64, 300), rng.integers(0, 80, 300)] = \
        rng.uniform(0.5, 1.5, 300).astype(np.float32)
    nnz = int(np.count_nonzero(a))
    want = roofline.spmv_bytes(nnz, a.shape)
    assert want == 4 * (nnz + 64 + 80)
    containers = {"coo": F.dense_to_coo(a), "csr": F.dense_to_csr(a),
                  "bcsr": F.dense_to_bcsr(a, block=(8, 16)),
                  "bcoo": F.dense_to_bcoo(a, block=(8, 16))}
    for name, m in containers.items():
        values = m.values if hasattr(m, "values") else m.bvalues
        # the nonzeros a container stores, not counting a block's padding
        stored = int(np.count_nonzero(np.asarray(values)))
        assert roofline.spmv_bytes(stored, m.shape) == want, name


def test_roofline_pct_and_missing_busy_time():
    kind = "TPU v5 lite"
    moved = 819e9 * 0.5  # half a second at peak
    assert roofline.roofline_pct(moved, 1.0, kind) == pytest.approx(50.0)
    assert roofline.roofline_pct(moved, 0.0, kind) is None


def test_peaks_table_raises_on_unknown_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.roofline_pct(1e9, 1.0, "cpu")
