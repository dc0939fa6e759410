"""Roofline machinery: HLO collective parser, extrapolation, analytic FLOPs."""
import numpy as np
import pytest

from repro.analysis import roofline as R
from repro.configs import get_config

HLO_SAMPLE = """
HloModule test
%fused (x: f32[8,16]) -> f32[8,16] { ... }
%ag = bf16[256,1024]{1,0} all-gather(%p0), replica_groups=...
%ar.5 = f32[128]{0} all-reduce(%x), to_apply=%add
%rs = f32[32,64]{1,0} reduce-scatter(%y), dimensions={0}
%a2a = (bf16[8,4]{1,0}, bf16[8,4]{1,0}) all-to-all(%a, %b)
%cp = f32[16]{0} collective-permute(%z), source_target_pairs=...
%dot = f32[64,64]{1,0} dot(%l, %r)
"""


def test_collective_parser():
    out = R.collective_bytes(HLO_SAMPLE)
    assert out["all-gather"] == 256 * 1024 * 2
    assert out["all-reduce"] == 128 * 4
    assert out["reduce-scatter"] == 32 * 64 * 4
    assert out["all-to-all"] == 2 * 8 * 4 * 2
    assert out["collective-permute"] == 16 * 4
    assert out["total"] == sum(
        out[k] for k in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute"))


def test_extrapolate_linear():
    l1 = R.CostTerms(flops=10.0, bytes_hbm=100.0, coll_bytes=7.0)
    l2 = R.CostTerms(flops=16.0, bytes_hbm=130.0, coll_bytes=9.0)
    tot = R.extrapolate(l1, l2, n_repeats=10)
    np.testing.assert_allclose(tot.flops, 4 + 10 * 6)
    np.testing.assert_allclose(tot.bytes_hbm, 70 + 10 * 30)
    np.testing.assert_allclose(tot.coll_bytes, 5 + 10 * 2)


def test_model_flops_train_scales_6nd():
    cfg = get_config("llama3.2-1b")
    mf = R.model_flops(cfg, "train_4k")
    n = cfg.n_params
    tokens = 4096 * 256
    assert mf >= 6 * n * tokens  # attention adds on top
    assert mf < 9 * n * tokens


def test_model_flops_decode_much_smaller():
    cfg = get_config("llama3.2-1b")
    assert R.model_flops(cfg, "decode_32k") < R.model_flops(cfg, "train_4k") / 1e3


def test_moe_uses_active_params():
    cfg = get_config("deepseek-v3-671b")
    mf = R.model_flops(cfg, "train_4k")
    # bounded by active (37B), not total (671B)
    assert mf < 6 * 60e9 * 4096 * 256
    assert mf > 6 * 30e9 * 4096 * 256


def test_roofline_report_fields():
    cfg = get_config("smollm-360m")
    terms = R.CostTerms(flops=1e12, bytes_hbm=1e8, coll_bytes=1e8)
    rep = R.roofline_report(cfg, "train_4k", 256, terms, "TPU v5 lite")
    for k in ("compute_s", "memory_s", "collective_s", "dominant",
              "model_flops", "useful_ratio", "roofline_fraction"):
        assert k in rep
    assert rep["dominant"] == "compute_s"
    assert rep["roofline_fraction"] > 0  # synthetic terms: no upper bound


def test_peaks_refuse_an_unknown_device_kind():
    assert R.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    cfg = get_config("smollm-360m")
    terms = R.CostTerms(flops=1e12, bytes_hbm=1e8, coll_bytes=1e8)
    with pytest.raises(KeyError, match="no published peaks"):
        R.roofline_report(cfg, "train_4k", 256, terms, "cpu")


def test_slstm_correction_only_for_slstm():
    assert R.slstm_scan_correction(get_config("llama3.2-1b"), "train_4k") == 0
    assert R.slstm_scan_correction(get_config("xlstm-1.3b"), "train_4k") > 0
    assert R.slstm_scan_correction(get_config("xlstm-1.3b"), "decode_32k") == 0
