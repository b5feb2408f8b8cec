"""Roofline peaks are keyed by the device kind a record names; a kind
with no published peaks is an error, never a default."""

import pytest

from repro.roofline import analysis


def _record(kind):
    return {"arch": "qwen3-4b", "shape": "decode_32k", "mesh": "16x16",
            "device_kind": kind, "n_devices": 256,
            "info": {"active_params": 4.0e9, "cache_bytes": 0},
            "hlo_stats": {"flops": 1.97e12, "hbm_bytes": 8.19e9,
                          "collectives": {"all-reduce": 1.0e8}},
            "memory_analysis": {"argument_bytes": 9e9, "output_bytes": 0,
                                "temp_bytes": 1e9, "alias_bytes": 0}}


def test_v5e_peaks_set_the_terms():
    peak = analysis.peaks_for("TPU v5 lite")
    assert (peak.flops, peak.hbm_bw, peak.hbm_bytes) == (197e12, 819e9, 16e9)
    row = analysis.analyze_record(_record("TPU v5 lite"))
    assert row["terms_s"]["compute"] == pytest.approx(0.01)
    assert row["terms_s"]["memory"] == pytest.approx(0.01)
    assert row["terms_s"]["collective"] == pytest.approx(2e8 / 50e9)
    assert row["fits_16gb"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "NVIDIA H100"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError, match="no published peaks"):
        analysis.peaks_for(kind)
    with pytest.raises(ValueError, match="no published peaks"):
        analysis.analyze_record(_record(kind))
