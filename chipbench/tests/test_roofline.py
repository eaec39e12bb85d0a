import pytest

from chipbench import peaks, roofline


def test_analyze_reads_prev_and_curr_once():
    assert roofline.analyze_bytes(25_000_000, 4) == 200_000_000


def test_dequant_counts_the_index_at_b_bits():
    # 25 M indices at B = 5 pack to 15.625 MB; prev and R are 100 MB each.
    assert roofline.dequant_bytes(25_000_000, 4, 5) == 215_625_000
    assert roofline.dequant_bytes(3, 4, 5) == 2 + 24      # 15 bits -> 2 bytes


def test_share_of_the_roofline():
    assert roofline.roofline_pct(819e9, 1.0, 819e9) == pytest.approx(100.0)
    assert roofline.roofline_pct(819e9, 4.0, 819e9) == pytest.approx(25.0)
    assert roofline.roofline_pct(1.0, 0.0, 819e9) is None


def test_peaks_are_looked_up_by_device_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in v5e["source"]


def test_a_device_missing_from_the_table_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
