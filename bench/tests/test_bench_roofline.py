"""The peaks table and the least work of one exact sweep."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import roofline  # noqa: E402

FMRI = (225, 59, 200, 200)


def test_v5e_peaks_have_a_source():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks(kind)


def test_fmri_sweep_least_work_by_hand():
    entries = 225 * 59 * 200 * 200  # 531,000,000
    assert roofline.sweep_least_bytes(FMRI, "float32") == 2_124_000_000 == 4 * entries
    assert roofline.sweep_least_bytes(FMRI, "bfloat16") == 1_062_000_000
    assert roofline.sweep_least_flops(FMRI, 25) == 26_550_000_000
    t, bound = roofline.sweep_least_seconds(FMRI, 25, "float32", roofline.peaks("TPU v5 lite"))
    assert bound == "hbm"
    assert t == pytest.approx(2.124e9 / 819e9)  # 2.593 ms
    assert t == pytest.approx(2.5934e-3, rel=1e-4)


def test_least_time_switches_to_flops_at_high_rank():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 2 C flops per 4 bytes: flops bound once C > 4 * 197e12 / (2 * 819e9) = 481
    assert roofline.sweep_least_seconds(FMRI, 481, "float32", peak)[1] == "hbm"
    assert roofline.sweep_least_seconds(FMRI, 482, "float32", peak)[1] == "flops"


def test_peaks_file_is_keyed_by_device_kind(tmp_path):
    path = tmp_path / "peaks.json"
    path.write_text(json.dumps({"TPU x": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 2.0,
                                          "source": "s"}}))
    assert roofline.peaks("TPU x", path)["hbm_bytes_per_s"] == 2.0
    with pytest.raises(KeyError):
        roofline.peaks("TPU v5 lite", path)
