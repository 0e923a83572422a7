"""The probe's least bytes, and the table of peaks."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench.roofline.probe import least_bytes, least_seconds  # noqa: E402
from chipbench.run import peaks_for  # noqa: E402


@pytest.mark.parametrize("mode,per_probe", [("packed", 8 + 1 + 8 + 8 + 8),
                                            ("coord", 12 + 1 + 12 + 8 + 8)])
def test_least_bytes_depend_only_on_P_and_the_arena_mode(mode, per_probe):
    for P in (16, 128, 512):
        assert least_bytes(P, mode) == P * per_probe


def test_least_seconds_are_bytes_over_hbm_bandwidth():
    peak = peaks_for(ROOT, "TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    got = least_seconds([128, 256], "coord", peak)
    assert got == pytest.approx(least_bytes(384, "coord") / 819e9)


def test_peaks_name_their_source():
    for kind, peak in json.loads(
            (ROOT / "chipbench" / "peaks.json").read_text()).items():
        assert peak["source"] and peak["hbm_bytes_per_s"] > 0, kind


@pytest.mark.parametrize("kind", ["TPU v9 imaginary", "cpu"])
def test_an_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks_for(ROOT, kind)
