"""The peaks table (chipbench/peaks.json)."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
from chipbench import peaks  # noqa: E402


def test_v5e_peaks():
    p = peaks.lookup("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        peaks.lookup(kind)
