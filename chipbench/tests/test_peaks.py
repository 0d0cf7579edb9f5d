import json

import pytest

from chipbench.lib import peaks


def test_v5e_peaks_are_the_published_ones():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "cloud.google.com" in v5e["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", "tpu v5 lite", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup(kind)


def test_every_entry_has_its_source(tmp_path):
    table = json.loads(peaks.PEAKS_FILE.read_text())
    for kind, row in table.items():
        assert {"flops_per_s", "hbm_bytes_per_s", "hbm_bytes", "source"} <= set(row), kind
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"X": {"flops_per_s": 1}}))
    assert peaks.lookup("X", other) == {"flops_per_s": 1}
    with pytest.raises(KeyError):
        peaks.lookup("TPU v5 lite", other)
