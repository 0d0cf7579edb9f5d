"""Each entry's samples, essential bytes and essential flops of one call,
against counts made by hand from the committed configurations."""

import json
import re

import pytest

from chipbench.lib import harness

# cell → (samples, bytes, flops), worked out by hand:
HAND = {
    # 14 scenes of 4096 x 8192 complex64: 469,762,048 points; in + out at 8 B;
    # 5 N log2 N with N = 2^25 per scene: 5 · 469,762,048 · 25.
    "sar_fft2": (469_762_048, 7_516_192_768, 58_720_256_000.0),
    # 57,344 lines of 8192: the same points; 5 · 469,762,048 · 13.
    "sar_range_fft": (469_762_048, 7_516_192_768, 30_534_533_120.0),
    # 1024 x 2^19 real samples; 4 B in + 4 B out each, plus 4097 filter taps;
    # N = 2^20 covers 2^19 + 4096 outputs: per channel 2 · 2.5 · N · 20
    # = 104,857,600 and 6 · (N/2 + 1) = 3,145,734 → 108,003,334 · 1024.
    "conv_os_4097": (536_870_912, 4_294_983_684, 110_595_414_016.0),
}


@pytest.mark.parametrize("cell", sorted(HAND))
def test_essential_work_matches_hand_count(cell):
    c = harness.load_cell(cell)
    samples, nbytes, flops = HAND[cell]
    assert c.entry.samples(c.config, c.traffic) == samples
    ess = c.entry.essential(c.config, c.traffic)
    assert ess["bytes"] == nbytes
    assert ess["flops"] == pytest.approx(flops, rel=1e-12)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_file_names_what_exists():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(c["reduced"]) <= set(cfg), c["name"]
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert len(w["why"]) <= 200
        cell = harness.load_cell(w["name"])
        assert cell.chips == cell.config["chips"]
        assert cell.traffic["loop"] == "closed" and cell.traffic["in_flight"] == 1
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
