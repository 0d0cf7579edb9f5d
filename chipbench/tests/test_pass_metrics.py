"""The per-pass kernel readers ``rows_hbm_frac`` and ``cols_hbm_frac``: on a
trace made by hand, on traces of a program whose kernels carry no family
name, and on short traces recorded on a TPU v5e with the named kernels
(``data/<cell>_named_trace.json``), against a count made event by event."""

import json
import pathlib

import pytest

from chipbench.lib import families, harness

DATA = pathlib.Path(__file__).parent / "data"
NEW = ("rows_hbm_frac", "cols_hbm_frac")
OLD = ("idle_frac", "kernel_hbm_frac", "glue_frac", "roofline_frac")


def reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").reduce


# window [0, 100) ns on one device, peak 1e11 B/s:
#   fft4step.1 [10,30) and [50,70): a row kernel of 1000 bytes;
#   pencil_cols [30,45): a column kernel of 600 bytes;
#   recomb_fwd.2 [70,80) and _unknown_.3 [80,90): kernels of neither pass;
#   fusion.1 [90,95): glue.
HAND = {
    "window_ns": [0, 100],
    "calls": 1,
    "chips": 1,
    "essential": {"bytes": 1000, "flops": 1000},
    "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
    "ops": {
        "kernel": {"fft4step.1": 1000, "pencil_cols": 600, "recomb_fwd.2": 500, "_unknown_.3": 800},
        "collective": [],
    },
    "devices": [[
        ["fft4step.1", 10, 20], ["pencil_cols", 30, 15], ["fft4step.1", 50, 20],
        ["recomb_fwd.2", 70, 10], ["_unknown_.3", 80, 10], ["fusion.1", 90, 5],
    ]],
    "host": [],
}


def test_hand_trace():
    # rows: 2 runs x 1000 B in 40 ns; cols: 600 B in 15 ns; both over 1e11 B/s
    assert reader("rows_hbm_frac")(HAND) == pytest.approx(100 * 2000 / (40e-9 * 1e11))
    assert reader("cols_hbm_frac")(HAND) == pytest.approx(100 * 600 / (15e-9 * 1e11))


def test_two_devices_add_bytes_and_time():
    tr = dict(HAND, chips=2, devices=HAND["devices"] + [[["fft4step.7", 0, 60]]])
    tr["ops"] = {"kernel": dict(HAND["ops"]["kernel"], **{"fft4step.7": 3000}), "collective": []}
    assert reader("rows_hbm_frac")(tr) == pytest.approx(100 * 5000 / (100e-9 * 1e11))


def test_family_is_the_name_less_its_suffix():
    assert families.family("fft4step.12") == "fft4step"
    assert families.family("pencil_cols") == "pencil_cols"
    assert families.family("pencil_rows_natural.3") == "pencil_rows_natural"
    assert families.family("_unknown_.3") == "_unknown_"


def test_unnamed_kernels_give_nothing():
    """A program whose kernels are named after the jitted function (the
    instruction names ``_unknown_.2``, ``_lambda_.8``) has no kernel of
    either pass: both readers say so, and do not raise."""
    tr = dict(HAND, ops={"kernel": {"_unknown_.3": 800}, "collective": []},
              devices=[[["_unknown_.3", 10, 20], ["fusion.1", 40, 5]]])
    for name in NEW:
        assert reader(name)(tr) is None, name


BEFORE = [p for p in sorted(DATA.glob("*_trace.json")) if "_named_" not in p.name]


@pytest.mark.parametrize("path", BEFORE, ids=lambda p: p.stem)
def test_recorded_before_the_names_give_nothing(path):
    tr = json.loads(path.read_text())
    for name in NEW:
        assert reader(name)(tr) is None, name


def test_the_families_cover_the_program_vocabulary():
    from repro.core import plan as P

    tpu = {n for n in P.KERNEL_NAMES if not n.endswith("_gpu")}
    rows, cols = set(families.ROW_KERNELS), set(families.COL_KERNELS)
    assert not rows & cols
    assert rows | cols | {"recomb_fwd", "recomb_inv"} == tpu


# -- traces recorded on the chip with the named kernels --------------------

NAMED = {cell: DATA / f"{cell}_named_trace.json" for cell in ("sar_fft2", "sar_range_fft", "conv_os_4097")}


def count(tr, prefixes):
    """Achieved share of the peak of the kernels whose instruction name
    starts with one of ``prefixes`` followed by ``.`` or nothing."""
    moved = ns = 0
    for ev in tr["devices"]:
        for name, _s, d in ev:
            if name in tr["ops"]["kernel"] and name.split(".")[0] in prefixes:
                moved += tr["ops"]["kernel"][name]
                ns += d
    return 100 * moved / (ns * 1e-9 * tr["peaks"]["hbm_bytes_per_s"]) if ns else None


@pytest.mark.parametrize("cell", sorted(NAMED))
def test_recorded_named_trace_against_count(cell):
    tr = json.loads(NAMED[cell].read_text())
    for name, fams in (("rows_hbm_frac", families.ROW_KERNELS), ("cols_hbm_frac", families.COL_KERNELS)):
        want, got = count(tr, fams), reader(name)(tr)
        assert (got is None) == (want is None), (cell, name)
        if want is not None:
            assert got == pytest.approx(want, rel=1e-12), (cell, name)


@pytest.mark.parametrize("cell", sorted(NAMED))
def test_recorded_named_trace_reads_the_cells_metrics(cell):
    """Each cell's traced run prints every per-layer metric that
    ``BENCHMARK.json`` gives it, the new ones in exactly the cells their
    ``workloads`` name; the kernels are named by family."""
    tr = json.loads(NAMED[cell].read_text())
    c = harness.load_cell(cell)
    got = harness.per_layer_metrics(c, tr)
    assert set(got) == {m["name"] for m in c.per_layer}
    assert set(OLD) <= set(got)
    assert ("cols_hbm_frac" in got) == (cell == "sar_fft2")
    assert "rows_hbm_frac" in got
    kernels = {families.family(n) for n in tr["ops"]["kernel"]}
    assert kernels and kernels <= set(families.ROW_KERNELS + families.COL_KERNELS + ("recomb_fwd", "recomb_inv"))
