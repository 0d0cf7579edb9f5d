"""The per-layer metric readers: on traces made by hand, with the numbers
worked out by hand, and on small traces recorded on a TPU v5e (committed
under ``data/``), against a brute-force count over a 1-ns timeline."""

import json
import pathlib

import numpy as np
import pytest

from chipbench.lib import harness, intervals, trace

DATA = pathlib.Path(__file__).parent / "data"
METRICS = ("idle_frac", "kernel_hbm_frac", "glue_frac", "roofline_frac")


def reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").reduce


# window [0, 100) ns on one device:
#   k1 [10,30) and [50,70): a kernel of 1000 bytes; g1 [30,40): glue;
#   a1 [75,85): a collective; g2 [80,90): glue overlapping it.
HAND = {
    "window_ns": [0, 100],
    "calls": 2,
    "chips": 1,
    "essential": {"bytes": 1000, "flops": 1000},
    "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
    "ops": {"kernel": {"k1": 1000}, "collective": ["a1"]},
    "devices": [[["k1", 10, 20], ["g1", 30, 10], ["k1", 50, 20], ["a1", 75, 10], ["g2", 80, 10]]],
    "host": [],
}


def test_hand_trace_one_device():
    tr = HAND
    # busy = [10,40) ∪ [50,70) ∪ [75,90) = 30 + 20 + 15 = 65 ns of 100
    assert reader("idle_frac")(tr) == pytest.approx(35.0)
    # 2 runs x 1000 B in 40 ns, against 1e11 B/s: 2000 / (40e-9 · 1e11)
    assert reader("kernel_hbm_frac")(tr) == pytest.approx(50.0)
    # glue = g1 (10) + the part of g2 past a1, [85,90) (5): 15 of 65 busy
    assert reader("glue_frac")(tr) == pytest.approx(100 * 15 / 65)
    # least time per call max(1000/1e11, 1000/1e12) = 10 ns; 2 calls of 65 busy
    assert reader("roofline_frac")(tr) == pytest.approx(100 * 20 / 65)


def test_hand_trace_two_devices_average():
    tr = dict(HAND, chips=2, devices=HAND["devices"] + [[["k1", 0, 100], ["a1", 20, 10]]])
    # device 2 busy the whole window: idle (35 + 0) / 2
    assert reader("idle_frac")(tr) == pytest.approx(17.5)
    # kernels: 3 runs, 3000 B in 140 ns
    assert reader("kernel_hbm_frac")(tr) == pytest.approx(100 * 3000 / (140e-9 * 1e11))
    # device 2 has no glue: 15 of 65 + 100 busy
    assert reader("glue_frac")(tr) == pytest.approx(100 * 15 / 165)
    # least time per call now over 2 chips: 5 ns, x 2 calls, of (65 + 100) / 2 busy
    assert reader("roofline_frac")(tr) == pytest.approx(100 * 10 / 82.5)


def test_readers_find_nothing_and_say_so():
    empty = dict(HAND, devices=[[]], ops={"kernel": {}, "collective": []})
    for name in METRICS:
        assert reader(name)(empty) is None, name
    assert reader("kernel_hbm_frac")(dict(HAND, ops={"kernel": {}, "collective": []})) is None


def test_interval_helpers():
    u = intervals.union([(5, 10), (0, 3), (2, 4), (9, 12)], 1, 11)
    assert u == [[1, 4], [5, 11]]
    assert intervals.length(u) == 9
    assert intervals.minus([[0, 10], [20, 30]], [[2, 3], [5, 25]]) == [[0, 2], [3, 5], [25, 30]]
    assert intervals.gaps([[1, 4], [5, 11]], 0, 12) == [[0, 1], [4, 5], [11, 12]]


# -- traces recorded on the chip -------------------------------------------


def _timeline(events, lo, hi):
    on = np.zeros(int(hi - lo), bool)
    for s, e in events:
        a, b = int(round(max(s, lo) - lo)), int(round(min(e, hi) - lo))
        if b > a:
            on[a:b] = True
    return on


def brute(tr):
    """The four numbers, counted nanosecond by nanosecond."""
    lo, hi = tr["window_ns"]
    kern, coll = tr["ops"]["kernel"], set(tr["ops"]["collective"])
    idle, busy, glue = [], 0, 0
    kbytes = kns = 0
    for ev in tr["devices"]:
        every = _timeline([(s, s + d) for _, s, d in ev], lo, hi)
        named = _timeline([(s, s + d) for n, s, d in ev if n in kern or n in coll], lo, hi)
        idle.append(1 - every.mean())
        busy += every.sum()
        glue += (every & ~named).sum()
        for n, _s, d in ev:
            if n in kern:
                kbytes += kern[n]
                kns += d
    chips, ess, pk = tr["chips"], tr["essential"], tr["peaks"]
    least = max(ess["bytes"] / (chips * pk["hbm_bytes_per_s"]), ess["flops"] / (chips * pk["flops_per_s"]))
    out = {
        "idle_frac": 100 * np.mean(idle),
        "glue_frac": 100 * glue / busy,
        "roofline_frac": 100 * tr["calls"] * least / (1e-9 * busy / chips),
        "kernel_hbm_frac": 100 * kbytes / (kns * 1e-9 * pk["hbm_bytes_per_s"]) if kns else None,
    }
    return out


RECORDED = sorted(DATA.glob("*_trace.json"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.stem for p in RECORDED])
def test_recorded_trace_against_brute_force(path):
    tr = json.loads(path.read_text())
    want = brute(tr)
    for name in METRICS:
        got = reader(name)(tr)
        if want[name] is None:
            assert got is None, name
            continue
        # 1-ns rounding of each event's ends: well under 0.1% of the window
        assert got == pytest.approx(want[name], rel=2e-3, abs=0.02), name


def test_recorded_traces_are_there():
    names = {p.stem for p in RECORDED}
    assert {"sar_fft2_trace", "sar_range_fft_trace", "conv_os_4097_trace"} <= names


def test_trim_keeps_the_first_calls():
    tr = json.loads((DATA / "conv_os_4097_trace.json").read_text())
    one = trace.trim(tr, 1)
    call = [e for e in tr["host"] if e[0] == "call"][0]
    assert one["calls"] == 1
    assert one["window_ns"] == [call[1], call[1] + call[2]]
    assert all(call[1] <= s < call[1] + call[2] for ev in one["devices"] for _, s, _ in ev)
    assert 0 < sum(map(len, one["devices"])) < sum(map(len, tr["devices"]))
