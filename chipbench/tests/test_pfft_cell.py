"""The four-chip cell ``pfft_16m``: its essential work against hand counts,
whole small runs on four virtual CPU devices (sound, and with the timed
path broken), the control against its limit, and the two collective
metrics against a brute-force count on hand-made traces."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from chipbench.lib import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "pfft_16m"


def test_essential_work_matches_hand_count():
    c = harness.load_cell(CELL)
    # 64 signals of 2^24 points: 1,073,741,824; in + out at 8 B a point;
    # 5 N log2 N with N = 2^24 per signal: 5 · 1,073,741,824 · 24.
    assert c.entry.samples(c.config, c.traffic) == 1_073_741_824
    ess = c.entry.essential(c.config, c.traffic)
    assert ess["bytes"] == 17_179_869_184
    assert ess["flops"] == pytest.approx(128_849_018_880.0, rel=1e-12)
    # Per device per call: 3 transposes of 64 · 2^22 points at 8 B, of
    # which 3/4 leaves the chip: 3 · 2 GiB · 3/4 = 4.5 GiB.
    assert ess["a2a_bytes"] == 4_831_838_208


# -- whole runs on four virtual devices --------------------------------------

_RUNS = r"""
import json, sys, time
sys.path[:0] = [ROOT, ROOT + "/src"]
import jax
from chipbench.lib import harness
from chipbench.readings import reading
from repro.core import fft as F

def cell():
    c = harness.load_cell("pfft_16m")
    c.config.update({"n": 4096, "batch": 8})
    return c

def unchanged(fn, built):
    return jax.jit(lambda a, b: (a, b))

def half_batch(fn, built):
    def run(a, b):
        yr, yi = fn(a, b)
        h = yr.shape[0] // 2
        return yr.at[h:].set(0), yi.at[h:].set(0)
    return jax.jit(run)

def answer_altered(fn, built):
    def run(a, b):
        yr, yi = fn(a, b)
        return yr.at[..., 1].set(0), yi.at[..., 1].set(0)
    return jax.jit(run)

out = {"limit": cell().limits["max_err_rel"], "runs": {}}
with F.use_backend("pallas"):
    for name, wrap in (("sound", None), ("unchanged", unchanged),
                       ("half_batch", half_batch), ("answer_altered", answer_altered)):
        out["runs"][name] = harness.run(
            jax, cell(), 2**31 + 7, 0.2, False, jax.devices(), time.perf_counter(), wrap=wrap)
    out["readings"] = [reading(jax, cell(), s, jax.devices()) for s in (11, 2**31 + 5, 987654321)]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    code = f"ROOT = {str(ROOT)!r}\n" + _RUNS
    res = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=600
    )
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.splitlines()[-1])


def test_sound_run_is_correct(runs):
    r = runs["runs"]["sound"]
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["count"] == 4
    assert set(r["metrics"]) == {"msamples_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "answer_altered"])
def test_fault_fails_the_run(runs, fault):
    r = runs["runs"][fault]
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_err_rel"]["value"] > r["checks"]["max_err_rel"]["limit"]


def test_control_fails_the_limit_the_program_meets(runs):
    for program, control in runs["readings"]:
        assert program <= runs["limit"] < control, (program, runs["limit"], control)


# -- the collective metrics on hand-made traces ------------------------------


def _metric(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py")


def _trace(devices, coll, labels=None, calls=3, a2a_bytes=1000, window=(0, 200)):
    return {
        "window_ns": list(window),
        "calls": calls,
        "essential": {"bytes": 1, "flops": 1.0, "a2a_bytes": a2a_bytes},
        "ops": {"kernel": {}, "collective": list(coll)},
        "devices": devices,
        "labels": labels or {},
    }


def _random_devices(rng, names, ndev=2, nev=12):
    devices = []
    for _ in range(ndev):
        ev = []
        for _ in range(nev):
            s = int(rng.integers(-10, 200))
            ev.append([str(rng.choice(names)), s, int(rng.integers(1, 30))])
        devices.append(ev)
    return devices


def _ticks(ev, keep, lo, hi):
    """Brute force: the nanoseconds of ``[lo, hi)`` some kept event covers."""
    on = np.zeros(hi - lo, bool)
    for n, s, d in ev:
        if keep(n):
            on[max(s, lo) - lo : max(min(s + d, hi), lo) - lo] = True
    return on


@pytest.mark.parametrize("seed", range(6))
def test_a2a_exposed_frac_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    coll = {"all-to-all.1", "all-to-all.2"}
    devices = _random_devices(rng, sorted(coll) + ["fusion.3", "pencil_cols.4"])
    shares = []
    for ev in devices:
        c = _ticks(ev, lambda n: n in coll, 0, 200)
        other = _ticks(ev, lambda n: n not in coll, 0, 200)
        if c.any():
            shares.append((c & ~other).sum() / (c | other).sum())
    got = _metric("a2a_exposed_frac").reduce(_trace(devices, coll))
    assert got == pytest.approx(100 * np.mean(shares), rel=1e-12)


@pytest.mark.parametrize("seed", range(6))
def test_a2a_gb_per_s_matches_brute_force(seed):
    rng = np.random.default_rng(100 + seed)
    coll = {"all-to-all.1", "all-to-all.2"}
    devices = _random_devices(rng, sorted(coll) + ["fusion.3", "pencil_cols.4"])
    tr = _trace(devices, coll, calls=5, a2a_bytes=7000)
    rates = []
    for ev in devices:
        ns = _ticks(ev, lambda n: n in coll, 0, 200).sum()
        if ns:
            rates.append(5 * 7000 / (ns * 1e-9) / 1e9)
    assert _metric("a2a_gb_per_s").reduce(tr) == pytest.approx(np.mean(rates), rel=1e-12)


def test_a2a_gb_per_s_counts_an_async_pair_from_start_to_done():
    # start 10–12, a kernel 12–40 that hides the transfer, done 40–45: the
    # collective holds [10, 45); a synchronous one holds [60, 75).
    ev = [
        ["a2a-start.1", 10, 2], ["pencil_cols.2", 12, 28], ["a2a-done.1", 40, 5],
        ["all-to-all.3", 60, 15],
    ]
    labels = {"a2a-start.1": "all-to-all-start", "a2a-done.1": "all-to-all-done",
              "all-to-all.3": "all-to-all", "pencil_cols.2": "custom-call:tpu_custom_call"}
    coll = {"a2a-start.1", "a2a-done.1", "all-to-all.3"}
    tr = _trace([ev], coll, labels=labels, calls=2, a2a_bytes=500)
    assert _metric("a2a_gb_per_s").reduce(tr) == pytest.approx(1000 / 50e-9 / 1e9)
    # exposed: the start, the done and the synchronous one, 2 + 5 + 15 of
    # the 35 + 15 busy
    assert _metric("a2a_exposed_frac").reduce(tr) == pytest.approx(100 * 22 / 50)


def test_collective_metrics_read_nothing_without_collectives():
    devices = [[["fusion.1", 0, 50], ["pencil_cols.2", 60, 30]]]
    for name in ("a2a_exposed_frac", "a2a_gb_per_s"):
        assert _metric(name).reduce(_trace(devices, [])) is None, name
        # collectives in the program, none in the window
        assert _metric(name).reduce(_trace(devices, ["all-to-all.9"])) is None, name
    # an entry that counts no bytes sent
    tr = _trace([[["all-to-all.1", 0, 10]]], ["all-to-all.1"], a2a_bytes=0)
    assert _metric("a2a_gb_per_s").reduce(tr) is None


def test_collective_metrics_on_the_recorded_trace():
    """The cell's first call as a v5e 2x2 traced it: nine all-to-alls on
    each device, and both metrics against a count on a 100-ns grid."""
    tr = json.loads((pathlib.Path(__file__).parent / "data" / "pfft_16m_named_trace.json").read_text())
    coll = set(tr["ops"]["collective"])
    lo, hi = tr["window_ns"]
    step = 100

    def grid(ev, keep):
        on = np.zeros(int((hi - lo) // step) + 1, bool)
        for n, s, d in ev:
            if keep(n):
                on[int((max(s, lo) - lo) // step) : int((min(s + d, hi) - lo) // step)] = True
        return on

    shares, rates = [], []
    for ev in tr["devices"]:
        assert sum(n in coll for n, _, _ in ev) == 9
        c, other = grid(ev, lambda n: n in coll), grid(ev, lambda n: n not in coll)
        shares.append((c & ~other).sum() / (c | other).sum())
        rates.append(tr["essential"]["a2a_bytes"] * tr["calls"] / (c.sum() * step * 1e-9) / 1e9)
    got = _metric("a2a_exposed_frac").reduce(tr)
    assert got == pytest.approx(100 * np.mean(shares), rel=1e-3)
    assert _metric("a2a_gb_per_s").reduce(tr) == pytest.approx(np.mean(rates), rel=1e-3)
