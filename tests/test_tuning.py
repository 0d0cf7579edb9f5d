"""Autotuner: spaces, roofline pruning, measurement discipline, cache
persistence/determinism, plan-log ring buffer."""

import collections
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_in_subprocess

from repro.analysis import roofline as rl
from repro.core import fft as fft_lib
from repro.core import plan as plan_lib
from repro.core import tuning
from repro.core.overlap import fft_conv_os, pick_block


@pytest.fixture
def fresh_cache(tmp_path, monkeypatch):
    """An isolated, empty persistent cache + clean measurement log."""
    path = str(tmp_path / "tuning.json")
    monkeypatch.setenv("REPRO_TUNING_CACHE", path)
    tuning.cache.clear()
    tuning.clear_measure_log()
    yield path
    tuning.cache.clear()
    tuning.clear_measure_log()


# ---------------------------------------------------------------------------
# mode resolution + pruning
# ---------------------------------------------------------------------------


def test_resolve_mode(monkeypatch):
    assert tuning.resolve_mode("off") == "off"
    assert tuning.resolve_mode(None) == "model"  # zero-measurement default
    monkeypatch.setenv("REPRO_FFT_TUNE", "measure")
    assert tuning.resolve_mode(None) == "measure"
    with pytest.raises(ValueError):
        tuning.resolve_mode("fastest")


def test_prune_candidates_roofline():
    budget = plan_lib.VMEM_BUDGET
    cands = [
        ({"a": 1}, 1000, budget // 2),   # heuristic: 0% over the floor
        ({"a": 2}, 1100, budget // 2),   # within 20% — survives
        ({"a": 3}, 1500, budget // 2),   # 50% over — pruned
        ({"a": 4}, 900, 2 * budget),     # best bytes but does not fit VMEM
    ]
    kept = rl.prune_candidates(cands, tol=0.2)
    assert [c[0]["a"] for c in kept] == [1, 2]
    # stable heuristic-first tie-break: the modeled pick at equal bytes is
    # the fixed-heuristic config, so tune="model" reproduces history
    tied = [({"a": 1}, 1000, 0), ({"a": 2}, 1000, 0)]
    assert rl.prune_candidates(tied)[0][0]["a"] == 1
    # nothing feasible → measure anyway rather than crash
    assert rl.prune_candidates([({"a": 4}, 900, 2 * budget)])


# ---------------------------------------------------------------------------
# persistent cache
# ---------------------------------------------------------------------------


def test_cache_round_trips_json(fresh_cache):
    tuning.cache.put("k1", {"config": {"block": 8192}, "mode": "measure"})
    assert tuning.cache.get("k1")["config"]["block"] == 8192
    # a FRESH cache object re-reads the persisted file — cross-process
    assert os.path.exists(fresh_cache)
    other = tuning.TuningCache()
    assert other.get("k1") == {"config": {"block": 8192}, "mode": "measure"}
    with open(fresh_cache) as f:
        doc = json.load(f)
    assert doc["version"] == tuning.CACHE_SCHEMA_VERSION
    assert doc["entries"]["k1"]["mode"] == "measure"


# ---------------------------------------------------------------------------
# overlap-save block tuning
# ---------------------------------------------------------------------------


def test_os_block_space_heuristic_first_and_valid():
    space = tuning.TuningSpace.for_os_block(2**16, 1025, 2, "xla")
    blocks = [c[0]["block"] for c in space.candidates]
    assert blocks[0] == pick_block(1025)  # the fixed heuristic leads
    assert all(b > 1024 and b <= plan_lib.FUSED_MAX for b in blocks)
    assert all(b & (b - 1) == 0 for b in blocks)
    assert len(set(blocks)) == len(blocks) > 1


def test_tuned_block_off_is_heuristic(fresh_cache):
    assert tuning.tuned_block(2**14, 129, 1, "xla", "off") == pick_block(129)
    assert tuning.measure_log() == ()  # off mode never measures


def test_tuned_block_model_is_deterministic_and_cached(fresh_cache):
    b1 = tuning.tuned_block(2**14, 257, 2, "xla", "model")
    assert tuning.measure_log() == ()  # model mode: zero measurements
    b2 = tuning.tuned_block(2**14, 257, 2, "xla", "model")
    assert b1 == b2
    # the winner is persisted — a fresh cache object sees it
    entries = tuning.TuningCache()._load()
    assert any("os_block" in k for k in entries)


def test_measure_mode_caches_winner_zero_remeasure(fresh_cache, rng):
    L, Lh = 2**13, 129
    x = jnp.asarray(rng.standard_normal((1, L)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((Lh,)), jnp.float32)
    y = fft_conv_os(x, h, backend="xla", tune="measure")
    first = tuning.measure_log()
    assert len(first) >= 1  # the pruned survivors were actually timed
    # ... and the result is still the convolution
    ref = fft_conv_os(x, h, block=pick_block(Lh), backend="xla")
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-3)
    # second call: persistent-cache hit, ZERO new measurements
    tuning.clear_measure_log()
    y2 = fft_conv_os(x, h, backend="xla", tune="measure")
    assert tuning.measure_log() == ()
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), atol=1e-6)
    # simulate a new process: fresh in-memory cache, same JSON file
    tuning.cache._mem, tuning.cache._loaded_path = {}, None
    fft_conv_os(x, h, backend="xla", tune="measure")
    assert tuning.measure_log() == ()


# ---------------------------------------------------------------------------
# plan() tuning
# ---------------------------------------------------------------------------


def test_plan_model_mode_zero_measurements_and_dominates_bytes(fresh_cache):
    spec = fft_lib.FFTSpec(n=2**17, kind="fft")
    tuned = fft_lib.plan(spec, backend="pallas", tune="model")
    off = fft_lib.plan(spec, backend="pallas", tune="off")
    assert tuned.tuned is not None and off.tuned is None
    # model mode never touches the device timer ...
    assert tuning.measure_log() == ()
    # ... and its pick can only improve the modeled HBM traffic (here it
    # swaps the 512/256 direct leaves — whose n² DFT matrices dominate the
    # stream — for fused four-step engines)
    assert plan_lib.program_hbm_bytes(tuned.fft_plan.passes) <= (
        plan_lib.program_hbm_bytes(off.fft_plan.passes)
    )
    assert len(tuned.fft_plan.passes) == len(off.fft_plan.passes)
    # tuned chunks cover exactly the chunked passes of the TUNED program
    heur = {
        i: plan_lib.pick_pass_chunk(p)
        for i, p in enumerate(tuned.fft_plan.passes)
        if p.view_in[0] > 1
    }
    assert set(tuned.pass_chunks) == set(heur)
    assert "tuned:" in tuned.describe() and "direct_max=" in tuned.describe()
    # numerics are engine-independent
    x = (np.random.default_rng(1).standard_normal((2, 2**17))).astype(np.float32)
    y_t = tuned((jnp.asarray(x), jnp.zeros((2, 2**17), jnp.float32)))
    y_o = off((jnp.asarray(x), jnp.zeros((2, 2**17), jnp.float32)))
    scale = float(np.abs(np.asarray(y_o[0])).max())
    np.testing.assert_allclose(
        np.asarray(y_t[0]), np.asarray(y_o[0]), atol=1e-3 * scale
    )
    assert fft_lib.plan(spec, backend="pallas", tune="model") is tuned


def test_plan_measure_zero_measurements_on_second_plan(fresh_cache):
    # The acceptance criterion: second plan() of the same spec performs
    # zero measurements — asserted via the plan log AND the measure log.
    spec = fft_lib.FFTSpec(n=4096, kind="fft", batch_hint=2)
    p1 = fft_lib.plan(spec, backend="pallas", tune="measure")
    assert len(tuning.measure_log()) >= 1
    log_snapshot = fft_lib.plan_log()
    tuning.clear_measure_log()
    p2 = fft_lib.plan(spec, backend="pallas", tune="measure")
    assert p2 is p1  # interned
    assert fft_lib.plan_log() == log_snapshot  # no new schedule forced
    assert tuning.measure_log() == ()
    # simulate a new process: the interning cache is cold but the
    # persistent tuning cache is warm → re-planning measures NOTHING
    cfg1 = p1.tuned
    fft_lib._plan_cached.cache_clear()
    p3 = fft_lib.plan(spec, backend="pallas", tune="measure")
    assert tuning.measure_log() == ()
    assert p3.tuned == cfg1  # same spec → same config, deterministically


def test_plan_tuned_strip_mined_chunks_cover_column_passes(fresh_cache):
    spec = fft_lib.FFTSpec(n=64, kind="fft2", n2=2**17)
    planned = fft_lib.plan(spec, backend="pallas", tune="model")
    col_idx = [i for i, p in enumerate(planned.fft_plan.passes) if p.axis == -2]
    assert col_idx and all(i in planned.pass_chunks for i in col_idx)
    # tuned chunks execute: same result as the untuned handle
    x = (np.random.default_rng(3).standard_normal((1, 2**17, 64))).astype(np.float32)
    y_t = planned((jnp.asarray(x), jnp.zeros_like(jnp.asarray(x))))
    off = fft_lib.plan(spec, backend="pallas", tune="off")
    y_o = off((jnp.asarray(x), jnp.zeros_like(jnp.asarray(x))))
    scale = float(np.abs(np.asarray(y_o[0])).max())
    np.testing.assert_allclose(
        np.asarray(y_t[0]), np.asarray(y_o[0]), atol=1e-4 * scale
    )


# ---------------------------------------------------------------------------
# plan_log ring buffer (satellite)
# ---------------------------------------------------------------------------


def test_plan_log_is_ring_buffer(monkeypatch):
    monkeypatch.setattr(
        fft_lib, "_PLAN_LOG", collections.deque(maxlen=4)
    )
    fft_lib._plan_cached.cache_clear()
    for n in (2, 4, 8, 16, 32, 64):
        fft_lib.plan(fft_lib.FFTSpec(n=n, kind="fft"), backend="stockham")
    log = fft_lib.plan_log()
    assert len(log) == 4  # capped: oldest entries fell off
    assert [s.n for s, _ in log] == [8, 16, 32, 64]
    fft_lib.clear_plan_log()
    assert fft_lib.plan_log() == ()
    fft_lib._plan_cached.cache_clear()


def test_plan_log_capacity_is_bounded():
    assert fft_lib._PLAN_LOG.maxlen == fft_lib.PLAN_LOG_MAX > 0


# ---------------------------------------------------------------------------
# the packaged seed cache
# ---------------------------------------------------------------------------


def test_seed_cache_layers_beneath_user_cache():
    seed = tuning.seed_cache()
    assert seed, "packaged tuning_seed.json missing or empty"
    key = "cpu|pallas|plan|fft|n=8192|batch=2"
    assert key in seed and seed[key]["mode"] == "measure"
    # the user cache shadows the seed on put()
    tuning.cache.put(key, {"config": {"sentinel": 1}, "mode": "measure"})
    try:
        assert tuning.cache.get(key)["config"] == {"sentinel": 1}
    finally:
        tuning.cache.clear()
    # after clearing the user layer, the seed answers again
    assert tuning.cache.get(key)["mode"] == "measure"


_SEED_BODY = r"""
from repro.core import fft as F
from repro.core import tuning

spec = F.FFTSpec(n=8192, kind="fft", batch_hint=2)
p = F.plan(spec, backend="pallas", tune="measure")
assert p.tuned is not None
assert tuning.measure_log() == (), tuning.measure_log()
print("SEED_ZERO_MEASURE_OK")
"""


def test_seeded_spec_measures_nothing_in_fresh_process():
    # A FRESH process (cold interning cache, empty user tuning cache —
    # conftest points REPRO_TUNING_CACHE at a tempdir) plans a seeded spec
    # under tune="measure" with zero device measurements, because the
    # packaged seed already has the measured winner.
    out = run_in_subprocess(_SEED_BODY, devices=1)
    assert "SEED_ZERO_MEASURE_OK" in out
