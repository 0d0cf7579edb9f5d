"""Pencil plan layer — in-process tests (no fake-device subprocess needed).

Covers the tuned-schedule resolution (`plan_pencil` / `tuning.pencil_config`
/ `roofline.pencil_report`) and the d=1 degenerate mesh, which runs on the
default single-device environment.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import roofline as rl
from repro.core import distributed as D
from repro.core import tuning


# ---------------------------------------------------------------------------
# PencilPlan / plan_pencil
# ---------------------------------------------------------------------------


def test_plan_pencil_resolves_and_caches():
    pl = D.plan_pencil(8192, 8)
    assert pl.n1 * pl.n2 == 8192
    assert pl.n1 % 8 == 0 and pl.n2 % 8 == 0
    assert pl.p == pl.n1 // 8 and pl.q == pl.n2 // 8
    # interned: same args → same handle
    assert D.plan_pencil(8192, 8) is pl
    assert D.plan_pencil(8192, 8, inverse=True) is not pl


def test_describe_prints_schedule():
    s = D.plan_pencil(8192, 8).describe()
    assert f"factors {D.plan_pencil(8192, 8).n1}x{D.plan_pencil(8192, 8).n2}" in s
    assert "a2a x3 natural" in s and "MB/step" in s
    assert "leaf n1:" in s and "leaf n2:" in s
    s1 = D.plan_pencil(4096, 1).describe()
    assert "0 collectives" in s1 and "local:" in s1


def test_a2a_count_math():
    assert D.plan_pencil(8192, 8, chunks=1).a2a_count(True) == 3
    assert D.plan_pencil(8192, 8, chunks=1).a2a_count(False) == 2
    assert D.plan_pencil(8192, 8, chunks=2).a2a_count(True) == 5
    assert D.plan_pencil(8192, 8, pack=False).a2a_count(True) == 6
    assert D.plan_pencil(8192, 8, pack=False).a2a_count(False) == 4
    assert D.plan_pencil(4096, 1).a2a_count(True) == 0
    assert D.plan_pencil(4096, 1).a2a_count(False) == 0


def test_chunk_count_clamps_to_divide_columns():
    pl = D.plan_pencil(8192, 8)  # q = n2 / 8
    big = D.plan_pencil(8192, 8, chunks=4 * pl.q)
    assert big.a2a_chunks == pl.q  # clamped to the column count
    odd = D.plan_pencil(8192, 8, chunks=3)
    assert odd.q % odd.a2a_chunks == 0
    # split-plane path never chunks
    assert D.plan_pencil(8192, 8, pack=False, chunks=4).a2a_chunks == 1


def test_plan_pencil_rejects_bad_factors():
    with pytest.raises(ValueError):
        D.plan_pencil(8192, 8, factors=(64, 64))  # product != n
    with pytest.raises(ValueError):
        D.plan_pencil(8192, 8, factors=(2048, 4))  # 4 % 8 != 0


# ---------------------------------------------------------------------------
# Deterministic modeled tuning (the SPMD contract)
# ---------------------------------------------------------------------------


def test_pencil_config_modeled_only_no_cache_no_measure(
    monkeypatch, tmp_path
):
    # Fresh cache path: other suites may legitimately populate the
    # session-wide cache file; pencil decisions themselves never write one.
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "tuning.json"))
    before = len(tuning.measure_log())
    cfg = tuning.pencil_config(65536, 8)
    assert cfg["n1"] * cfg["n2"] == 65536
    assert cfg["n1"] % 8 == 0 and cfg["n2"] % 8 == 0
    # "measure" clamps to the modeled pick — identical, still zero timings
    assert tuning.pencil_config(65536, 8, tune="measure") == cfg
    assert tuning.pencil_config(65536, 8) == cfg  # repeatable
    assert len(tuning.measure_log()) == before
    assert not os.path.exists(tuning.cache_path())


def test_pencil_config_off_is_balanced_serial():
    cfg = tuning.pencil_config(8192, 8, tune="off")
    assert (cfg["n1"], cfg["n2"]) == D.pencil_factors(8192, 8)
    assert cfg["pack"] and cfg["a2a_chunks"] == 1


def test_for_pencil_space_candidates_valid():
    space = tuning.TuningSpace.for_pencil(65536, 16)
    assert space.measure_fn is None  # never measurable — SPMD safety
    assert len(space.candidates) > 1
    for cfg, cost, vmem in space.candidates:
        assert cfg["n1"] * cfg["n2"] == 65536
        assert cfg["n1"] % 16 == 0 and cfg["n2"] % 16 == 0
        assert cost > 0 and vmem > 0
        if cfg["a2a_chunks"] > 1:
            assert cfg["pack"]  # chunk overlap rides the packed path only


# ---------------------------------------------------------------------------
# Roofline comm model
# ---------------------------------------------------------------------------


def test_pencil_report_keys_and_overlap():
    rep = rl.pencil_report(65536, 8)
    for k in (
        "n1",
        "n2",
        "comm_bytes_per_step",
        "local_hbm_bytes",
        "modeled_s",
        "serial_s",
        "overlap_win",
    ):
        assert k in rep, k
    assert rep["comm_bytes_per_step"] > 0
    assert rep["modeled_s"] <= rep["serial_s"] * (1 + 1e-9)
    # packing strictly beats split-plane in the model (launch charges)
    unpacked = rl.pencil_report(65536, 8, pack=False)
    assert rep["modeled_s"] < unpacked["modeled_s"]


def test_pencil_report_single_device_has_no_comm():
    rep = rl.pencil_report(65536, 1)
    assert rep["comm_bytes_per_step"] == 0
    assert rep["modeled_s"] > 0


# ---------------------------------------------------------------------------
# d=1 degenerate mesh: collapses to the local plan, zero collectives
# ---------------------------------------------------------------------------


def _mesh1():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))


def test_single_shard_collapses_to_local_plan():
    n = 4096
    rng = np.random.default_rng(11)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(
        np.complex64
    )
    ref = np.fft.fft(x)
    mesh = _mesh1()
    yr, yi = D.pfft_sharded(jnp.asarray(x.real), jnp.asarray(x.imag), mesh, "x")
    rel = (
        np.abs((np.asarray(yr) + 1j * np.asarray(yi)) - ref).max()
        / np.abs(ref).max()
    )
    assert rel < 5e-5, rel
    zr, zi = D.pifft_sharded(yr, yi, mesh, "x")
    assert np.abs((np.asarray(zr) + 1j * np.asarray(zi)) - x).max() < 5e-5


def test_single_shard_zero_collectives_jaxpr():
    n = 4096
    mesh = _mesh1()
    from jax.sharding import PartitionSpec as P

    for natural in (True, False):
        fn = jax.shard_map(
            lambda xr, xi: D.pfft(
                xr,
                xi,
                n=n,
                axis_name="x",
                num_shards=1,
                natural_order=natural,
            ),
            mesh=mesh,
            in_specs=(P("x"), P("x")),
            out_specs=(P("x"), P("x")),
            check_vma=False,
        )
        jx = str(jax.make_jaxpr(fn)(jnp.zeros(n), jnp.zeros(n)))
        for coll in ("all_to_all", "all_gather", "psum", "ppermute"):
            assert coll not in jx, (natural, coll)


def test_single_shard_pencil_layout_semantics():
    # d=1, natural_order=False must keep the k1-major layout contract.
    n = 4096
    rng = np.random.default_rng(12)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(
        np.complex64
    )
    ref = np.fft.fft(x)
    mesh = _mesh1()
    pr, pi = D.pfft_sharded(
        jnp.asarray(x.real), jnp.asarray(x.imag), mesh, "x", natural_order=False
    )
    n1, n2 = D.pencil_factors(n, 1)
    pen = (np.asarray(pr) + 1j * np.asarray(pi)).reshape(n1, n2)
    perm = ref.reshape(n2, n1).T
    rel = np.abs(pen - perm).max() / np.abs(ref).max()
    assert rel < 5e-5, rel
    # and the mirrored inverse consumes it
    zr, zi = D.pifft_sharded(pr, pi, mesh, "x", from_pencil=True)
    assert np.abs((np.asarray(zr) + 1j * np.asarray(zi)) - x).max() < 5e-5


# ---------------------------------------------------------------------------
# StreamingConv under SPMD
# ---------------------------------------------------------------------------


def test_streaming_conv_spmd_block_is_modeled():
    from repro.core.overlap import StreamingConv, pick_block

    h = jnp.asarray(np.random.default_rng(13).standard_normal(257), jnp.float32)
    before = len(tuning.measure_log())
    sc = StreamingConv(h, chunk_hint=4096, spmd=True)
    expect = tuning.modeled_block(4096, 257, 1, None, chunk=4096)
    assert sc.block == expect
    assert len(tuning.measure_log()) == before  # no timings taken
    # and it still convolves correctly at that block
    x = np.random.default_rng(14).standard_normal(10000).astype(np.float32)
    state = sc.init_state()
    y1, state = sc(jnp.asarray(x[:4096]), state)
    y2, state = sc(jnp.asarray(x[4096:]), state)
    y = np.concatenate([np.asarray(y1), np.asarray(y2)])
    ref = np.convolve(x, np.asarray(h))[: x.shape[-1]]
    np.testing.assert_allclose(y, ref, atol=5e-3)
