"""Multi-axis (2-D) pass programs: fft2/rfft2 as ONE compiled schedule.

The image acceptance criterion (paper §3's remote-sensing workload): a
planned ``fft2`` lowers to exactly rows+cols kernel calls with zero
standalone HBM transposes between them — the `_fft2_planes` swapaxes
sandwich is gone.  Asserted over the jaxpr like the 1-D split regime, plus
cross-backend numerical acceptance, the rfft2/irfft2 Hermitian-epilogue
kinds, the joint-program halves the distributed driver consumes, and the
2-D fft_conv2d matched-filter path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import DEMOTED

from repro.analysis import roofline as rl
from repro.core import fft as F
from repro.core import plan as P
from repro.core.conv import fft_conv2d, toeplitz_conv_ref

BACKENDS = ["stockham", "xla", "pallas", DEMOTED]


def _rand_c(rng, shape):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
        np.complex64
    )


# ---------------------------------------------------------------------------
# plan structure: one joint program, rows then in-place columns
# ---------------------------------------------------------------------------


def test_plan_fft2_is_one_joint_program():
    plan = P.plan_fft2(2048, 64)
    rows = [p for p in plan.passes if p.axis == -1]
    cols = [p for p in plan.passes if p.axis == -2]
    assert plan.n2 == 64
    assert [p.axis for p in plan.passes] == [-1] * len(rows) + [-2] * len(cols)
    assert len(cols) == 1 and cols[0].n == 64
    assert plan.hbm_round_trips == len(rows) + 1
    # split-regime rows: the 1-D program rides along unchanged
    plan = P.plan_fft2(2**17, 8)
    assert [p.axis for p in plan.passes] == [-1, -1, -2]
    assert tuple(p.n for p in plan.passes if p.axis == -1) == P.program_factors(2**17)


def test_plan_fft2_strip_mined_columns_past_fused():
    # n2 = 2¹⁷ > FUSED_MAX: ONE joint program — row pass(es) then the
    # strip-mined column factors of the n2 axis, re-tagged axis=-2 with the
    # same pencil views as the 1-D split program (the tentpole acceptance).
    plan = P.plan_fft2(512, 2**17)
    cols = [p for p in plan.passes if p.axis == -2]
    assert plan.n2 == 2**17
    assert tuple(p.n for p in cols) == P.program_factors(2**17)
    f0, f1 = P.program_factors(2**17)
    assert cols[0].view_in == (2**17 // f0, f1, f0)
    assert cols[0].twiddle_after == (f0, f1)
    assert cols[1].view_in == (f0, 1, f1)
    assert cols[1].view_out == (f0, f0, f1)  # fused natural digit write
    assert plan.hbm_round_trips == len(plan.passes)
    # column factors show up as plan leaves (LUT warm-up needs them)
    assert {f0, f1} <= {p.n for p in plan.leaf_passes}


def test_plan_fft2_gated_only_beyond_fused_squared():
    # Strip-mined columns cover n2 ≤ FUSED_MAX²; beyond that the column
    # program would need a digit-reversal relayout down axis -2.
    with pytest.raises(NotImplementedError):
        P.plan_fft2(256, 2**33)


def test_strip_mined_joint_program_beats_fallback_bytes():
    # Acceptance: modeled HBM bytes of the joint strip-mined program are
    # strictly below the per-axis composition it replaced (which paid a
    # swapaxes sandwich around its multi-pass column plan).
    rep = rl.fft2_fallback_report(512, 2**17)
    assert rep["joint_passes"] == 3  # 1 row pass + 2 strip-mined col passes
    assert rep["joint_hbm_bytes"] < rep["fallback_hbm_bytes"]
    assert rep["fallback_transpose_bytes"] > 0


@pytest.mark.parametrize("backend", ["xla", "pallas", DEMOTED], indirect=True)
def test_tall_image_plans_joint_and_matches_numpy(backend, rng):
    # Tall images now plan as ONE joint strip-mined program; non-native-2d
    # backends still execute it through per-axis composition, the pallas
    # backend through execute_program2d — both must match numpy.
    planned = F.plan(F.FFTSpec(n=64, kind="fft2", n2=2**17), backend=backend)
    assert planned.fft_plan is not None and planned.fft_plan.n2 == 2**17
    x = _rand_c(rng, (1, 2**17, 64))
    y = np.asarray(planned(jnp.asarray(x)))
    ref = np.fft.fft2(x)
    assert np.abs(y - ref).max() <= 1e-4 * np.abs(ref).max(), backend
    # the joint-program halves compose to the same transform
    yr, yi = planned.apply_cols(*planned.apply_rows(jnp.asarray(x.real), jnp.asarray(x.imag)))
    err = np.abs((np.asarray(yr) + 1j * np.asarray(yi)) - ref).max()
    assert err <= 1e-4 * np.abs(ref).max(), backend


def test_describe_is_multi_axis_with_mb():
    planned = F.plan(F.FFTSpec(n=2048, n2=512, kind="fft2"), backend="pallas")
    s = planned.describe()
    assert "N=512x2048" in s
    assert "axis -2 in-place columns" in s
    assert "MB" in s
    assert "2 HBM round trip" in s


def test_pass_hbm_bytes_charge_whole_image():
    plan = P.plan_fft2(2048, 64)
    img = 64 * 2048 * 2 * 4  # split-complex f32 image bytes
    for p in plan.passes:
        other = P.pass_other(p, plan)
        assert P.pass_hbm_bytes(p, 1, other) >= 2 * img  # read + write
    total = P.program_hbm_bytes(plan.passes, 1, shape2d=(64, 2048))
    assert total >= 2 * len(plan.passes) * img


def test_fft_pass_report_2d():
    rep = rl.fft_pass_report(2048, batch=2, n2=64)
    assert rep["n2"] == 64 and rep["hbm_round_trips"] == len(rep["passes"]) == 2
    assert [e["axis"] for e in rep["passes"]] == [-1, -2]
    assert rep["modeled_hbm_bytes"] == sum(e["hbm_bytes"] for e in rep["passes"])
    assert rep["memory_s"] > 0


# ---------------------------------------------------------------------------
# schedule purity: rows+cols pallas_calls only, no HBM glue between them
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n2,n", [(512, 512), (4, 2**17), (2**17, 64)])
def test_fft2_schedule_is_pure_pass_program(n2, n):
    # (2**17, 64) is the strip-mined acceptance case: a taller-than-fused
    # image still lowers to pallas_calls + reshapes only — the column
    # digit transpose and inter-factor twiddle live inside the kernels.
    planned = F.plan(F.FFTSpec(n=n, n2=n2, kind="fft2"), backend="pallas")
    x = jnp.zeros((1, n2, n), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a, b: planned.apply_planes(a, b))(x, x).jaxpr
    prims = [e.primitive.name for e in jaxpr.eqns]
    assert prims.count("pallas_call") == len(planned.passes), (n2, n, prims)
    # Zero standalone HBM transpose / twiddle / relayout ops between the
    # kernel calls — the row→column handoff is a free row-major reshape.
    forbidden = {"transpose", "mul", "add", "sub", "gather", "dynamic_slice"}
    assert not forbidden & set(prims), prims
    assert set(prims) <= {"pallas_call", "reshape", "device_put"}, prims


# ---------------------------------------------------------------------------
# numerical acceptance across backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS, indirect=True)
@pytest.mark.parametrize("n2,n", [(16, 64), (64, 128), (32, 2048), (128, 32)])
def test_fft2_matches_numpy(backend, n2, n, rng):
    x = _rand_c(rng, (2, n2, n))
    y = np.asarray(F.fft2(jnp.asarray(x), backend=backend))
    ref = np.fft.fft2(x)
    assert np.abs(y - ref).max() <= 1e-3 * np.abs(ref).max(), (backend, n2, n)


@pytest.mark.parametrize("backend", ["xla", "pallas", DEMOTED], indirect=True)
def test_fft2_split_regime_rows(backend, rng):
    x = _rand_c(rng, (1, 4, 2**17))
    y = np.asarray(F.fft2(jnp.asarray(x), backend=backend))
    ref = np.fft.fft2(x)
    rel = np.abs(y - ref).max() / np.abs(ref).max()
    assert rel < 5e-5, (backend, rel)


@pytest.mark.parametrize("backend", BACKENDS, indirect=True)
def test_fft2_ifft2_roundtrip(backend, rng):
    x = _rand_c(rng, (2, 32, 256))
    y = F.ifft2(F.fft2(jnp.asarray(x), backend=backend), backend=backend)
    np.testing.assert_allclose(np.asarray(y), x, atol=2e-4)


@pytest.mark.parametrize("backend", BACKENDS, indirect=True)
@pytest.mark.parametrize("n2,n", [(16, 64), (64, 256)])
def test_rfft2_matches_numpy_and_roundtrips(backend, n2, n, rng):
    x = rng.standard_normal((2, n2, n)).astype(np.float32)
    Xr, Xi = F.rfft2(jnp.asarray(x), backend=backend)
    ref = np.fft.rfft2(x)
    assert Xr.shape == ref.shape
    scale = np.abs(ref).max()
    np.testing.assert_allclose(np.asarray(Xr), ref.real, atol=3e-3 * scale)
    np.testing.assert_allclose(np.asarray(Xi), ref.imag, atol=3e-3 * scale)
    back = np.asarray(F.irfft2((Xr, Xi), n, n2, backend=backend))
    np.testing.assert_allclose(back, x, atol=2e-3)


def test_rfft2_plan_carries_epilogue_and_trips():
    planned = F.plan(F.FFTSpec(n=256, n2=64, kind="rfft2"), backend="pallas")
    assert planned.epilogue is not None and planned.epilogue.kind == "rfft_recomb"
    inner, cols = planned.children
    # packed rows + recomb epilogue + column pass, in execution order
    assert planned.hbm_round_trips == inner.hbm_round_trips + 1 + cols.hbm_round_trips
    kinds = [p.kind for p in planned.passes]
    assert kinds.index("rfft_recomb") == len(inner.passes)


# ---------------------------------------------------------------------------
# joint-program halves (what the distributed pencil driver consumes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS, indirect=True)
def test_apply_rows_cols_compose_to_fft2(backend, rng):
    planned = F.plan(F.FFTSpec(n=256, n2=128, kind="fft2"), backend=backend)
    x = _rand_c(rng, (2, 128, 256))
    xr, xi = jnp.asarray(x.real), jnp.asarray(x.imag)
    yr, yi = planned.apply_cols(*planned.apply_rows(xr, xi))
    ref = np.fft.fft2(x)
    err = np.abs((np.asarray(yr) + 1j * np.asarray(yi)) - ref).max()
    assert err <= 1e-3 * np.abs(ref).max(), backend


def test_ragged_width_chunk_bounds_padding():
    # rfft2's m+1-wide half-spectrum: a pow2-floored chunk of ~width would
    # pad a whole extra chunk (2x the pass); the executor's chunk keeps the
    # padding under half a chunk (floored at one 128-lane tile).
    from repro.kernels import ops

    p = P.Pass(kind="direct", n=512, view_in=(1, 1, 512), view_out=(1, 1, 512), axis=-2)
    for w in (513, 1025, 2049):
        chunk = ops.image_chunk(p, w)
        assert (-w) % chunk < max(chunk // 2, 128), (w, chunk)
        assert (-w) % chunk < w // 4  # padding waste is bounded, never ~2x
    for w in (128, 512, 2048):  # pow2 widths stay exact
        assert (-w) % ops.image_chunk(p, w) == 0


def test_ragged_width_and_chunk1_execution(rng):
    # Ragged 3·2^k widths and the chunk=1 degenerate execute correctly on
    # both program shapes: a 2-D column pass over a width-24 image, and a
    # 1-D split program with every pass forced to chunk=1.
    from repro.kernels import ops

    p = P.Pass(
        kind="direct", n=64, view_in=(1, 1, 64), view_out=(1, 1, 64),
        order="natural", axis=-2,
    )
    x = _rand_c(rng, (2, 64, 24))
    ref = np.fft.fft(x, axis=-2)
    for chunks in (None, {0: 1}):
        yr, yi = ops.execute_program2d(
            jnp.asarray(x.real), jnp.asarray(x.imag), (p,),
            interpret=True, chunks=chunks,
        )
        err = np.abs((np.asarray(yr) + 1j * np.asarray(yi)) - ref).max()
        assert err <= 1e-4 * np.abs(ref).max(), chunks
    passes1d = P.compile_passes(1024, 256)  # (32, 32) split program
    x1 = _rand_c(rng, (2, 1024))
    ref1 = np.fft.fft(x1)
    yr, yi = ops.execute_program(
        jnp.asarray(x1.real), jnp.asarray(x1.imag), passes1d,
        interpret=True, chunks={i: 1 for i in range(len(passes1d))},
    )
    err = np.abs((np.asarray(yr) + 1j * np.asarray(yi)) - ref1).max()
    assert err <= 1e-4 * np.abs(ref1).max()


def test_apply_cols_accepts_narrow_slab(rng):
    # The column half runs at whatever width the a2a left behind (q = n/D).
    planned = F.plan(F.FFTSpec(n=256, n2=128, kind="fft2"), backend="pallas")
    x = _rand_c(rng, (2, 128, 16))
    yr, yi = planned.apply_cols(jnp.asarray(x.real), jnp.asarray(x.imag))
    ref = np.fft.fft(x, axis=-2)
    err = np.abs((np.asarray(yr) + 1j * np.asarray(yi)) - ref).max()
    assert err <= 1e-3 * np.abs(ref).max()


# ---------------------------------------------------------------------------
# fft_conv2d: the SAR matched-filter path (rfft2/irfft2 plan pair)
# ---------------------------------------------------------------------------


def _direct_conv2d(x, h):
    H, W = x.shape[-2:]
    Hh, Wh = h.shape[-2:]
    out = np.zeros(x.shape[:-2] + (H + Hh - 1, W + Wh - 1), np.float64)
    for a in range(Hh):
        for b in range(Wh):
            out[..., a : a + H, b : b + W] += h[..., a : a + 1, b : b + 1] * x
    return out


def test_fft_conv2d_matches_direct(rng):
    x = rng.standard_normal((2, 24, 50)).astype(np.float32)
    h = rng.standard_normal((3, 7)).astype(np.float32)
    ref = _direct_conv2d(x, h)
    y_full = np.asarray(fft_conv2d(jnp.asarray(x), jnp.asarray(h), mode="full"))
    np.testing.assert_allclose(y_full, ref, atol=2e-3)
    y_same = np.asarray(fft_conv2d(jnp.asarray(x), jnp.asarray(h)))
    np.testing.assert_allclose(y_same, ref[..., :24, :50], atol=2e-3)


def test_fft_conv2d_row_matched_filter(rng):
    # A (1, Lh) filter is per-row range compression: equals 1-D row convs.
    x = rng.standard_normal((16, 128)).astype(np.float32)
    h = rng.standard_normal((1, 32)).astype(np.float32)
    y = np.asarray(fft_conv2d(jnp.asarray(x), jnp.asarray(h)))
    ref = np.stack([np.convolve(row, h[0], mode="full")[:128] for row in x])
    np.testing.assert_allclose(y, ref, atol=2e-3)


def test_toeplitz_ref_exercises_every_filter(rng):
    # Regression: the oracle used to convolve every row with h[0].
    x = rng.standard_normal((4, 32))
    hs = rng.standard_normal((4, 8))
    ref = toeplitz_conv_ref(x, hs)
    manual = np.stack(
        [np.convolve(x[i], hs[i], mode="full")[:32] for i in range(4)]
    )
    np.testing.assert_allclose(ref, manual)
    # a wrong (h[0]-only) oracle would disagree on rows 1..3
    wrong = np.stack([np.convolve(x[i], hs[0], mode="full")[:32] for i in range(4)])
    assert not np.allclose(ref[1:], wrong[1:])
