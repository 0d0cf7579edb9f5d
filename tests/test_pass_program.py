"""The linearized pass program: schedule purity, pencil kernels, epilogues.

The split regime's acceptance criterion (paper §2.3.2 made literal): the
executed schedule is exactly ``len(plan.passes)`` pallas_call round trips
with zero standalone HBM transpose / twiddle-cmul ops between them — glue
lives inside the kernels.  Asserted over the jaxpr, plus numerical
acceptance of the executor and the individual pass kernels.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import DEMOTED

from repro.analysis import roofline as rl
from repro.core import fft as F
from repro.core import plan as P
from repro.core import twiddle as tw
from repro.kernels import ops, pencil


def _rand(rng, shape):
    return (
        rng.standard_normal(shape).astype(np.float32),
        rng.standard_normal(shape).astype(np.float32),
    )


# ---------------------------------------------------------------------------
# schedule purity: pallas_call round trips only, no HBM glue between them
# ---------------------------------------------------------------------------


def _top_level_primitives(n):
    plan = P.plan_fft(n)

    def run(xr, xi):
        return ops.execute_plan(xr, xi, plan, interpret=True)

    xr = jnp.zeros((1, n), jnp.float32)
    jaxpr = jax.make_jaxpr(run)(xr, xr).jaxpr
    return [e.primitive.name for e in jaxpr.eqns], plan


@pytest.mark.parametrize("n", [2**17, 2**18])
def test_schedule_is_pure_pass_program(n):
    prims, plan = _top_level_primitives(n)
    kernel_calls = prims.count("pallas_call")
    assert kernel_calls == len(plan.passes), (n, prims)
    # Zero standalone HBM relayout or twiddle ops between the kernel calls:
    # the only non-kernel primitives are free row-major reshapes.
    forbidden = {"transpose", "mul", "add", "sub", "gather", "dynamic_slice"}
    assert not forbidden & set(prims), prims
    # device_put: the host-cached LUT constants entering the trace.
    assert set(prims) <= {"pallas_call", "reshape", "device_put"}, prims


def test_n18_schedule_beats_paper_call_count():
    # Paper §2.3.2: ≥ 3 global-memory kernel calls beyond 32K.  The fused
    # program covers N = 2¹⁸ in 2 — twiddle and natural-order transpose ride
    # inside the kernels.
    prims, plan = _top_level_primitives(2**18)
    assert prims.count("pallas_call") == plan.hbm_round_trips == 2


# ---------------------------------------------------------------------------
# executor acceptance (split regime) — natural and pencil order
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("inverse", [False, True])
def test_execute_program_matches_jnp_2e18(inverse, rng):
    n = 2**18
    xr, xi = _rand(rng, (2, n))
    plan = P.plan_fft(n)
    yr, yi = ops.execute_plan(
        jnp.asarray(xr), jnp.asarray(xi), plan, inverse=inverse, interpret=True
    )
    x = xr + 1j * xi
    ref = np.fft.ifft(x) if inverse else np.fft.fft(x)
    err = np.abs((np.asarray(yr) + 1j * np.asarray(yi)) - ref).max()
    assert err <= 1e-3 * np.abs(ref).max()


def test_pencil_order_is_k1_major_permutation(rng):
    n = 2**17
    f0, f1 = P.program_factors(n)
    xr, xi = _rand(rng, (1, n))
    plan = P.plan_fft(n)
    nat = ops.execute_plan(jnp.asarray(xr), jnp.asarray(xi), plan, interpret=True)
    pen = ops.execute_plan(
        jnp.asarray(xr), jnp.asarray(xi), plan, interpret=True, order="pencil"
    )
    # pencil[k0, k1] holds X[k0 + f0·k1]: transposing recovers natural order.
    for a, b in zip(pen, nat):
        a = np.asarray(a).reshape(1, f0, f1).transpose(0, 2, 1).reshape(1, n)
        np.testing.assert_allclose(a, np.asarray(b), rtol=0, atol=1e-4)


def test_pencil_program_has_no_reorder_and_uniform_views():
    for n in (2**17, 2**18, 2**20):
        passes = P.compile_passes(n, order="pencil")
        assert all(p.kind != "reorder" for p in passes)
        assert all(p.view_in == p.view_out for p in passes)
        assert passes[-1].order == "pencil"


# ---------------------------------------------------------------------------
# pass kernels in isolation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("f,s", [(256, 128), (512, 256)])
def test_cols_pass_matches_axis_fft(f, s, rng):
    xr, xi = _rand(rng, (2, f, s))
    wr, wi = tw.dft_matrix(f)
    yr, yi = pencil.cols_pass_call(
        jnp.asarray(xr), jnp.asarray(xi), (wr, wi), kind="direct",
        chunk=s // 2, interpret=True,
    )
    ref = np.fft.fft(xr + 1j * xi, axis=1)
    scale = np.abs(ref).max()
    np.testing.assert_allclose(np.asarray(yr), ref.real, atol=3e-4 * scale)
    np.testing.assert_allclose(np.asarray(yi), ref.imag, atol=3e-4 * scale)


def test_cols_pass_fused4_kind(rng):
    f, s = 2048, 128  # f > DIRECT_MAX → in-VMEM four-step per pencil
    n1, n2 = P.four_step_split(f)  # a lane group: (16, 128)
    xr, xi = _rand(rng, (1, f, s))
    yr, yi = pencil.cols_pass_call(
        jnp.asarray(xr), jnp.asarray(xi), ops._fused_luts(n1, n2, False),
        kind="fused4", n1=n1, n2=n2, chunk=s, interpret=True,
    )
    ref = np.fft.fft(xr + 1j * xi, axis=1)
    scale = np.abs(ref).max()
    np.testing.assert_allclose(np.asarray(yr), ref.real, atol=4e-4 * scale)
    np.testing.assert_allclose(np.asarray(yi), ref.imag, atol=4e-4 * scale)


def test_cols_pass_twiddle_epilogue(rng):
    f, s = 128, 128
    xr, xi = _rand(rng, (1, f, s))
    wr, wi = tw.dft_matrix(f)
    twr, twi = tw.pass_twiddle(f, s)
    yr, yi = pencil.cols_pass_call(
        jnp.asarray(xr), jnp.asarray(xi), (wr, wi), (twr, twi),
        kind="direct", chunk=64, interpret=True,
    )
    base = np.fft.fft(xr + 1j * xi, axis=1)
    ref = base * (twr + 1j * twi)[None]
    scale = np.abs(ref).max()
    np.testing.assert_allclose(np.asarray(yr), ref.real, atol=3e-4 * scale)
    np.testing.assert_allclose(np.asarray(yi), ref.imag, atol=3e-4 * scale)


def test_rows_natural_fuses_transpose(rng):
    p, f = 64, 256
    xr, xi = _rand(rng, (2, p, f))
    wr, wi = tw.dft_matrix(f)
    yr, yi = pencil.rows_natural_call(
        jnp.asarray(xr), jnp.asarray(xi), (wr, wi), kind="direct",
        chunk=32, interpret=True,
    )
    assert yr.shape == (2, f, p)
    ref = np.fft.fft(xr + 1j * xi, axis=-1).transpose(0, 2, 1)
    scale = np.abs(ref).max()
    np.testing.assert_allclose(np.asarray(yr), ref.real, atol=3e-4 * scale)
    np.testing.assert_allclose(np.asarray(yi), ref.imag, atol=3e-4 * scale)


# ---------------------------------------------------------------------------
# axis=-2 column execution (the distributed pencil driver's pass)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["pallas", "xla", "stockham", DEMOTED], indirect=True)
def test_axis_minus2_plan_matches_jnp(backend, rng):
    n, q = 512, 128
    xr, xi = _rand(rng, (2, n, q))
    planned = F.plan(F.FFTSpec(n=n, kind="fft", axis=-2), backend=backend)
    yr, yi = planned.apply_planes(jnp.asarray(xr), jnp.asarray(xi))
    ref = np.fft.fft(xr + 1j * xi, axis=-2)
    err = np.abs((np.asarray(yr) + 1j * np.asarray(yi)) - ref).max()
    assert err <= 1e-3 * np.abs(ref).max(), backend


def test_axis_minus2_pallas_emits_no_transpose():
    n, q = 512, 128
    planned = F.plan(F.FFTSpec(n=n, kind="fft", axis=-2), backend="pallas")
    x = jnp.zeros((1, n, q), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a, b: planned.apply_planes(a, b))(x, x).jaxpr
    prims = [e.primitive.name for e in jaxpr.eqns]
    assert "transpose" not in prims, prims
    assert prims.count("pallas_call") == 1, prims


# ---------------------------------------------------------------------------
# rfft/irfft recombination as a kernel epilogue pass
# ---------------------------------------------------------------------------


def test_rfft_irfft_pallas_epilogue_pass(rng):
    n = 4096
    x = rng.standard_normal((3, n)).astype(np.float32)
    planned = F.plan(F.FFTSpec(n=n, kind="rfft"), backend="pallas")
    assert planned.epilogue is not None and planned.epilogue.kind == "rfft_recomb"
    Xr, Xi = planned(jnp.asarray(x))
    ref = np.fft.rfft(x)
    scale = np.abs(ref).max()
    np.testing.assert_allclose(np.asarray(Xr), ref.real, atol=3e-3 * scale)
    np.testing.assert_allclose(np.asarray(Xi), ref.imag, atol=3e-3 * scale)
    inv = F.plan(F.FFTSpec(n=n, kind="irfft"), backend="pallas")
    assert inv.epilogue is not None and inv.epilogue.kind == "irfft_recomb"
    back = inv((Xr, Xi))
    np.testing.assert_allclose(np.asarray(back), x, atol=2e-4)
    # the epilogue is one extra HBM round trip on top of the inner plan
    assert planned.hbm_round_trips == planned.children[0].fft_plan.hbm_round_trips + 1


# ---------------------------------------------------------------------------
# modeled HBM bytes (dryrun/roofline observability)
# ---------------------------------------------------------------------------


def test_fft_pass_report_models_round_trips():
    rep = rl.fft_pass_report(2**18, batch=2)
    assert rep["hbm_round_trips"] == len(rep["passes"]) == 2
    sig = 2 * (2**18) * 2 * 4  # batch · n · split-complex f32
    for entry in rep["passes"]:
        assert entry["hbm_bytes"] >= 2 * sig  # read + write at least
    assert rep["modeled_hbm_bytes"] == sum(e["hbm_bytes"] for e in rep["passes"])
    assert rep["memory_s"] > 0
    # the twiddle grid is charged to the pass that fuses it
    assert rep["passes"][0]["twiddle"] is not None
    assert rep["passes"][1]["twiddle"] is None
