"""Arbitrary-length FFTs: the Bluestein chirp-conv leaf.

The tentpole's acceptance gates, made literal:

* numerics — planned non-pow2 fft/ifft/rfft/irfft match ``numpy.fft`` at
  1e-3 across primes, 3·2^k, and the n=1 degenerate case;
* purity — a Bluestein leaf executes as pallas_calls + shape glue only
  (jaxpr-asserted), and a demoted one as no pallas_call at all, one
  degradation per pass;
* interning — the chirp spectrum is computed once per interned plan: zero
  new plans on warm reuse (``plan_log()``-asserted), and the spectrum LUT
  is cache-identical across lookups.

Plus the split-regime composition, tuning knob, validation-message, and
hypothesis property sweeps that ride along.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from conftest import DEMOTED

from repro.analysis import roofline as rl
from repro.core import fft as F
from repro.core import limits
from repro.core import plan as P
from repro.core import twiddle as tw
from repro.kernels import ops

PRIMES = [3, 7, 97, 251, 2029]
THREE_POW2 = [6, 12, 96, 1536]
SIZES = PRIMES + THREE_POW2 + [1]


def _rand_c(rng, shape):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
        np.complex64
    )


# ---------------------------------------------------------------------------
# numerics gate: primes, 3·2^k, n=1 vs numpy at 1e-3
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("backend", ["pallas", DEMOTED, "xla"], indirect=True)
def test_fft_ifft_match_numpy(n, backend, rng):
    x = _rand_c(rng, (3, n))
    tol = 1e-3 * max(np.abs(np.fft.fft(x)).max(), 1.0)
    y = np.asarray(F.plan(F.FFTSpec(n=n), backend=backend)(jnp.asarray(x)))
    np.testing.assert_allclose(y, np.fft.fft(x), atol=tol)
    z = np.asarray(
        F.plan(F.FFTSpec(n=n, kind="ifft"), backend=backend)(jnp.asarray(x))
    )
    np.testing.assert_allclose(z, np.fft.ifft(x), atol=1e-3)


@pytest.mark.parametrize("n", [n for n in SIZES if n >= 2])
def test_rfft_irfft_match_numpy(n, rng):
    x = rng.standard_normal((3, n)).astype(np.float32)
    ref = np.fft.rfft(x)
    tol = 1e-3 * max(np.abs(ref).max(), 1.0)
    Xr, Xi = F.rfft(jnp.asarray(x))
    assert Xr.shape[-1] == n // 2 + 1
    np.testing.assert_allclose(np.asarray(Xr) + 1j * np.asarray(Xi), ref, atol=tol)
    back = np.asarray(F.irfft((Xr, Xi), n))
    np.testing.assert_allclose(back, x, atol=1e-3)


def test_fft2_non_pow2_rows(rng):
    x = _rand_c(rng, (2, 16, 97))
    p = F.plan(F.FFTSpec(n=97, kind="fft2", n2=16))
    y = np.asarray(p(jnp.asarray(x)))
    ref = np.fft.fft2(x)
    np.testing.assert_allclose(y, ref, atol=1e-3 * np.abs(ref).max())


# ---------------------------------------------------------------------------
# jaxpr purity: pallas_calls + shape glue only; demoted, no pallas_call
# ---------------------------------------------------------------------------

_GLUE = {
    "reshape",
    "pad",
    "slice",
    "squeeze",
    "device_put",
    "convert_element_type",
    "broadcast_in_dim",
    "pjit",
    "jit",
}


def _collect_prims(jaxpr, acc):
    for e in jaxpr.eqns:
        acc.append(e.primitive.name)
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                _collect_prims(inner, acc)
    return acc


@pytest.mark.parametrize("n", [97, 2029])
@pytest.mark.parametrize("backend", ["pallas", DEMOTED], indirect=True)
def test_bluestein_leaf_is_pallas_calls_plus_glue(n, backend, request):
    p = F.plan(F.FFTSpec(n=n), backend=backend, tune="off")
    assert all(k.kind == "bluestein" for k in p.passes)
    # tile-aligned batch: no pad/unpad glue beyond the leaf's own framing
    bt = p.batch_tiles[n]
    xr = jnp.zeros((bt, n), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a, b: p.apply_planes(a, b))(xr, xr)
    prims = _collect_prims(jaxpr.jaxpr, [])
    if request.node.callspec.params["backend"] == DEMOTED:
        # Every stage runs ops' XLA fallback: no kernel, one demotion each.
        assert "pallas_call" not in prims, prims
        assert [d["pass"] for d in p.degradations] == list(range(len(p.passes)))
        return
    assert not p.degradations
    assert prims.count("pallas_call") == len(p.passes), prims
    stray = [q for q in prims if q != "pallas_call" and q not in _GLUE]
    assert not stray, f"Bluestein leaf leaked XLA math outside the kernel: {stray}"


# ---------------------------------------------------------------------------
# interning: one plan per spec, one chirp spectrum per (n, pad, dir)
# ---------------------------------------------------------------------------


def test_warm_reuse_plans_nothing(rng):
    spec = F.FFTSpec(n=251)
    p = F.plan(spec)  # cold: intern the plan + chirp LUTs
    x = _rand_c(rng, (2, 251))
    p(jnp.asarray(x))
    F.clear_plan_log()
    for _ in range(3):
        q = F.plan(spec)
        assert q is p
        q(jnp.asarray(x))
    assert len(F.plan_log()) == 0, F.plan_log()


def test_chirp_spectrum_cached_identity():
    a = tw.bluestein_spectrum(97, 256)
    b = tw.bluestein_spectrum(97, 256)
    assert a is b  # lru-cached: computed once, interned like twiddle LUTs
    assert tw.bluestein_chirp(97) is tw.bluestein_chirp(97)
    assert tw.bluestein_spectrum(97, 512) is not a  # pad is part of the key


# ---------------------------------------------------------------------------
# program shapes: fused 2-pass leaf, split composition, limits helpers
# ---------------------------------------------------------------------------


def test_fused_bluestein_is_two_passes():
    prog = P.compile_bluestein(2029)
    assert [(p.kind, p.stage) for p in prog] == [
        ("bluestein", "fwd"),
        ("bluestein", "inv"),
    ]
    assert prog[0].n1 == limits.bluestein_pad(2029) == 4096


def test_split_bluestein_composes_with_pass_programs(rng):
    # Force the inner pow2 conv past fused_max: the chirp stages become
    # standalone passes around the inner split-regime programs.
    plan = P.plan_fft(300, fused_max=256)
    kinds = [p.kind for p in plan.passes]
    assert kinds.count("bluestein") >= 3  # pre / mul / post at least
    assert any(k != "bluestein" for k in kinds)  # inner pow2 program inlined
    x = _rand_c(rng, (2, 300))
    yr, yi = ops.execute_plan(
        jnp.asarray(x.real), jnp.asarray(x.imag), plan, interpret=True
    )
    ref = np.fft.fft(x)
    np.testing.assert_allclose(
        np.asarray(yr) + 1j * np.asarray(yi), ref, atol=1e-3 * np.abs(ref).max()
    )


def test_limits_helpers():
    assert limits.next_fast_len(48) == 64
    assert limits.bluestein_pad(97) == 256  # next_pow2(2*97 - 1)
    assert limits.bluestein_pad(2029) == 4096
    assert limits.BLUESTEIN_MIN == 2


def test_tuning_pad_knob(rng):
    # The chirp pad length is a searchable knob: 2x the minimal pad is a
    # legal plan and still correct.
    pad = 2 * limits.bluestein_pad(97)
    plan = P.plan_fft(97, pad=pad)
    assert plan.passes[0].n1 == pad
    x = _rand_c(rng, (2, 97))
    yr, yi = ops.execute_plan(
        jnp.asarray(x.real), jnp.asarray(x.imag), plan, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(yr) + 1j * np.asarray(yi), np.fft.fft(x), atol=1e-2
    )
    with pytest.raises(ValueError):
        P.plan_fft(128, pad=512)  # pad is a Bluestein-only knob


# ---------------------------------------------------------------------------
# validation messages + roofline report
# ---------------------------------------------------------------------------


def test_validation_errors_name_the_route():
    with pytest.raises(ValueError, match="Bluestein"):
        F.FFTSpec(n=48, kind="rfft2", n2=64)
    with pytest.raises(ValueError, match="fft"):
        F.FFTSpec(n=64, kind="dct")
    with pytest.raises(ValueError):
        F.FFTSpec(n=0)


def test_bluestein_report():
    rep = rl.bluestein_report(2029)
    assert rep["pad"] == 4096
    assert 2.0 <= rep["pad_ratio"] <= 2.1
    assert rep["flops_overhead"] > 1.0
    assert rep["hbm_round_trips"] == 2
    with pytest.raises(ValueError):
        rl.bluestein_report(1024)  # pow2 lengths don't pay the chirp tax


def test_describe_surfaces_the_tax():
    d = F.plan(F.FFTSpec(n=2029)).describe()
    assert "bluestein" in d and "pad 4096" in d


# ---------------------------------------------------------------------------
# hypothesis property sweep: random n ∈ [2, 4096]
# ---------------------------------------------------------------------------


@given(n=st.integers(min_value=2, max_value=4096))
@settings(max_examples=20, deadline=None)
def test_property_random_n_matches_numpy(n):
    rng = np.random.default_rng(n)
    x = _rand_c(rng, (2, n))
    spec = F.FFTSpec(n=n)
    p = F.plan(spec)
    assert F.plan(spec) is p  # plan-cache interning across repeated specs
    y = np.asarray(p(jnp.asarray(x)))
    ref = np.fft.fft(x)
    np.testing.assert_allclose(y, ref, atol=1e-3 * max(np.abs(ref).max(), 1.0))
