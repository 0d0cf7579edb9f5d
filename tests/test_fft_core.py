"""Core FFT correctness + property-based invariants (hypothesis optional)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st
from conftest import DEMOTED

from repro.core import fft as F

REGISTERED = ["stockham", "xla", "pallas"]
BACKENDS = REGISTERED + [DEMOTED]
SIZES = [2, 8, 64, 256, 1024, 4096]


def _rand_c(rng, shape):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
        np.complex64
    )


@pytest.mark.parametrize("backend", BACKENDS, indirect=True)
@pytest.mark.parametrize("n", SIZES)
def test_fft_matches_numpy(backend, n, rng):
    x = _rand_c(rng, (3, n))
    y = np.asarray(F.fft(jnp.asarray(x), backend=backend))
    ref = np.fft.fft(x)
    np.testing.assert_allclose(y, ref, rtol=2e-4, atol=2e-3 * np.abs(ref).max())


@pytest.mark.parametrize("backend", BACKENDS, indirect=True)
def test_fft_large_split_regime(backend, rng):
    n = 2**17  # forces the 2-round-trip plan
    x = _rand_c(rng, (1, n))
    y = np.asarray(F.fft(jnp.asarray(x), backend=backend))
    ref = np.fft.fft(x)
    rel = np.abs(y - ref).max() / np.abs(ref).max()
    assert rel < 5e-5, rel


@pytest.mark.parametrize("backend", BACKENDS, indirect=True)
@pytest.mark.parametrize("n", [16, 1024, 4096])
def test_roundtrip(backend, n, rng):
    x = _rand_c(rng, (2, n))
    y = F.ifft(F.fft(jnp.asarray(x), backend=backend), backend=backend)
    np.testing.assert_allclose(np.asarray(y), x, atol=2e-4)


@pytest.mark.parametrize("n", [16, 256, 4096])
def test_rfft_matches_numpy(n, rng):
    x = rng.standard_normal((2, n)).astype(np.float32)
    Xr, Xi = F.rfft(jnp.asarray(x))
    ref = np.fft.rfft(x)
    np.testing.assert_allclose(np.asarray(Xr), ref.real, atol=3e-3 * np.abs(ref).max())
    np.testing.assert_allclose(np.asarray(Xi), ref.imag, atol=3e-3 * np.abs(ref).max())
    back = np.asarray(F.irfft((Xr, Xi), n))
    np.testing.assert_allclose(back, x, atol=2e-4)


def test_fft2_matches_numpy(rng):
    x = _rand_c(rng, (2, 64, 128))
    y = np.asarray(F.fft2(jnp.asarray(x)))
    ref = np.fft.fft2(x)
    np.testing.assert_allclose(y, ref, atol=2e-3 * np.abs(ref).max())


def test_planes_api(rng):
    x = _rand_c(rng, (2, 256))
    yr, yi = F.fft((jnp.asarray(x.real), jnp.asarray(x.imag)))
    ref = np.fft.fft(x)
    np.testing.assert_allclose(np.asarray(yr), ref.real, atol=2e-3 * np.abs(ref).max())
    np.testing.assert_allclose(np.asarray(yi), ref.imag, atol=2e-3 * np.abs(ref).max())


# --------------------------------------------------------------------------
# property-based invariants
# --------------------------------------------------------------------------

_sizes = st.sampled_from([8, 64, 256, 1024])
_seed = st.integers(0, 2**31 - 1)
_backend = st.sampled_from(REGISTERED)


@settings(max_examples=20, deadline=None)
@given(n=_sizes, seed=_seed, backend=_backend)
def test_linearity(n, seed, backend):
    rng = np.random.default_rng(seed)
    a, b = rng.standard_normal(2).astype(np.float32)
    x = _rand_c(rng, (n,))
    y = _rand_c(rng, (n,))
    lhs = F.fft(jnp.asarray(a * x + b * y), backend=backend)
    rhs = a * F.fft(jnp.asarray(x), backend=backend) + b * F.fft(
        jnp.asarray(y), backend=backend
    )
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-2)


@settings(max_examples=20, deadline=None)
@given(n=_sizes, seed=_seed, backend=_backend)
def test_parseval(n, seed, backend):
    rng = np.random.default_rng(seed)
    x = _rand_c(rng, (n,))
    X = np.asarray(F.fft(jnp.asarray(x), backend=backend))
    lhs = np.sum(np.abs(x) ** 2)
    rhs = np.sum(np.abs(X) ** 2) / n
    np.testing.assert_allclose(lhs, rhs, rtol=1e-3)


@settings(max_examples=20, deadline=None)
@given(n=_sizes, seed=_seed, shift=st.integers(0, 63), backend=_backend)
def test_time_shift_theorem(n, seed, shift, backend):
    rng = np.random.default_rng(seed)
    shift = shift % n
    x = _rand_c(rng, (n,))
    X = np.asarray(F.fft(jnp.asarray(x), backend=backend))
    Xs = np.asarray(F.fft(jnp.asarray(np.roll(x, shift)), backend=backend))
    k = np.arange(n)
    phase = np.exp(-2j * np.pi * k * shift / n)
    np.testing.assert_allclose(Xs, X * phase, atol=2e-2 * (np.abs(X).max() + 1))


@settings(max_examples=10, deadline=None)
@given(n=_sizes, pos=st.integers(0, 1023), backend=_backend)
def test_impulse_is_phasor(n, pos, backend):
    pos = pos % n
    x = np.zeros(n, np.complex64)
    x[pos] = 1.0
    X = np.asarray(F.fft(jnp.asarray(x), backend=backend))
    k = np.arange(n)
    ref = np.exp(-2j * np.pi * k * pos / n)
    np.testing.assert_allclose(X, ref, atol=1e-3)


@settings(max_examples=15, deadline=None)
@given(n=_sizes, seed=_seed)
def test_backends_agree(n, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(_rand_c(rng, (n,)))
    ys = [np.asarray(F.fft(x, backend=b)) for b in REGISTERED]
    np.testing.assert_allclose(ys[0], ys[1], atol=1e-2)
    np.testing.assert_allclose(ys[0], ys[2], atol=1e-2)


# ---------------------------------------------------------------------------
# twiddle overflow regression: huge-n traced tables with x64 DISABLED
# ---------------------------------------------------------------------------


def test_traced_twiddle_int32_safe_beyond_2_31():
    """n > 2³¹ twiddles must be right under the default (x64-off) config.

    The old implementation built jnp.int64 iotas which silently downcast to
    int32 without x64, so the (k1·m2) % n reduction overflowed — producing
    wrong twiddles exactly in the huge-N regime the traced tables exist for.
    A column window keeps the table small while the products span ~2³³.
    """
    from repro.core import twiddle as tw

    assert not jax.config.jax_enable_x64  # the regression's precondition
    n1, n2 = 1 << 15, 1 << 18  # n = 2**33 > 2**31
    n = n1 * n2
    q = 64
    start = n2 - q  # top of the range: k1·m2 up to ~n, the overflow zone
    tr, ti = tw.traced_twiddle(n1, n2, col_start=start, col_count=q)
    k1 = np.arange(n1, dtype=np.int64)[:, None]
    m2 = (start + np.arange(q, dtype=np.int64))[None, :]
    ang = (2.0 * np.pi / n) * ((k1 * m2) % n).astype(np.float64)
    np.testing.assert_allclose(np.asarray(tr), np.cos(ang), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ti), -np.sin(ang), atol=2e-5)


def test_traced_twiddle_at_exactly_2_31():
    # The boundary case: n == 2**31 must take the int32-safe path (an int32
    # `% n` operand would fail to parse at trace time).
    from repro.core import twiddle as tw

    n1, n2 = 1 << 15, 1 << 16  # n = 2**31
    q, start = 32, (1 << 16) - 32
    tr, ti = tw.traced_twiddle(n1, n2, col_start=start, col_count=q)
    k1 = np.arange(n1, dtype=np.int64)[:, None]
    m2 = (start + np.arange(q, dtype=np.int64))[None, :]
    ang = (2.0 * np.pi / (n1 * n2)) * ((k1 * m2) % (n1 * n2)).astype(np.float64)
    np.testing.assert_allclose(np.asarray(tr), np.cos(ang), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ti), -np.sin(ang), atol=2e-5)


def test_traced_twiddle_small_n_matches_host_grid():
    from repro.core import twiddle as tw

    for n1, n2 in [(8, 16), (64, 64)]:
        tr, ti = tw.traced_twiddle(n1, n2)
        hr, hi = tw.twiddle_grid(n1, n2)
        np.testing.assert_allclose(np.asarray(tr), hr, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ti), hi, atol=1e-6)
        # the column window agrees with the full grid
        wr, wi = tw.traced_twiddle(n1, n2, col_start=4, col_count=8)
        np.testing.assert_allclose(np.asarray(wr), hr[:, 4:12], atol=1e-6)
        np.testing.assert_allclose(np.asarray(wi), hi[:, 4:12], atol=1e-6)


def test_mulfrac_pow2_exact_across_regimes():
    from repro.core import twiddle as tw

    rng = np.random.default_rng(7)
    for e in (20, 31, 32, 33, 40, 48):
        n = 1 << e
        k1 = rng.integers(0, min(n, 2**31), size=(32, 1))
        m2 = rng.integers(0, min(n, 2**31), size=(1, 32))
        exact = ((k1 * m2) % n) / n
        got = np.asarray(
            tw.mulfrac_pow2(
                jnp.asarray(k1, jnp.int32), jnp.asarray(m2, jnp.int32), n
            )
        ) % 1.0
        err = np.abs(got - exact)
        err = np.minimum(err, 1.0 - err)  # wrap at the 0/1 seam
        assert err.max() < 1e-6, (e, err.max())
