"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracles.

Sweeps shapes and regimes per the assignment: every kernel is asserted
allclose against ref.py's float64 naive DFT (small N) and jnp.fft.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fft as F
from repro.core import twiddle as tw
from repro.kernels import ops, ref
from repro.kernels.dft_matmul import dft_matmul_call
from repro.kernels.fft4step import fft4step_call


def _rand(rng, shape):
    return (
        rng.standard_normal(shape).astype(np.float32),
        rng.standard_normal(shape).astype(np.float32),
    )


@pytest.mark.parametrize("n", [16, 64, 256, 1024])
@pytest.mark.parametrize("batch", [1, 3, 8])
def test_dft_matmul_vs_naive(n, batch, rng):
    xr, xi = _rand(rng, (batch, n))
    wr, wi = tw.dft_matrix(n)
    yr, yi = dft_matmul_call(
        jnp.asarray(xr), jnp.asarray(xi), jnp.asarray(wr), jnp.asarray(wi),
        batch_tile=batch, interpret=True,
    )
    refv = ref.naive_dft(xr + 1j * xi)
    scale = np.abs(refv).max()
    np.testing.assert_allclose(np.asarray(yr), refv.real, atol=2e-4 * scale)
    np.testing.assert_allclose(np.asarray(yi), refv.imag, atol=2e-4 * scale)


def _fft4step(xr, xi, n1, n2, batch_tile, natural_order=True, **kw):
    """fft4step_call with the leaf's own LUTs for that order."""
    luts = ops._fused_luts(n1, n2, False, natural_order)
    return fft4step_call(
        jnp.asarray(xr), jnp.asarray(xi), *(jnp.asarray(a) for a in luts),
        batch_tile=batch_tile, natural_order=natural_order, interpret=True, **kw,
    )


# (n1, n2): the lane groups of n = 2048, 4096 and 8192 (p = 8, 4, 2 signals
# per 128-lane GEMM), one of p = 1, and a one-signal group (n1 ≥ 128).
# (b, batch_tile): one signal; tiles smaller than a group; one full group
# and a ragged tail; two grid steps of two full groups.
@pytest.mark.parametrize(
    "n1,n2", [(16, 128), (32, 128), (64, 128), (64, 64), (128, 64)]
)
@pytest.mark.parametrize("b,batch_tile", [(1, 1), (2, 1), (4, 2), (13, 13), (32, 16)])
def test_fft4step_vs_four_step_ref(n1, n2, b, batch_tile, rng):
    n = n1 * n2
    xr, xi = _rand(rng, (b, n))
    yr, yi = _fft4step(xr, xi, n1, n2, batch_tile)
    refv = ref.four_step_ref(xr + 1j * xi, n1, n2)
    oracle = ref.naive_dft if n <= 4096 else np.fft.fft
    refv2 = oracle(xr + 1j * xi)
    scale = np.abs(refv).max()
    np.testing.assert_allclose(refv, refv2, atol=1e-9 * scale)  # ref self-check
    np.testing.assert_allclose(np.asarray(yr), refv.real, atol=3e-4 * scale)
    np.testing.assert_allclose(np.asarray(yi), refv.imag, atol=3e-4 * scale)


@pytest.mark.parametrize("n1,n2", [(64, 64), (32, 128), (64, 128)])
def test_fft4step_pencil_layout(n1, n2, rng):
    n = n1 * n2
    xr, xi = _rand(rng, (2, n))
    yr, yi = _fft4step(xr, xi, n1, n2, 2, natural_order=False)
    refv = np.fft.fft(xr + 1j * xi)
    # pencil (k1-major): y.reshape(n1, n2)[k1, k2] == X[k1 + n1*k2]
    y = (np.asarray(yr) + 1j * np.asarray(yi)).reshape(2, n1, n2)
    perm = refv.reshape(2, n2, n1).transpose(0, 2, 1)
    np.testing.assert_allclose(y, perm, atol=3e-4 * np.abs(refv).max())


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("n", [16, 1024, 2048, 4096, 8192, 16384])
def test_ops_fft_all_regimes(n, inverse, rng):
    xr, xi = _rand(rng, (3, n))
    yr, yi = ops.fft(jnp.asarray(xr), jnp.asarray(xi), inverse=inverse, interpret=True)
    x = xr + 1j * xi
    refv = np.fft.ifft(x) if inverse else np.fft.fft(x)
    scale = np.abs(refv).max()
    np.testing.assert_allclose(np.asarray(yr), refv.real, atol=4e-4 * scale)
    np.testing.assert_allclose(np.asarray(yi), refv.imag, atol=4e-4 * scale)


def test_ops_fft_split_regime_smoke(rng):
    n = 2**17  # two pallas_call passes via the ops-level split
    xr, xi = _rand(rng, (1, n))
    yr, yi = ops.fft(jnp.asarray(xr), jnp.asarray(xi), interpret=True)
    refv = np.fft.fft(xr + 1j * xi)
    rel = np.abs((np.asarray(yr) + 1j * np.asarray(yi)) - refv).max() / np.abs(refv).max()
    assert rel < 1e-4, rel


def test_ops_batch_padding(rng):
    # batch not a multiple of the tile must round-trip unchanged
    xr, xi = _rand(rng, (5, 2048))
    yr, yi = ops.fft(jnp.asarray(xr), jnp.asarray(xi), interpret=True)
    assert yr.shape == (5, 2048)
    refv = np.fft.fft(xr + 1j * xi)
    np.testing.assert_allclose(
        np.asarray(yr) + 1j * np.asarray(yi), refv, atol=3e-4 * np.abs(refv).max()
    )


def test_ops_nd_batch(rng):
    xr, xi = _rand(rng, (2, 3, 1024))
    yr, yi = ops.fft(jnp.asarray(xr), jnp.asarray(xi), interpret=True)
    refv = np.fft.fft(xr + 1j * xi)
    np.testing.assert_allclose(
        np.asarray(yr) + 1j * np.asarray(yi), refv, atol=3e-4 * np.abs(refv).max()
    )


def test_dft_matmul_twiddle_epilogue(rng):
    """Post-GEMM per-bin twiddle rides the same HBM round trip."""
    n, b = 256, 4
    xr, xi = _rand(rng, (b, n))
    wr, wi = tw.dft_matrix(n)
    er, ei = tw.rfft_recomb_twiddle(2 * n)  # any unit phasor table works
    er, ei = er[:n], ei[:n]
    yr, yi = dft_matmul_call(
        jnp.asarray(xr), jnp.asarray(xi), jnp.asarray(wr), jnp.asarray(wi),
        batch_tile=b, twiddle=(er, ei), interpret=True,
    )
    refv = ref.naive_dft(xr + 1j * xi) * (er + 1j * ei)[None]
    scale = np.abs(refv).max()
    np.testing.assert_allclose(np.asarray(yr), refv.real, atol=3e-4 * scale)
    np.testing.assert_allclose(np.asarray(yi), refv.imag, atol=3e-4 * scale)


@pytest.mark.parametrize("n1,n2", [(64, 64), (16, 128), (64, 128)])
def test_fft4step_twiddle_after_epilogue(n1, n2, rng):
    n = n1 * n2
    xr, xi = _rand(rng, (2, n))
    er, ei = tw.rfft_recomb_twiddle(2 * n)
    er, ei = er[:n], ei[:n]
    yr, yi = _fft4step(xr, xi, n1, n2, 2, twiddle_after=(er, ei))
    refv = np.fft.fft(xr + 1j * xi) * (er + 1j * ei)[None]
    scale = np.abs(refv).max()
    np.testing.assert_allclose(np.asarray(yr), refv.real, atol=4e-4 * scale)
    np.testing.assert_allclose(np.asarray(yi), refv.imag, atol=4e-4 * scale)


@pytest.mark.parametrize(
    "n,b,n2",
    [(4096, 13, None), (2048, 3, None), (2029, 11, None), (8192, 1, None),
     (8192, 13, None), (2048, 21, None), (128, 3, 4096)],
)
def test_four_step_ragged_and_short_tiles(n, b, n2, rng):
    """Row-group sweep of the four-step: a tile of fewer rows than a group
    (zero-padded) and a ragged tail (the last full group recomputed, only
    the tail written) — also in place, through the Bluestein fwd stage and
    (``n2``) through a 2-D program's in-place column pass of length n2."""
    if n2 is None:
        xr, xi = _rand(rng, (b, n))
        p = F.plan(F.FFTSpec(n=n), backend="pallas")
    else:
        xr, xi = _rand(rng, (b, n2, n))
        p = F.plan(F.FFTSpec(n=n, n2=n2, kind="fft2"), backend="pallas")
    yr, yi = p.apply_planes(jnp.asarray(xr), jnp.asarray(xi))
    refv = np.fft.fftn(xr + 1j * xi, axes=(-1,) if n2 is None else (-2, -1))
    np.testing.assert_allclose(
        np.asarray(yr) + 1j * np.asarray(yi), refv, atol=3e-4 * np.abs(refv).max()
    )


@pytest.mark.parametrize("m", [64, 200, 256, 300])
@pytest.mark.parametrize("b", [1, 3])
def test_recomb_kernels_match_xla_glue(m, b, rng):
    """The lane-tiled recomb passes (any m ≥ 128, incl. widths that are not
    a whole number of 128-lane blocks) and the whole-row one (m < 128)
    agree with the traced-XLA recombination, forward and inverse."""
    from repro.core import fft_xla
    from repro.kernels import pencil

    zr, zi = _rand(rng, (b, m))
    wr, wi = tw.rfft_recomb_twiddle(2 * m)
    got = pencil.rfft_recomb_call(jnp.asarray(zr), jnp.asarray(zi), wr, wi, interpret=True)
    want = fft_xla.rfft_recomb(zr, zi, wr, wi)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)
    xr, xi = _rand(rng, (b, m + 1))
    vr, vi = tw.rfft_recomb_twiddle(2 * m, inverse=True)
    got = pencil.irfft_recomb_call(jnp.asarray(xr), jnp.asarray(xi), vr, vi, interpret=True)
    want = fft_xla.irfft_recomb(xr, xi, vr, vi)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


def test_inverse_scaling_folded(rng):
    """ifft(fft(x)) == x exactly through the kernel path (scaled LUTs)."""
    xr, xi = _rand(rng, (2, 4096))
    yr, yi = ops.fft(jnp.asarray(xr), jnp.asarray(xi), interpret=True)
    zr, zi = ops.ifft(yr, yi, interpret=True)
    np.testing.assert_allclose(np.asarray(zr), xr, atol=2e-4)
    np.testing.assert_allclose(np.asarray(zi), xi, atol=2e-4)
