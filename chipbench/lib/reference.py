"""Seeded inputs, float64 references, the comparison, and the control.

Nothing here imports the code under test.  The inputs are made on the
device from the run's seed; the reference is ``numpy.fft`` in float64 on
the host; the comparison is the widest gap between the produced answer and
the reference, over the reference's largest magnitude.

The control stands in for the program at the next precision below the one
the configurations state.  The program's GEMMs run at
``Precision.HIGHEST`` (float32); the control is a matmul FFT whose GEMMs
run in ``high``, three bfloat16 passes (the ``bf16_3x`` algorithm, written
out so that it computes the same on any backend).  A limit that the
control does not fail would also pass a program that quietly dropped to
that precision.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
import math

import numpy as np

#: Host threads for the float64 reference (numpy's FFT releases the GIL).
REF_THREADS = 8


def seed32(seed: int) -> int:
    """A 32-bit key for ``jax.random`` from a seed of any size."""
    ss = np.random.SeedSequence(int(seed) % 2**64)
    return int(ss.generate_state(1, np.uint32)[0])


def complex_input(jax, shape, seed, sharding=None):
    """Standard normal complex64 of ``shape``, made on the device in one
    jitted call.  The key is an argument, not a constant of the program, so
    every seed runs the same compiled program."""
    import jax.numpy as jnp

    def make(key):
        kr, ki = jax.random.split(key)
        return (jax.random.normal(kr, shape) + 1j * jax.random.normal(ki, shape)).astype(
            jnp.complex64
        )

    return jax.jit(make, out_shardings=sharding)(jax.random.key(seed32(seed)))


def real_input(jax, shape, seed, salt=0):
    """Standard normal float32 of ``shape``, made on the device."""
    make = lambda key: jax.random.normal(jax.random.fold_in(key, salt), shape)  # noqa: E731
    return jax.jit(make)(jax.random.key(seed32(seed)))


def host_complex(x) -> np.ndarray:
    """A complex64 device array on the host as complex128.  It leaves the
    chip as its two float32 planes, which copy many times faster than
    complex64 itself."""
    import jax.numpy as jnp

    return np.asarray(jnp.real(x)).astype(np.float64) + 1j * np.asarray(jnp.imag(x))


def stratified(batch: int, k: int, seed: int) -> np.ndarray:
    """``k`` indices of ``range(batch)`` drawn from ``seed``, one from each
    of ``k`` equal strata, so that every part of the batch is compared."""
    if k >= batch:
        return np.arange(batch)
    rng = np.random.default_rng(int(seed) % 2**64)
    edges = np.linspace(0, batch, k + 1).astype(int)
    return np.array([rng.integers(a, b) for a, b in zip(edges[:-1], edges[1:])])


def threaded(fn, items):
    """``[fn(i) for i in items]`` on the reference's host threads."""
    with ThreadPoolExecutor(REF_THREADS) as ex:
        return list(ex.map(fn, items))


def max_err_rel(got: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap between ``got`` and ``ref`` over ``max|ref|``."""
    return float(np.abs(got - ref).max() / np.abs(ref).max())


# ---------------------------------------------------------------------------
# The control: a matmul FFT in three bfloat16 passes
# ---------------------------------------------------------------------------

#: Largest transform the control computes as one DFT matrix product.
DIRECT = 1024


def _split_sizes(n: int) -> tuple[int, int]:
    n1 = 1 << (int(math.log2(n)) // 2)
    return n1, n // n1


def tables(n: int) -> dict:
    """Host float32 planes of the DFT matrices and four-step twiddles a
    forward transform of length ``n`` (a power of two) needs, computed in
    float64."""
    out: dict = {}

    def add(m):
        if m <= DIRECT:
            k = np.arange(m)
            ang = -2 * np.pi * ((k[:, None] * k[None, :]) % m) / m
            out[f"dft{m}"] = (np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32))
            return
        m1, m2 = _split_sizes(m)
        j2 = np.arange(m2)[:, None]
        k1 = np.arange(m1)[None, :]
        ang = -2 * np.pi * ((j2 * k1) % m) / m
        out[f"tw{m}"] = (np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32))
        add(m1)
        add(m2)

    add(n)
    return out


def _mm_high(a, b):
    """``a @ b`` for float32 operands in three bfloat16 passes (bf16_3x):
    hi·hi + hi·lo + lo·hi, accumulated in float32.  The split is made with
    ``reduce_precision``, which XLA keeps: a float32 → bfloat16 → float32
    round trip of ``convert``s it may fold away as excess precision."""
    import jax
    import jax.numpy as jnp

    bf, f32 = jnp.bfloat16, jnp.float32
    rp = lambda v: jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)  # noqa: E731
    ah, bh = rp(a), rp(b)
    al, bl = rp(a - ah), rp(b - bh)
    dot = lambda p, q: jnp.matmul(p.astype(bf), q.astype(bf), preferred_element_type=f32)  # noqa: E731
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def fft_high(xr, xi, tabs: dict):
    """Forward FFT along the last axis of the planes ``(xr, xi)`` with
    every GEMM in three bfloat16 passes; traceable under ``jax.jit``.
    ``tabs`` holds :func:`tables` of the length, as device arrays."""
    import jax.numpy as jnp

    n = xr.shape[-1]
    if n <= DIRECT:
        wr, wi = tabs[f"dft{n}"]
        return (
            _mm_high(xr, wr) - _mm_high(xi, wi),
            _mm_high(xr, wi) + _mm_high(xi, wr),
        )
    n1, n2 = _split_sizes(n)
    lead = xr.shape[:-1]
    # x[n2·j1 + j2] → a[j2, j1]; transform over j1 → b[j2, k1]
    sw = lambda a: jnp.swapaxes(a.reshape(*lead, n1, n2), -1, -2)  # noqa: E731
    br, bi = fft_high(sw(xr), sw(xi), tabs)
    tr, ti = tabs[f"tw{n}"]
    br, bi = br * tr - bi * ti, br * ti + bi * tr
    # transform over j2 → c[k1, k2]; X[k1 + n1·k2] = c[k1, k2]
    cr, ci = fft_high(jnp.swapaxes(br, -1, -2), jnp.swapaxes(bi, -1, -2), tabs)
    flat = lambda c: jnp.swapaxes(c, -1, -2).reshape(*lead, n)  # noqa: E731
    return flat(cr), flat(ci)


def ifft_high(xr, xi, tabs: dict):
    """Inverse of :func:`fft_high`: conj(fft(conj(X))) / n."""
    n = xr.shape[-1]
    yr, yi = fft_high(xr, -xi, tabs)
    return yr / n, -yi / n
