"""Entry ``fft_rows``: a planned 1-D complex transform over every range line
of a batch of scenes.

One call is ``plan(FFTSpec(n=range_samples))`` applied to
``(batch · azimuth_lines, range_samples)`` complex64 lines.  ``compare``
lines of the last call, drawn from the seed, one from each equal part of
the batch, are compared with ``numpy.fft.fft`` in float64.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench.lib import reference as R

#: Lines per block of the threaded float64 reference.
REF_BLOCK = 512


def _shape(cfg) -> tuple[int, int]:
    return cfg["batch"] * cfg["azimuth_lines"], cfg["range_samples"]


def samples(cfg, traffic) -> int:
    """Complex input points of one call."""
    rows, n = _shape(cfg)
    return rows * n


def essential(cfg, traffic) -> dict:
    """One read of the input and one write of the output (complex64), and
    5·n·log2 n flops per line."""
    rows, n = _shape(cfg)
    return {"bytes": 2 * 8 * rows * n, "flops": 5.0 * rows * n * math.log2(n)}


def build(jax, cfg, traffic, seed, devices) -> dict:
    from repro.core import fft as F

    rows, n = _shape(cfg)
    planned = F.plan(F.FFTSpec(n=n))
    x = jax.device_put(R.complex_input(jax, (rows, n), seed), devices[0])
    return {"fn": jax.jit(planned), "args": (x,), "plans": [planned], "note": {}}


def picks(cfg, traffic, seed) -> np.ndarray:
    """``compare`` lines drawn from the seed, one from each equal part of
    the batch."""
    return R.stratified(_shape(cfg)[0], traffic["compare"], seed)


def answers(out, args, sel) -> tuple:
    return R.host_complex(out[sel]), R.host_complex(args[0][sel])


def reference(host_in, cfg, traffic) -> np.ndarray:
    blocks = [host_in[i : i + REF_BLOCK] for i in range(0, len(host_in), REF_BLOCK)]
    fft = lambda b: np.fft.fft(b.astype(np.complex128), axis=-1)  # noqa: E731
    return np.concatenate(R.threaded(fft, blocks))


def control(jax, args, sel, cfg, traffic) -> np.ndarray:
    """The transform in three bfloat16 passes, in the program's place."""
    import jax.numpy as jnp

    tabs = jax.device_put(R.tables(_shape(cfg)[1]), args[0].sharding)

    def run(x, tabs):
        return R.fft_high(jnp.real(x), jnp.imag(x), tabs)

    yr, yi = jax.jit(run)(args[0][sel], tabs)
    return np.asarray(yr) + 1j * np.asarray(yi)
