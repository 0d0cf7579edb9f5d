"""Entry ``fft2``: the planned 2-D transform of a batch of complex scenes.

One call is ``plan(FFTSpec(n=range_samples, kind="fft2",
n2=azimuth_lines))`` applied to ``(batch, azimuth_lines, range_samples)``
complex64 scenes: a row pass over the range lines, then the strided
column pass over the azimuth lines.  ``compare`` scenes of the last
call, drawn from the seed, one from each equal part of the batch, are
compared, whole, with ``numpy.fft.fft2`` in float64.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench.lib import reference as R


def _shape(cfg) -> tuple[int, int, int]:
    return cfg["batch"], cfg["azimuth_lines"], cfg["range_samples"]


def samples(cfg, traffic) -> int:
    """Complex input points of one call."""
    b, n1, n2 = _shape(cfg)
    return b * n1 * n2


def essential(cfg, traffic) -> dict:
    """One read of the input and one write of the output (complex64), and
    5·N·log2 N flops per N-point complex transform."""
    b, n1, n2 = _shape(cfg)
    n = n1 * n2
    return {"bytes": 2 * 8 * b * n, "flops": 5.0 * b * n * math.log2(n)}


def build(jax, cfg, traffic, seed, devices) -> dict:
    from repro.core import fft as F

    b, n1, n2 = _shape(cfg)
    planned = F.plan(F.FFTSpec(n=n2, kind="fft2", n2=n1))
    x = jax.device_put(R.complex_input(jax, (b, n1, n2), seed), devices[0])
    return {"fn": jax.jit(planned), "args": (x,), "plans": [planned], "note": {}}


def picks(cfg, traffic, seed) -> np.ndarray:
    """``compare`` whole scenes drawn from the seed, one from each equal
    part of the batch."""
    return R.stratified(cfg["batch"], traffic["compare"], seed)


def answers(out, args, sel) -> tuple:
    """The produced scenes and the inputs the reference needs, on the host."""
    return R.host_complex(out[sel]), R.host_complex(args[0][sel])


def reference(host_in, cfg, traffic) -> np.ndarray:
    return np.stack(R.threaded(lambda s: np.fft.fft2(s.astype(np.complex128)), host_in))


def control(jax, args, sel, cfg, traffic) -> np.ndarray:
    """The transform in three bfloat16 passes, in the program's place."""
    import jax.numpy as jnp

    _, n1, n2 = _shape(cfg)
    tabs = {**R.tables(n1), **R.tables(n2)}
    tabs = jax.device_put(tabs, args[0].sharding)

    def run(x, tabs):
        yr, yi = R.fft_high(jnp.real(x), jnp.imag(x), tabs)
        yr, yi = R.fft_high(jnp.swapaxes(yr, -1, -2), jnp.swapaxes(yi, -1, -2), tabs)
        return jnp.swapaxes(yr, -1, -2), jnp.swapaxes(yi, -1, -2)

    out = []
    for s in sel:
        yr, yi = jax.jit(run)(args[0][s], tabs)
        out.append(np.asarray(yr) + 1j * np.asarray(yi))
    return np.stack(out)
