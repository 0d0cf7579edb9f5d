"""Entry ``fft_conv_os``: overlap-save convolution of real channels with one
long real filter.

One call is ``overlap.fft_conv_os(x, h)`` on ``(channels, signal_len)``
float32 signals and a ``taps``-tap float32 filter, both made from the
seed; the block is the one the default tuner picks (recorded in the run's
note).  The call frames the signal, transforms the frames and the filter,
multiplies the spectra, transforms back and keeps the valid tails.
``compare`` channels of the last call, drawn from the seed, are compared
with a float64 ``numpy.fft`` convolution at one transform length covering
signal and filter.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench.lib import reference as R


def _sizes(cfg) -> tuple[int, int, int]:
    return cfg["channels"], cfg["signal_len"], cfg["taps"]


def _full_len(L: int, taps: int) -> int:
    return 1 << (L + taps - 2).bit_length()


def samples(cfg, traffic) -> int:
    """Real input samples of one call."""
    c, L, _ = _sizes(cfg)
    return c * L


def essential(cfg, traffic) -> dict:
    """One read of signal and filter and one write of the output (float32);
    per channel a real forward and a real inverse transform of the full
    length N (2.5·N·log2 N each) and the complex spectrum product (6 flops
    per bin)."""
    c, L, taps = _sizes(cfg)
    N = _full_len(L, taps)
    per_channel = 2 * 2.5 * N * math.log2(N) + 6.0 * (N // 2 + 1)
    return {"bytes": 4 * (2 * c * L + taps), "flops": c * per_channel}


def _filter(jax, taps, seed):
    return R.real_input(jax, (taps,), seed, salt=1) / np.float32(math.sqrt(taps))


def build(jax, cfg, traffic, seed, devices) -> dict:
    from repro.core import fft as F
    from repro.core import overlap, tuning

    c, L, taps = _sizes(cfg)
    x = jax.device_put(R.real_input(jax, (c, L), seed), devices[0])
    h = jax.device_put(_filter(jax, taps, seed), devices[0])
    block = tuning.tuned_block(L, taps, c)
    F.clear_plan_log()
    fn = jax.jit(lambda x, h: overlap.fft_conv_os(x, h))
    jax.eval_shape(fn, x, h)  # plans the block's rfft/irfft pair
    plans = [F.plan(s) for s in {s for s, _ in F.plan_log() if s.kind in ("rfft", "irfft")}]
    step = block - (taps - 1)
    note = {"block": block, "frames_per_channel": -(-L // step)}
    return {"fn": fn, "args": (x, h), "plans": plans, "note": note}


def picks(cfg, traffic, seed) -> np.ndarray:
    """``compare`` channels drawn from the seed, one from each equal part of
    the batch."""
    return R.stratified(cfg["channels"], traffic["compare"], seed)


def answers(out, args, sel) -> tuple:
    got = np.asarray(out[sel]).astype(np.float64)
    return got, (np.asarray(args[0][sel]), np.asarray(args[1]))


def reference(host_in, cfg, traffic) -> np.ndarray:
    x, h = host_in
    L, taps = x.shape[-1], h.shape[-1]
    N = _full_len(L, taps)
    H = np.fft.rfft(h.astype(np.float64), N)

    def conv(xs):
        X = np.fft.rfft(xs.astype(np.float64), N, axis=-1)
        return np.fft.irfft(X * H, N, axis=-1)[..., :L]

    return np.concatenate(R.threaded(conv, [x[i : i + 1] for i in range(len(x))]))


def control(jax, args, sel, cfg, traffic) -> np.ndarray:
    """The same convolution at the full length N, every GEMM in three
    bfloat16 passes, in the program's place."""
    import jax.numpy as jnp

    _, L, taps = _sizes(cfg)
    N = _full_len(L, taps)
    tabs = jax.device_put(R.tables(N), args[0].sharding)

    def run(x, h, tabs):
        pad = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, N - a.shape[-1])])  # noqa: E731
        xr, xi = R.fft_high(pad(x), jnp.zeros_like(pad(x)), tabs)
        hr, hi = R.fft_high(pad(h[None]), jnp.zeros_like(pad(h[None])), tabs)
        yr, _ = R.ifft_high(xr * hr - xi * hi, xr * hi + xi * hr, tabs)
        return yr[..., :L]

    return np.asarray(jax.jit(run)(args[0][sel], args[1], tabs)).astype(np.float64)
