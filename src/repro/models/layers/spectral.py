"""Spectral mixer — the paper's FFT as an LM layer (Hyena-style long conv).

Token mixing by causal convolution with a learned per-channel global filter,
computed as rfft → pointwise → irfft through :mod:`repro.core` — i.e. every
transform uses the paper's memory-optimized plan (fused Pallas kernels on
TPU, four-step XLA elsewhere).  A multiplicative gate keeps it competitive
as a drop-in replacement for attention in the ablation configs.

Decode has two exactly-equivalent state layouts (``cfg.spectral_decode_mode``):

* ``"stream"`` (default) — the serving path.  The cache carries the
  overlap-save tail (:class:`repro.core.overlap.StreamingConv`'s state) plus
  a chunk accumulator and a precomputed *lookahead*: the history-only half
  of the next ``C`` outputs, refreshed once per ``C`` tokens by ONE cached
  block-plan conv (:func:`repro.core.overlap.stream_lookahead`).  Per token
  the layer only adds the direct head — taps ``j ≤ phase`` against the
  accumulating chunk, an O(C·D) dot — so FFT cost is amortized to
  ``O(block·log block / C)`` per token and every transform stays on the
  plan prefill already cached.
* ``"ring"`` — a ring buffer of the last ``Lf`` inputs and the O(Lf·D)
  direct dot per token; the exactness oracle the stream path is tested
  against.

Prefill routes through :func:`repro.core.conv.fft_conv`, which auto-routes
to overlap-save (``fft_conv_os``) whenever the one-shot padded length would
leave the fused regime — long prompts never plan past ``FUSED_MAX``.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import overlap as ov_lib
from repro.core.conv import fft_conv
from repro.core.limits import next_pow2
from repro.sharding.logical import ann
from repro.utils.params import Param, normal

__all__ = [
    "spectral_init",
    "spectral_forward",
    "spectral_decode",
    "spectral_stream_decode",
    "spectral_stream_rephase",
    "init_spectral_cache",
    "init_spectral_stream_cache",
    "stream_grain",
    "stream_plan_info",
    "SpectralCache",
    "SpectralStreamCache",
]


class SpectralCache(NamedTuple):
    buf: jax.Array  # (B, Lf, D) ring buffer of recent inputs
    t: jax.Array    # scalar step counter (for ring indexing)


class SpectralStreamCache(NamedTuple):
    """StreamingConv-carried decode state (amortized FFT serving path).

    The decode window boundary ``B0`` is the stream position where the
    current lookahead was computed; ``phase`` counts decode *steps* since
    (global across batch slots, so batched decode flushes in lockstep under
    one jitted scan — per-slot timelines live in the attention caches).

    hist:   (B, D, Lf−1+C) float32 — the last ``Lf−1+C`` mixer inputs
            before ``B0``.  Only the trailing ``Lf−1`` (the overlap-save
            tail) feed flushes; the extra leading ``C`` slots carry enough
            history that a freshly-prefilled request can be re-phased into
            a running batch at ANY global phase
            (:func:`spectral_stream_rephase`).
    chunk:  (B, D, C) float32 — inputs accumulated since ``B0``
            (slots ``[0, phase)`` live, the rest zero).
    future: (B, D, C) float32 — history-only contribution to outputs
            ``B0 … B0+C−1`` (filter taps ``j > i`` for entry ``i``),
            computed once per window by one cached block-plan conv.
    phase:  () int32 in ``[0, C)`` — next chunk slot to fill.
    """

    hist: jax.Array
    chunk: jax.Array
    future: jax.Array
    phase: jax.Array


def stream_grain(cfg) -> Tuple[int, int]:
    """(chunk C, flush block) for the streaming decode state.

    ``C`` balances the per-token direct head (O(C·D)) against the amortized
    flush (O(block·log block·D / C) per token): ``max(8, next_pow2(Lf)/4)``
    keeps both well under the ring path's O(Lf·D) for Lf ≥ 64 and is
    overridable via ``cfg.spectral_decode_chunk``.  The block is the
    smallest power of two covering one flush input (tail + chunk =
    ``Lf−1+C`` samples), so every flush is a SINGLE frame through one
    cached rfft/irfft plan pair.
    """
    lf = cfg.spectral_filter_len
    c = cfg.spectral_decode_chunk or max(8, next_pow2(lf) // 4)
    block = next_pow2(max(lf - 1 + c, 2))
    return c, block


def spectral_init(key, cfg, dtype) -> dict:
    D, Lf = cfg.d_model, cfg.spectral_filter_len
    ks = jax.random.split(key, 4)
    # Smooth decaying filter init: h[d, j] ~ N(0, 1/Lf) · exp(-j/τ_d).
    j = np.arange(Lf, dtype=np.float32)
    tau = np.logspace(1.0, np.log10(Lf), D, dtype=np.float32)
    envelope = np.exp(-j[None, :] / tau[:, None])  # (D, Lf)
    base = jax.random.normal(ks[0], (D, Lf), jnp.float32) * (Lf**-0.5)
    return {
        "filt": Param((base * envelope).astype(jnp.float32), ("embed", "filter")),
        "w_gate": normal(ks[1], (D, D), ("embed", "ff"), dtype=dtype),
        "w_in": normal(ks[2], (D, D), ("embed", "ff"), dtype=dtype),
        "w_out": normal(ks[3], (D, D), ("ff", "embed"), dtype=dtype),
    }


def _stream_state_from_u(u32: jax.Array, filt: jax.Array, cfg) -> SpectralStreamCache:
    """Build the streaming decode state after a prefill of ``u32`` (B,S,D)
    float32 mixer inputs: window boundary at position S, empty chunk, and
    the lookahead for the next C outputs through the cached block plan."""
    b, s, d = u32.shape
    lf = cfg.spectral_filter_len
    c, block = stream_grain(cfg)
    cap = lf - 1 + c
    uT = jnp.moveaxis(u32, 1, 2)  # (B, D, S)
    pos = np.arange(s - cap, s)   # static: prompt shorter than cap → zeros
    hist = uT[..., np.clip(pos, 0, s - 1)] * (pos >= 0)
    Hr, Hi = ov_lib.filter_spectrum(filt, block)
    future = ov_lib.stream_lookahead(hist[..., c:], Hr, Hi, window=c, block=block)
    return SpectralStreamCache(
        hist=hist,
        chunk=jnp.zeros((b, d, c), jnp.float32),
        future=future,
        phase=jnp.asarray(0, jnp.int32),
    )


def spectral_forward(params, x, *, cfg, return_cache: bool = False):
    """x: (B, S, D) → (B, S, D) via gated FFT long convolution."""
    b, s, d = x.shape
    cd = x.dtype
    u = jnp.einsum("bsd,de->bse", x, params["w_in"].astype(cd))
    g = jax.nn.silu(jnp.einsum("bsd,de->bse", x, params["w_gate"].astype(cd)))
    # axis-aware planned conv over the sequence axis; per-channel filters
    # broadcast once the conv axis is moved last inside fft_conv.  fft_conv
    # auto-routes to overlap-save past the fused regime, so prefill never
    # plans a transform larger than FUSED_MAX.
    y = fft_conv(u.astype(jnp.float32), params["filt"], axis=1)  # (B, S, D)
    y = y.astype(cd) * g
    out = jnp.einsum("bse,ed->bsd", y, params["w_out"].astype(cd))
    out = ann(out, "batch", "seq", "embed")
    if return_cache:
        if getattr(cfg, "spectral_decode_mode", "stream") == "ring":
            lf = cfg.spectral_filter_len
            keep = min(lf, s)
            pos = jnp.arange(s - keep, s)
            buf = jnp.zeros((b, lf, d), jnp.float32)
            # ring layout: buf[p % lf] = u[position p] (decode's convention).
            buf = buf.at[:, pos % lf, :].set(u.astype(jnp.float32)[:, s - keep :, :])
            return out, SpectralCache(buf=buf, t=jnp.asarray(s, jnp.int32))
        return out, _stream_state_from_u(
            u.astype(jnp.float32), params["filt"], cfg
        )
    return out


def init_spectral_cache(cfg, batch, dtype=jnp.float32) -> SpectralCache:
    return SpectralCache(
        buf=jnp.zeros((batch, cfg.spectral_filter_len, cfg.d_model), jnp.float32),
        t=jnp.asarray(0, jnp.int32),
    )


def init_spectral_stream_cache(cfg, batch, dtype=jnp.float32) -> SpectralStreamCache:
    d = cfg.d_model
    c, _ = stream_grain(cfg)
    cap = cfg.spectral_filter_len - 1 + c
    return SpectralStreamCache(
        hist=jnp.zeros((batch, d, cap), jnp.float32),
        chunk=jnp.zeros((batch, d, c), jnp.float32),
        future=jnp.zeros((batch, d, c), jnp.float32),
        phase=jnp.asarray(0, jnp.int32),
    )


def spectral_decode(params, x, cache: SpectralCache, *, cfg) -> Tuple[jax.Array, SpectralCache]:
    """One token.  Direct dot with the filter over the ring buffer."""
    b, _, d = x.shape
    lf = cfg.spectral_filter_len
    cd = x.dtype
    u = jnp.einsum("bsd,de->bse", x, params["w_in"].astype(cd))[:, 0]  # (B,D)
    g = jax.nn.silu(jnp.einsum("bsd,de->bse", x, params["w_gate"].astype(cd)))[:, 0]
    slot = cache.t % lf
    buf = jax.lax.dynamic_update_slice_in_dim(
        cache.buf, u.astype(jnp.float32)[:, None, :], slot, axis=1
    )
    # Filter tap j multiplies input from j steps ago = slot - j (mod Lf).
    ages = (slot - jnp.arange(lf)) % lf  # index of the input j steps back
    hist = jnp.take(buf, ages, axis=1)  # (B, Lf, D) newest-first
    valid = jnp.arange(lf) <= jnp.minimum(cache.t, lf - 1)
    hist = hist * valid[None, :, None]
    y = jnp.einsum("blD,Dl->bD", hist, params["filt"])  # Σ_j h[d,j]·u[t-j,d]
    y = (y.astype(cd) * g)[:, None, :]
    out = jnp.einsum("bse,ed->bsd", y, params["w_out"].astype(cd))
    return out, SpectralCache(buf=buf, t=cache.t + 1)


def _head_taps(filt: jax.Array, c: int) -> jax.Array:
    """Filter taps 0..C−1 as (D, C): the direct-head coefficients (taps
    past the filter length are zero)."""
    lf = filt.shape[-1]
    if lf >= c:
        return filt[..., :c]
    return jnp.pad(filt, [(0, 0)] * (filt.ndim - 1) + [(0, c - lf)])


def spectral_stream_decode(
    params, x, cache: SpectralStreamCache, *, cfg
) -> Tuple[jax.Array, SpectralStreamCache]:
    """One token through the StreamingConv-carried state.

    Output = ``future[phase]`` (history half, precomputed at the last
    flush) + the direct head Σ_{j≤phase} h[j]·chunk[phase−j] — together
    exactly Σ_j h[j]·u[t−j], the ring path's answer.  When the chunk fills
    (``phase == C−1``) the window advances: the tail shifts by C and one
    :func:`repro.core.overlap.stream_lookahead` through the cached block
    plan precomputes the next window's history half.
    """
    b, _, d = x.shape
    cd = x.dtype
    c, block = stream_grain(cfg)
    filt = params["filt"]
    u = jnp.einsum("bsd,de->bse", x, params["w_in"].astype(cd))[:, 0]  # (B,D)
    g = jax.nn.silu(jnp.einsum("bsd,de->bse", x, params["w_gate"].astype(cd)))[:, 0]
    i = cache.phase
    chunk = jax.lax.dynamic_update_slice_in_dim(
        cache.chunk, u.astype(jnp.float32)[..., None], i, axis=2
    )
    # Direct head: slot (i−j) mod C holds u[t−j] for j ≤ i; later slots are
    # zero (flush/insert clears them) — the mask is cheap insurance.
    ages = (i - jnp.arange(c)) % c
    recent = jnp.take(chunk, ages, axis=2) * (jnp.arange(c) <= i)  # (B,D,C)
    y = jnp.einsum("bdc,dc->bd", recent, _head_taps(filt, c))
    y = y + jnp.take(cache.future, i, axis=-1)
    y = (y.astype(cd) * g)[:, None, :]
    out = jnp.einsum("bse,ed->bsd", y, params["w_out"].astype(cd))

    def _flush(args):
        hist, chunk = args
        hist2 = jnp.concatenate([hist[..., c:], chunk], axis=-1)
        Hr, Hi = ov_lib.filter_spectrum(filt, block)
        fut2 = ov_lib.stream_lookahead(
            hist2[..., c:], Hr, Hi, window=c, block=block
        )
        return hist2, jnp.zeros_like(chunk), fut2, jnp.asarray(0, jnp.int32)

    def _advance(args):
        hist, chunk = args
        return hist, chunk, cache.future, i + 1

    hist2, chunk2, fut2, phase2 = jax.lax.cond(
        i == c - 1, _flush, _advance, (cache.hist, chunk)
    )
    return out, SpectralStreamCache(
        hist=hist2, chunk=chunk2, future=fut2, phase=phase2
    )


def spectral_stream_rephase(
    filt: jax.Array, cache: SpectralStreamCache, phase, *, cfg
) -> SpectralStreamCache:
    """Re-align a freshly-prefilled stream cache (phase 0, boundary at its
    own prompt end S) to a running batch's global ``phase`` f ∈ [0, C).

    The joined slot's window boundary moves back to ``S − f``: its last
    ``f`` prompt inputs become live chunk slots ``[0, f)`` and the tail is
    re-cut at the new boundary (the extra ``C`` history slots in ``hist``
    exist exactly so this slice is always available).  One lookahead conv
    rebuilds ``future`` for the shifted window; leading ``hist`` slots the
    shift exposes are zeroed — they are only ever dropped by later flushes.
    All ops address the trailing axis, so this maps over stacked
    (repeats-leading) caches unchanged.
    """
    lf = cfg.spectral_filter_len
    c, block = stream_grain(cfg)
    cap = lf - 1 + c
    f = jnp.asarray(phase, jnp.int32)
    lead = cache.hist.shape[:-1]
    histp = jnp.pad(
        cache.hist, [(0, 0)] * (cache.hist.ndim - 1) + [(0, c)]
    )  # index m ↦ u[S − cap + m], zeros for m ≥ cap
    tail = jax.lax.dynamic_slice_in_dim(histp, c - f, lf - 1, axis=-1)
    chunk = jax.lax.dynamic_slice_in_dim(histp, cap - f, c, axis=-1)
    chunk = chunk * (jnp.arange(c) < f)
    hist = jnp.concatenate(
        [jnp.zeros((*lead, c), jnp.float32), tail], axis=-1
    )
    Hr, Hi = ov_lib.filter_spectrum(filt, block)
    future = ov_lib.stream_lookahead(tail, Hr, Hi, window=c, block=block)
    return SpectralStreamCache(hist=hist, chunk=chunk, future=future, phase=f)


def stream_plan_info(cfg, batch: int = 1) -> dict:
    """Streaming-conv plan metadata for artifacts (dry-run decode cells):
    the decode grain, the flush plan's schedule, and the modeled HBM bytes
    of one flush at that grain."""
    from repro.analysis import roofline as rl
    from repro.core import plan as plan_lib

    lf = cfg.spectral_filter_len
    c, block = stream_grain(cfg)
    report = rl.conv_report(lf - 1 + c, lf, batch=batch, block=block, stream=True)
    return {
        "filter_len": lf,
        "chunk": c,
        "block": block,
        "flushes_per_token": 1.0 / c,
        "flush_schedule": plan_lib.describe(block),
        "flush_hbm_bytes": report["overlap_save"]["hbm_bytes"],
    }
