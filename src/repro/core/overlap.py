"""Overlap-save streaming convolution — long signals through small plans.

The paper's whole point (§2.3.2, §3) is bounding global-memory round trips
by keeping each transform inside the fast tier, yet a one-shot ``fft_conv``
does the opposite for long signals: a 1M-sample signal with a 4k-tap filter
pads to ONE length-2²⁰ transform and plans a split-regime program.  Adámek
et al. ("GPU Fast Convolution via the Overlap-and-Save Method in Shared
Memory", PAPERS.md) show the alternative this module implements:

* **block** the signal into overlapping segments sized to the fast-memory
  tier — ``B = next_pow2(Lh)·OS_FACTOR``, capped at the fused-kernel regime
  (:data:`repro.core.plan.FUSED_MAX`), so every transform is a single
  HBM round trip;
* transform the blocks **two at a time as one complex signal**: with a
  real filter, ``conv(a + i·b, h) = conv(a, h) + i·conv(b, h)``, so two
  adjacent real frames of one channel go into the real and imaginary
  planes of ONE cached complex ``fft``/``ifft`` plan pair of length B,
  batched over all pairs and multiplied by the filter's full B-bin
  spectrum (its half spectrum computed once, extended by Hermitian
  symmetry and broadcast) — exactly the shape the pallas pass programs
  are fastest at: big batch × fused-regime N, with no real-FFT even/odd
  packing or Hermitian recombination around the frames.  A
  single frame per signal (a streaming chunk), or a block past
  ``FUSED_MAX``, keeps one cached rfft/irfft plan pair instead
  (:func:`pairs_apply` decides from the shapes);
* scatter each block's valid tail (the ``B − (Lh−1)`` samples whose history
  is fully inside the block) back into the output.

On top of the one-shot :func:`fft_conv_os`:

* :class:`StreamingConv` carries the ``Lh − 1`` overlap tail as **explicit
  state**, so chunked calls (serving decode, SAR strip ingest) compose to
  the one-shot result bit-for-bit at tolerance — including ragged final
  chunks and chunks shorter than the filter;
* ``repro.core.distributed.pconv_os_sharded`` shards the blocks over a mesh
  axis with ``shard_map`` — blocks are embarrassingly parallel, so the
  distributed convolution pays **zero** all-to-alls versus the 4 of the
  ``pfft``-based pencil path;
* ``repro.core.conv.fft_conv`` auto-routes here whenever the one-shot
  padded length would leave the fused regime.

``analysis.roofline.conv_report`` models the HBM traffic and device time
of both schedules so the win is observable, not just asserted; the block
tuner ranks blocks by that time.
"""

from __future__ import annotations

import collections
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import faults
from repro.core import fft as fft_lib
from repro.core import plan as plan_lib
from repro.core.fft_xla import cmul
from repro.core.limits import OS_FACTOR, next_pow2

Planes = Tuple[jax.Array, jax.Array]

__all__ = [
    "OS_FACTOR",
    "pick_block",
    "pairs_apply",
    "pairing_log",
    "clear_pairing_log",
    "describe_conv",
    "frame_signal",
    "frame_pairs",
    "filter_spectrum",
    "conv_frames",
    "overlap_save",
    "fft_conv_os",
    "stream_lookahead",
    "StreamingConv",
]

# OS_FACTOR (the fixed block-size heuristic the autotuner searches past)
# lives in repro.core.limits with the other regime thresholds; re-exported
# here because this engine is where callers historically imported it from.


def pick_block(filter_len: int, block: Optional[int] = None) -> int:
    """FIXED-heuristic overlap-save block size for a ``filter_len``-tap
    filter (the tuner's baseline; :func:`_resolve_block` searches past it).

    Default: ``next_pow2(filter_len) · OS_FACTOR``, capped at
    :data:`~repro.core.plan.FUSED_MAX` so no planned transform leaves the
    one-round-trip regime; for filters too long for that cap to leave room
    (``next_pow2(filter_len) > FUSED_MAX/2``) the block grows to twice the
    filter's padded length instead — correctness over the cap.  ``block``
    overrides (power of two, > filter_len − 1 so each block produces at
    least one valid sample).
    """
    if filter_len < 1:
        raise faults.PlanError(f"filter must have at least one tap, got {filter_len}")
    p = next_pow2(filter_len)
    if block is not None:
        if block <= 0 or block & (block - 1):
            raise faults.PlanError(f"block must be a power of two, got {block}")
        if block <= filter_len - 1:
            raise faults.PlanError(
                f"block={block} leaves no valid samples for a "
                f"{filter_len}-tap filter (needs block > {filter_len - 1})"
            )
        return block
    return max(min(p * OS_FACTOR, plan_lib.FUSED_MAX), 2 * p, 2)


def _resolve_block(
    filter_len: int,
    block: Optional[int],
    L: int,
    batch: int,
    backend: Optional[str],
    tune: Optional[str],
    chunk: Optional[int] = None,
) -> int:
    """The block an overlap-save call actually uses: an explicit ``block``
    is validated and wins; otherwise the autotuner decides (``tune="off"``
    → the fixed ``OS_FACTOR`` heuristic, ``"model"`` → the roofline
    modeled minimum, ``"measure"`` → the measured winner from the
    persistent cache — see :mod:`repro.core.tuning`).  ``chunk`` keys the
    decision to a streaming call grain: the tuner models and measures
    per-chunk calls (state + chunk in, chunk out) instead of one long
    ingest."""
    if block is not None:
        return pick_block(filter_len, block)
    from repro.core import tuning  # lazy: tuning measures through this module

    mode = tuning.resolve_mode(tune)
    if mode == "off" or filter_len < 2:
        return pick_block(filter_len)
    return tuning.tuned_block(L, filter_len, batch, backend, mode, chunk=chunk)


def pairs_apply(num_blocks: int, block: int) -> bool:
    """Whether ``num_blocks`` frames of one signal run paired: two real
    frames per complex transform of length ``block``.

    Decided from the shapes alone.  With one frame per signal (a streaming
    chunk) pairing would double the transformed work, and a block past
    :data:`~repro.core.plan.FUSED_MAX` would take the complex transform out
    of the fused regime (the real-FFT packing keeps it at ``block / 2``), so
    both keep the rfft/irfft pair.
    """
    return num_blocks >= 2 and block <= plan_lib.FUSED_MAX


#: Ring-buffer capacity of :func:`pairing_log`.
PAIRING_LOG_MAX = 256

#: One record per paired overlap-save call, newest last; written when the
#: call is traced (so once per compile under ``jit``).
_PAIRINGS: collections.deque = collections.deque(maxlen=PAIRING_LOG_MAX)


def pairing_log() -> Tuple[dict, ...]:
    """Process-global record of the paired overlap-save calls (bounded):
    ``{"frames", "pairs", "pad_frames"}`` over all leading indices, the pad
    frames being the transformed frames that carry no output (the zero
    imaginary frame of an odd frame count, and the frames that round the
    sharded variant's pairs up to the mesh).  ``pad_frames / (2·pairs)`` is
    the share of transformed frames that is padding."""
    return tuple(_PAIRINGS)


def clear_pairing_log() -> None:
    _PAIRINGS.clear()


def _pairing(signals: int, frames: int, pairs: int) -> dict:
    return {
        "frames": signals * frames,
        "pairs": signals * pairs,
        "pad_frames": signals * (2 * pairs - frames),
    }


def _schedule(L: int, filter_len: int, block: int, causal: bool) -> Tuple[int, int]:
    """``(L_out, frames)``: the output length of a length-``L`` signal
    through a ``filter_len``-tap filter, and the overlap-save frames of
    ``block`` that cover it."""
    L_out = L if causal else L + filter_len - 1
    return L_out, -(-L_out // (block - filter_len + 1))


def describe_conv(
    L: int, filter_len: int, block: int, *, causal: bool = True
) -> str:
    """One line naming the overlap-save schedule that :func:`fft_conv_os`
    runs for a length-``L`` signal through a ``filter_len``-tap filter at
    ``block``."""
    _, nb = _schedule(L, filter_len, block, causal)
    head = (
        f"overlap-save block {block}, step {block - filter_len + 1}: "
        f"{nb} frame(s) a signal"
    )
    if pairs_apply(nb, block):
        p = _pairing(1, nb, -(-nb // 2))
        return (
            f"{head} as {p['pairs']} paired complex frames "
            f"({p['pad_frames']} pad), fft/ifft n={block}"
        )
    return f"{head}, rfft/irfft n={block}"


@jax.named_scope("os_frame")
def frame_signal(
    x: jax.Array, block: int, step: int, num_blocks: int
) -> jax.Array:
    """Strided overlap-save framing of the last axis.

    Left-pads with ``block − step`` zeros (the causal history of the first
    block), right-pads with zeros to a whole number of steps, and gathers
    the overlapping windows: frame ``j`` covers padded offsets
    ``[j·step, j·step + block)``, so consecutive frames share the
    ``block − step`` overlap.  Returns ``(..., num_blocks, block)``.
    """
    overlap = block - step
    pad_r = num_blocks * step - x.shape[-1]
    if pad_r < 0:
        raise faults.PlanError(
            f"{num_blocks} blocks of step {step} cover only "
            f"{num_blocks * step} < {x.shape[-1]} samples"
        )
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(overlap, pad_r)])
    idx = np.arange(num_blocks)[:, None] * step + np.arange(block)[None, :]
    # indices are in-bounds by construction; mode="clip" skips the gather's
    # OOB mask (which XLA otherwise constant-folds at O(nb·B) compile cost)
    return jnp.take(xp, jnp.asarray(idx, np.int32), axis=-1, mode="clip")


def _frame_scale(frames: jax.Array) -> jax.Array:
    """The power of two at or above each frame's largest magnitude over the
    last axis (1 for a zero frame).  Dividing by it is exact, and puts both
    frames of a pair on one scale, so each keeps float32 rounding relative
    to its own size rather than its partner's."""
    _, e = jnp.frexp(jnp.max(jnp.abs(frames), axis=-1, keepdims=True))
    return jnp.ldexp(jnp.float32(1), e)


@jax.named_scope("os_pair")
def frame_pairs(
    x: jax.Array, block: int, step: int, num_blocks: int, pairs: Optional[int] = None
) -> Tuple[Planes, Planes]:
    """Overlap-save framing of the last axis straight into complex pairs.

    The frames are :func:`frame_signal`'s, each divided by its
    :func:`_frame_scale`; frame ``2j`` of each leading index goes to pair
    ``j``'s real plane and frame ``2j + 1`` to its imaginary plane.
    ``pairs`` (default ``⌈num_blocks / 2⌉``) pairs are made; frames past
    ``num_blocks``, such as the last imaginary frame of an odd
    ``num_blocks``, are zeros.  Returns
    ``((re, im), (scale_re, scale_im))``, planes ``(pairs, *lead, block)``
    and scales ``(pairs, *lead, 1)``: the pair axis leads, so the
    transform's rows and the output's whole frames are tiles of the planes.
    One loop over the pairs copies each pair's two windows of the
    zero-padded signal into place, so the program is the same for any
    frame count.
    """
    if num_blocks * step < x.shape[-1]:
        raise faults.PlanError(
            f"{num_blocks} blocks of step {step} cover only "
            f"{num_blocks * step} < {x.shape[-1]} samples"
        )
    pairs = pairs or -(-num_blocks // 2)
    pad = [(0, 0)] * (x.ndim - 1) + [(block - step, 2 * pairs * step - x.shape[-1])]

    def window(f):
        # padded inside the loop, XLA reads each window from x itself
        # instead of first writing the whole padded signal
        w = lax.dynamic_slice_in_dim(jnp.pad(x, pad), f * step, block, axis=-1)
        w = jnp.where(f < num_blocks, w, 0)
        s = _frame_scale(w)
        return (w / s)[None], s[None]

    def body(j, acc):
        (wr, sr), (wi, si) = window(2 * j), window(2 * j + 1)
        return tuple(
            lax.dynamic_update_slice_in_dim(a, u, j, axis=0)
            for a, u in zip(acc, (wr, wi, sr, si))
        )

    planes = jnp.zeros((pairs, *x.shape[:-1], block), x.dtype)
    scales = jnp.zeros((pairs, *x.shape[:-1], 1), x.dtype)
    zr, zi, sr, si = lax.fori_loop(0, pairs, body, (planes, planes, scales, scales))
    return (zr, zi), (sr, si)


@jax.named_scope("os_filter")
def filter_spectrum(
    h: jax.Array, block: int, backend: Optional[str] = None
) -> Planes:
    """Half-spectrum of ``h`` zero-padded to ``block``, with a broadcast
    block axis inserted before the bins — computed once per call and shared
    by every block (the paper's precomputed-LUT idea one level up)."""
    h = jnp.asarray(h, jnp.float32)
    hp = jnp.pad(h, [(0, 0)] * (h.ndim - 1) + [(0, block - h.shape[-1])])
    fwd = fft_lib.plan(fft_lib.FFTSpec(n=block, kind="rfft"), backend=backend)
    Hr, Hi = fwd(hp)
    return Hr[..., None, :], Hi[..., None, :]


def _conv_pairs(
    planes: Planes,
    Hr: jax.Array,
    Hi: jax.Array,
    *,
    backend: Optional[str] = None,
) -> Planes:
    """Circular convolution of :func:`frame_pairs`' complex pairs of real
    frames with a real filter: ONE cached complex fft/ifft plan pair over
    all pairs, the product taken against the filter's full spectrum.

    ``Hr``/``Hi`` are :func:`filter_spectrum` planes at the block; the full
    spectrum extends them by Hermitian symmetry (bin ``B − k`` is the
    conjugate of bin ``k``).  The real plane of the result is the real
    frames' convolution, the imaginary plane the imaginary frames'.  The
    product is named ``os_product``, the transforms by their kinds.
    """
    zr, zi = planes
    block = zr.shape[-1]
    Fr = jnp.concatenate([Hr, jnp.flip(Hr[..., 1:-1], -1)], axis=-1)[..., 0, :]
    Fi = jnp.concatenate([Hi, -jnp.flip(Hi[..., 1:-1], -1)], axis=-1)[..., 0, :]
    fwd = fft_lib.plan(fft_lib.FFTSpec(n=block, kind="fft"), backend=backend)
    inv = fft_lib.plan(fft_lib.FFTSpec(n=block, kind="ifft"), backend=backend)
    Zr, Zi = fwd.apply_planes(zr, zi)
    with jax.named_scope("os_product"):
        Yr, Yi = cmul(Zr, Zi, Fr, Fi)
    return inv.apply_planes(Yr, Yi)


def conv_frames(
    frames: jax.Array,
    Hr: jax.Array,
    Hi: jax.Array,
    *,
    overlap: int,
    backend: Optional[str] = None,
) -> jax.Array:
    """Batched circular convolution of ``(..., nb, B)`` frames with the
    broadcast filter spectrum, keeping each frame's valid tail: the
    rfft/irfft branch of :func:`overlap_save`, for the shapes where
    :func:`pairs_apply` does not hold (one frame a signal, or ``B`` past
    ``FUSED_MAX``); the others pair frames through :func:`_conv_pairs`.

    ONE cached rfft/irfft plan pair over all blocks (batch = leading dims ×
    nb), pointwise spectrum multiply, and the overlap-save discard: the
    first ``overlap`` samples of each block alias history that belongs to
    the previous block.  Returns ``(..., nb, B − overlap)``.  It is
    collective-free, so blocks shard over a mesh axis with no all-to-alls.
    The product and the discard are named ``os_product`` and
    ``os_discard``; the transforms by their kinds.
    """
    block = frames.shape[-1]
    fwd = fft_lib.plan(fft_lib.FFTSpec(n=block, kind="rfft"), backend=backend)
    inv = fft_lib.plan(fft_lib.FFTSpec(n=block, kind="irfft"), backend=backend)
    Fr, Fi = fwd(frames)
    with jax.named_scope("os_product"):
        Yr, Yi = cmul(Fr, Fi, Hr, Hi)
    y = inv((Yr, Yi))
    with jax.named_scope("os_discard"):
        return y[..., overlap:]


@jax.named_scope("os_unpair")
def _unpair(planes: Planes, scales: Planes, overlap: int, length: int) -> jax.Array:
    """The valid tails of :func:`frame_pairs`-ordered results, scaled back
    and written in signal order a whole frame at a time: two
    ``(pairs, *lead, B)`` planes → ``(*lead, length)``.  One loop writes
    the pairs whose two frames are whole; the last one or two frames, the
    last cut at ``length``, follow.  Frames are joined along the samples
    axis, never stacked on a minor axis of size 2."""
    step = planes[0].shape[-1] - overlap
    nb = -(-length // step)

    def row(a, j, start, size):  # pair j of ``a``, samples [start, start + size)
        at = (j,) + (0,) * (a.ndim - 2) + (start,)
        return lax.dynamic_slice(a, at, (1, *a.shape[1:-1], size))[0]

    def tail(k, j, n):
        # one dynamic slice, so XLA copies each tail straight into place
        return row(planes[k], j, overlap, n) * row(scales[k], j, 0, 1)

    def put(out, f, t):
        return lax.dynamic_update_slice_in_dim(out, t, f * step, axis=-1)

    def body(j, out):
        return put(put(out, 2 * j, tail(0, j, step)), 2 * j + 1, tail(1, j, step))

    whole = (nb - 1) // 2
    out = jnp.zeros((*planes[0].shape[1:-1], length), planes[0].dtype)
    out = lax.fori_loop(0, whole, body, out)
    for f in range(2 * whole, nb):
        out = put(out, f, tail(f % 2, f // 2, min(step, length - f * step)))
    return out


def _run(fn, data, Hr, Hi):
    return fn(data, Hr, Hi)


def overlap_save(
    x: jax.Array,
    Hr: jax.Array,
    Hi: jax.Array,
    *,
    block: int,
    overlap: int,
    length: int,
    backend: Optional[str] = None,
    shards: int = 1,
    run=_run,
) -> jax.Array:
    """The overlap-save schedule of the last axis of ``x`` through the
    filter spectrum ``Hr``/``Hi`` (:func:`filter_spectrum` planes at
    ``block``): the first ``length`` outputs, shaped ``(*lead, length)``,
    ``lead`` being the leading shapes of ``x`` and the filter broadcast.

    With :func:`pairs_apply` (``nb ≥ 2`` frames a signal, ``block ≤
    FUSED_MAX``), frames ``2j`` and ``2j + 1`` of each leading index run as
    one complex signal: :func:`frame_pairs` (``os_pair``), :func:`_conv_pairs`,
    and the tails written back whole frames at a time (``os_unpair``); each
    such call is recorded in :func:`pairing_log`.  ``x`` is framed at its
    own leading shape, so a bank of filters transforms the signal once.  A
    non-finite sample in a paired frame reaches its partner frame's output.
    Otherwise :func:`frame_signal`, :func:`conv_frames` and the ``os_tail``
    reshape run one cached rfft/irfft plan pair over all frames.

    ``run(fn, data, Hr, Hi)`` applies the collective-free transform stage
    (``_conv_pairs`` to the pairs, ``conv_frames`` to the frames); the
    sharded variant shards it over a mesh axis, after rounding the pairs
    (zero frames) or frames (windows past ``length``) up to a multiple of
    ``shards``.
    """
    step = block - overlap
    nb = -(-length // step)
    lead = np.broadcast_shapes(x.shape[:-1], Hr.shape[:-2])
    if pairs_apply(nb, block):
        pairs = -(-nb // 2)
        pairs = -(-pairs // shards) * shards
        _PAIRINGS.append(_pairing(int(np.prod(lead)), nb, pairs))
        x = x.reshape((1,) * (len(lead) + 1 - x.ndim) + x.shape)
        planes, scales = frame_pairs(x, block, step, nb, pairs)
        y = run(functools.partial(_conv_pairs, backend=backend), planes, Hr, Hi)
        return _unpair(y, scales, overlap, length)
    nb = -(-nb // shards) * shards
    frames = frame_signal(x, block, step, nb)
    tails = run(
        functools.partial(conv_frames, overlap=overlap, backend=backend), frames, Hr, Hi
    )
    with jax.named_scope("os_tail"):
        return tails.reshape(*tails.shape[:-2], nb * step)[..., :length]


def fft_conv_os(
    x: jax.Array,
    h: jax.Array,
    *,
    causal: bool = True,
    axis: int = -1,
    block: Optional[int] = None,
    backend: Optional[str] = None,
    tune: Optional[str] = None,
) -> jax.Array:
    """Overlap-save convolution of ``x`` with filter ``h`` along ``axis``.

    Matches :func:`repro.core.conv.fft_conv` outputs at tolerance while
    never planning a transform larger than the block (≤ ``FUSED_MAX`` by
    default): the signal is framed into overlapping blocks, the blocks run
    through one cached plan pair, and the valid tails are scattered back
    (:func:`overlap_save`).  ``h`` broadcasts against ``x`` with the
    convolution axis moved last, exactly like ``fft_conv``.

    With two or more blocks per signal and ``block ≤ FUSED_MAX``
    (:func:`pairs_apply`), adjacent blocks of one signal are paired into
    one complex signal and run through one cached complex fft/ifft plan
    pair against the filter's full spectrum, with no real-FFT packing per
    block; a non-finite sample then reaches the output of its partner
    block too, within the same signal.  Otherwise one cached rfft/irfft
    plan pair runs over all blocks.  :func:`describe_conv` names the
    schedule.

    With ``block=None`` the block size is a tuned decision
    (:mod:`repro.core.tuning`): ``tune="off"`` keeps the fixed
    ``OS_FACTOR`` heuristic, ``"model"`` (default) takes the roofline
    modeled minimum, ``"measure"`` times the pruned candidates once per
    ``(device, backend, L, Lh, batch)`` and reuses the persisted winner.
    """
    x = jnp.asarray(x)
    out_dtype = x.dtype
    x = x.astype(jnp.float32)
    h = jnp.asarray(h, jnp.float32)
    if axis != -1:
        x = jnp.moveaxis(x, axis, -1)
    L, Lh = x.shape[-1], h.shape[-1]
    batch = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    B = _resolve_block(Lh, block, L, batch, backend, tune)
    L_out, _ = _schedule(L, Lh, B, causal)
    Hr, Hi = filter_spectrum(h, B, backend)
    y = overlap_save(x, Hr, Hi, block=B, overlap=Lh - 1, length=L_out, backend=backend)
    if axis != -1:
        y = jnp.moveaxis(y, -1, axis)
    return y.astype(out_dtype)


def _stream_conv(
    xin: jax.Array,
    Hr: jax.Array,
    Hi: jax.Array,
    *,
    block: int,
    overlap: int,
    backend: Optional[str] = None,
) -> jax.Array:
    """Causal conv of ``xin`` (carried history prefix included) through the
    cached block plan, keeping only the outputs past the history:
    ``conv(xin)[..., overlap:]``.

    When everything fits one block (the decode-grain case: a flush of
    ``Lh − 1`` tail + one chunk) this is a single padded frame through ONE
    cached rfft/irfft pair — no framing gather at all.  Every kept output
    position ``p ≥ overlap ≥ j`` for all filter taps ``j``, so the circular
    convolution never wraps into the kept range and the single frame equals
    the framed multi-block result.  Longer inputs take :func:`overlap_save`
    (paired frames where :func:`pairs_apply` holds).
    """
    L = xin.shape[-1]
    if L <= block:
        pad = [(0, 0)] * (xin.ndim - 1) + [(0, block - L)]
        frames = jnp.pad(xin, pad)[..., None, :]
        y = conv_frames(frames, Hr, Hi, overlap=overlap, backend=backend)
        return y[..., 0, : L - overlap]
    y = overlap_save(
        xin, Hr, Hi, block=block, overlap=overlap, length=L, backend=backend
    )
    return y[..., overlap:]


def stream_lookahead(
    tail: jax.Array,
    Hr: jax.Array,
    Hi: jax.Array,
    *,
    window: int,
    block: int,
    backend: Optional[str] = None,
) -> jax.Array:
    """History-only contributions for the next ``window`` stream positions.

    ``tail``: (..., Lh − 1) — the carried overlap state.  Returns
    (..., window): entry ``i`` is what the causal conv would emit at the
    ``i``-th upcoming position if every upcoming input were zero, i.e. the
    Σ_{j>i} h[j]·x[t−j] half of the output.  This is the flush primitive of
    the amortized spectral decode: the serving cache adds the direct head
    (taps ``j ≤ i`` against the accumulating chunk) per token and refreshes
    this lookahead once per ``window`` tokens through the same cached block
    plan as prefill — no per-token transforms.

    ``Hr``/``Hi`` must be :func:`filter_spectrum` planes at ``block``; the
    kept outputs are exact (no circular contamination) for any
    ``tail``/``window`` because only positions ≥ ``len(tail)`` are kept.
    """
    lead = tail.shape[:-1]
    zeros = jnp.zeros((*lead, window), jnp.float32)
    xin = jnp.concatenate([tail.astype(jnp.float32), zeros], axis=-1)
    return _stream_conv(
        xin, Hr, Hi, block=block, overlap=tail.shape[-1], backend=backend
    )


class StreamingConv:
    """Chunked causal convolution with the overlap tail as explicit state.

    The streaming form of :func:`fft_conv_os` for serving decode and SAR
    strip ingest: the only cross-chunk dependency of a causal conv is the
    last ``Lh − 1`` input samples, carried as a state array so the object
    itself stays immutable (scan/jit-friendly — state in, state out).
    Chunked calls compose to the one-shot result for any chunking,
    including ragged final chunks and chunks shorter than the filter::

        sc = StreamingConv(h)
        state = sc.init_state(x.shape[:-1])
        y1, state = sc(x[..., :4096], state)
        y2, state = sc(x[..., 4096:], state)
        # concat([y1, y2]) == fft_conv_os(x, h)

    Every chunk reuses the same cached block-plan pair (the block size is
    fixed by the filter at construction) AND the filter spectrum computed
    here once — per-chunk work is the chunk's own frames only.

    With ``block=None`` the block is tuned like :func:`fft_conv_os`'s
    (``tune=`` modes, persistent cache); ``chunk_hint`` is the expected
    per-call chunk length.  When given, the tuner keys the decision to that
    decode grain and its measurement pass times chunked streaming calls
    (state + chunk in) rather than one long ingest — serving decode and
    strip ingest genuinely prefer different blocks (chunks shorter than the
    heuristic block waste the unfilled step on every call).  Without a hint
    the measurement uses a long-ingest stand-in of 8 heuristic blocks.

    ``spmd=True`` makes the block pick cache- and measurement-free
    (:func:`repro.core.tuning.modeled_block`): every host of a
    multi-process mesh derives the identical block from the shape alone,
    so a ``StreamingConv`` built inside per-host setup code stays safe to
    close over in a ``shard_map`` program.  A per-host cache hit or timing
    run could diverge across hosts and desynchronize collective shapes —
    the same rule :func:`repro.core.distributed.pconv_os_sharded` follows.
    """

    def __init__(
        self,
        h: jax.Array,
        *,
        block: Optional[int] = None,
        backend: Optional[str] = None,
        tune: Optional[str] = None,
        chunk_hint: Optional[int] = None,
        spmd: bool = False,
    ):
        self.h = jnp.asarray(h, jnp.float32)
        self.filter_len = int(self.h.shape[-1])
        self.overlap = self.filter_len - 1
        self.chunk_hint = chunk_hint
        L_tune = chunk_hint or 8 * pick_block(self.filter_len)
        if spmd and block is None:
            from repro.core import tuning  # lazy: tuning measures through here

            self.block = tuning.modeled_block(
                L_tune, self.filter_len, 1, backend, chunk=chunk_hint
            )
        else:
            self.block = _resolve_block(
                self.filter_len, block, L_tune, 1, backend, tune, chunk=chunk_hint
            )
        self.backend = backend
        self._Hr, self._Hi = filter_spectrum(self.h, self.block, backend)

    def init_state(self, lead: tuple = (), dtype=jnp.float32) -> jax.Array:
        """Zero history: ``(*lead, Lh − 1)``.  ``lead`` must broadcast like
        the chunks' leading dims (e.g. ``(batch, channels)``)."""
        return jnp.zeros((*tuple(lead), self.overlap), dtype)

    def __call__(self, x: jax.Array, state: jax.Array) -> tuple:
        """Convolve one chunk; returns ``(y, new_state)`` with ``y`` the
        causal output for exactly this chunk's samples."""
        x = jnp.asarray(x)
        out_dtype = x.dtype
        if state.shape[-1] != self.overlap:
            raise faults.PlanError(
                f"state carries {state.shape[-1]} samples, filter needs "
                f"{self.overlap}"
            )
        xin = jnp.concatenate(
            [state.astype(jnp.float32), x.astype(jnp.float32)], axis=-1
        )
        # The first ``overlap`` outputs re-derive samples the previous chunk
        # already emitted; _stream_conv keeps only this chunk's contribution
        # (single padded frame when state + chunk fit one block).
        y = _stream_conv(
            xin,
            self._Hr,
            self._Hi,
            block=self.block,
            overlap=self.overlap,
            backend=self.backend,
        )
        new_state = (
            xin[..., xin.shape[-1] - self.overlap :]
            if self.overlap
            else xin[..., :0]
        )
        return y.astype(out_dtype), new_state

    def lookahead(self, state: jax.Array, window: int) -> jax.Array:
        """History-only outputs for the next ``window`` positions — what the
        stream would emit if the next ``window`` samples were zero.  The
        decode-grain flush primitive; see :func:`stream_lookahead`."""
        if state.shape[-1] != self.overlap:
            raise faults.PlanError(
                f"state carries {state.shape[-1]} samples, filter needs "
                f"{self.overlap}"
            )
        return stream_lookahead(
            state,
            self._Hr,
            self._Hi,
            window=window,
            block=self.block,
            backend=self.backend,
        )
