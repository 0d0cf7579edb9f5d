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
* run ONE cached rfft/irfft plan pair **batched over all blocks** (the
  filter spectrum is computed once and broadcast) — exactly the shape the
  pallas pass programs are fastest at: big batch × fused-regime N;
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

``analysis.roofline.conv_report`` models the HBM traffic of both schedules
so the win is observable, not just asserted.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults
from repro.core import fft as fft_lib
from repro.core import plan as plan_lib
from repro.core.fft_xla import cmul
from repro.core.limits import OS_FACTOR, next_pow2

Planes = Tuple[jax.Array, jax.Array]

__all__ = [
    "OS_FACTOR",
    "pick_block",
    "frame_signal",
    "filter_spectrum",
    "conv_frames",
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


def conv_frames(
    frames: jax.Array,
    Hr: jax.Array,
    Hi: jax.Array,
    *,
    overlap: int,
    backend: Optional[str] = None,
) -> jax.Array:
    """Batched circular convolution of ``(..., nb, B)`` frames with the
    broadcast filter spectrum, keeping each frame's valid tail.

    ONE cached rfft/irfft plan pair over all blocks (batch = leading dims ×
    nb), pointwise spectrum multiply, and the overlap-save discard: the
    first ``overlap`` samples of each block alias history that belongs to
    the previous block.  Returns ``(..., nb, B − overlap)``.  Also the body
    of the sharded variant — it is collective-free, so blocks shard over a
    mesh axis with no all-to-alls.  The product and the discard are named
    ``os_product`` and ``os_discard``; the transforms by their kinds.
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
    default): the signal is framed into overlapping blocks, all blocks run
    through one cached rfft/irfft plan pair, and the valid tails are
    scattered back.  ``h`` broadcasts against ``x`` with the convolution
    axis moved last, exactly like ``fft_conv``.

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
    overlap = Lh - 1
    step = B - overlap
    L_out = L if causal else L + Lh - 1
    nb = -(-L_out // step)
    frames = frame_signal(x, B, step, nb)
    Hr, Hi = filter_spectrum(h, B, backend)
    tails = conv_frames(frames, Hr, Hi, overlap=overlap, backend=backend)
    with jax.named_scope("os_tail"):
        lead = tails.shape[:-2]
        y = tails.reshape(*lead, nb * step)[..., :L_out]
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
    the framed multi-block result.
    """
    L = xin.shape[-1]
    if L <= block:
        pad = [(0, 0)] * (xin.ndim - 1) + [(0, block - L)]
        frames = jnp.pad(xin, pad)[..., None, :]
        y = conv_frames(frames, Hr, Hi, overlap=overlap, backend=backend)
        return y[..., 0, : L - overlap]
    step = block - overlap
    nb = -(-L // step)
    frames = frame_signal(xin, block, step, nb)
    tails = conv_frames(frames, Hr, Hi, overlap=overlap, backend=backend)
    with jax.named_scope("os_tail"):
        lead = tails.shape[:-2]
        y = tails.reshape(*lead, nb * step)[..., :L]
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
