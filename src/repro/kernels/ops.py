"""Jit-ready wrappers around the Pallas FFT kernels.

``ops.execute_plan`` *consumes* an :class:`repro.core.plan.FFTPlan` by
walking its **linearized pass program** (:attr:`FFTPlan.passes`) with
:func:`execute_program` — an iterative executor, not a recursion.  Every
program pass is exactly one ``pallas_call`` HBM round trip:

* whole-signal pass  → :func:`dft_matmul_call` / :func:`fft4step_call`
  (the ≤ FUSED_MAX one-call regimes);
* strided-column pass → :func:`~repro.kernels.pencil.cols_pass_call`, which
  reads/writes the ``(b, n1, n2)`` view's columns in place and applies the
  inter-factor twiddle as its VMEM epilogue;
* contiguous-row pass → :func:`~repro.kernels.pencil.rows_natural_call`
  when the natural-order transpose is fused into its strided write, or the
  plain leaf kernel for pencil-order output.

Between passes the executor only reshapes; there are **zero** standalone
``swapaxes``/transpose or twiddle ``cmul`` ops in the schedule, which is
what makes the split regime match the paper's §2.3.2 call-count discipline
(and beat it: two round trips cover every N ≤ 2³²).  The tests assert this
over the jaxpr.  A reshape is free only where XLA can keep the array's
tiled TPU layout ((8, 128) tiles over the last two dims): flattening a
leading dim that does not fill whole tiles into the batch, or an input
that its producer left in another layout, makes XLA copy the array through
HBM.  The overlap-save convolution hits both (its frames, 9 per channel,
come from a gather); the compiled program names those copies by the scope
they run in (``rfft/reshape``, ``irfft/concatenate``), and each pass runs
under ``p{i}_rows`` / ``p{i}_cols`` (:func:`repro.core.plan.pass_scope`).

Responsibilities handled here so kernels stay minimal: batch flattening and
tile padding, LUT construction (host-cached, inverse scaling folded into W2 /
W; the inter-factor twiddle grids cached per (bins, phases) pair), interpret-
mode selection (auto on CPU), and per-pass chunk sizing against the VMEM
budget.  ``ops.fft``/``ops.ifft`` remain as plan-deriving conveniences.

Each kernel pass has a traced-XLA twin beside it (:func:`_xla_pass` for
row passes, :func:`_cols_image_xla` for image columns): the same LUTs and
scaling, run as plain XLA ops.  :func:`repro.core.faults.run_leaf` demotes
a leaf to it only after an injected fault quarantines ``(pallas, kind)``.
"""

from __future__ import annotations

import functools
import os
from typing import Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults
from repro.core import fft_xla
from repro.core import plan as plan_lib
from repro.core import twiddle as tw
from repro.core.fft_xla import cmul
from repro.core.limits import LANES, SUBLANES, row_group
from repro.kernels.dft_matmul import dft_matmul_call, dft_tile
from repro.kernels.fft4step import fft4step_call, four_step_tile, lane_group_luts
from repro.kernels import pencil

Planes = Tuple[jax.Array, jax.Array]

__all__ = [
    "execute_plan",
    "execute_program",
    "execute_program2d",
    "fft",
    "ifft",
    "should_interpret",
]


def should_interpret() -> bool:
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() == "cpu"


@functools.lru_cache(maxsize=256)
def _direct_luts(n: int, inverse: bool):
    wr, wi = tw.dft_matrix(n, inverse)
    if inverse:
        wr = wr / np.float32(n)  # fold 1/N into the LUT
        wi = wi / np.float32(n)
    return wr, wi


@functools.lru_cache(maxsize=256)
def _fused_luts(n1: int, n2: int, inverse: bool, natural_order: bool = True):
    """The fused four-step leaf's LUTs (W1, T, W2), 1/N folded into W2 for
    the inverse; a natural-order lane group (``n1 < LANES``) takes them
    rearranged by :func:`~repro.kernels.fft4step.lane_group_luts`."""
    w1r, w1i = tw.dft_matrix(n1, inverse)
    tr, ti = tw.twiddle_grid(n1, n2, inverse)
    w2r, w2i = tw.dft_matrix(n2, inverse)
    if inverse:
        s = np.float32(1.0 / (n1 * n2))
        w2r, w2i = w2r * s, w2i * s
    if natural_order and row_group(n1, n2) > 1:
        return lane_group_luts(w1r, w1i, tr, ti, w2r, w2i)
    return w1r, w1i, tr, ti, w2r, w2i


@functools.lru_cache(maxsize=64)
def _pass_twiddle_luts(n_bins: int, n_phases: int, inverse: bool):
    """Host-cached inter-factor twiddle grid for a program pass's epilogue
    (served to the kernel chunk-by-chunk through its BlockSpec)."""
    return tw.pass_twiddle(n_bins, n_phases, inverse)


@functools.lru_cache(maxsize=64)
def _pass_twiddle_rows(n_bins: int, n_phases: int, inverse: bool):
    """The same grid transposed to ``(n_phases, n_bins)``: one phase row per
    strip-mined column chunk, broadcast across the image width in VMEM."""
    return tuple(
        np.ascontiguousarray(t.T) for t in _pass_twiddle_luts(n_bins, n_phases, inverse)
    )


def _transform_luts(p: plan_lib.Pass, inverse: bool, natural_order: bool = True):
    if p.kind == "direct":
        return _direct_luts(p.n, inverse)
    return _fused_luts(p.n1, p.n2, inverse, natural_order)


def _bluestein_luts(p: plan_lib.Pass, inverse: bool):
    """The LUT tuple of one Bluestein pass stage, host-cached piecewise.

    The chirp planes and B̂ spectrum come from the interned
    :mod:`repro.core.twiddle` caches (computed once per (n, pad,
    direction), like every twiddle table); the fused ``fwd``/``inv``
    stages additionally carry the pad-length transform's own LUTs.  The
    INNER conv direction is fixed — forward then inverse — regardless of
    ``inverse``, which only selects the chirp tables.
    """
    n, m_pad = p.n, p.n1
    if p.stage == "pre":
        ar, ai = tw.bluestein_chirp(n, inverse)
        return (ar.reshape(1, n), ai.reshape(1, n))
    if p.stage == "mul":
        br, bi = tw.bluestein_spectrum(n, m_pad, inverse)
        return (br.reshape(1, m_pad), bi.reshape(1, m_pad))
    if p.stage == "post":
        pr, pi = tw.bluestein_postchirp(n, inverse)
        return (pr.reshape(1, n), pi.reshape(1, n))
    inner = plan_lib._leaf_pass(m_pad)
    if p.stage == "fwd":
        ar, ai = tw.bluestein_chirp(n, inverse)
        inner_luts = (
            _direct_luts(m_pad, False)
            if inner.kind == "direct"
            else _fused_luts(inner.n1, inner.n2, False)
        )
        br, bi = tw.bluestein_spectrum(n, m_pad, inverse)
        return (
            ar.reshape(1, n), ai.reshape(1, n),
            *inner_luts,
            br.reshape(1, m_pad), bi.reshape(1, m_pad),
        )
    if p.stage != "inv":
        raise ValueError(f"unknown bluestein stage {p.stage!r}")
    inner_luts = (
        _direct_luts(m_pad, True)
        if inner.kind == "direct"
        else _fused_luts(inner.n1, inner.n2, True)
    )
    pr, pi = tw.bluestein_postchirp(n, inverse)
    return (*inner_luts, pr.reshape(1, n), pi.reshape(1, n))


def _bluestein_pass(xr, xi, p: plan_lib.Pass, inverse, interpret, bt) -> Planes:
    """One Bluestein program pass (any stage) as a single pallas_call."""
    from repro.kernels import bluestein as bk

    n, m_pad = p.n, p.n1
    bt = _batch_tile(bt, xr.shape[0])
    xr, xi, b, pad = _pad_batch(xr, xi, bt)
    luts = _bluestein_luts(p, inverse)
    kw = dict(n=n, m_pad=m_pad, batch_tile=bt, interpret=interpret)
    if p.stage in ("fwd", "inv"):
        inner = plan_lib._leaf_pass(m_pad)
        call = bk.bluestein_fwd_call if p.stage == "fwd" else bk.bluestein_inv_call
        yr, yi = call(
            xr, xi, luts, inner_kind=inner.kind, in1=inner.n1, in2=inner.n2, **kw
        )
    else:
        yr, yi = bk.bluestein_elem_call(xr, xi, luts, stage=p.stage, **kw)
    return (yr, yi) if pad == 0 else (yr[:b], yi[:b])


def _batch_tile(bt: int, b: int) -> int:
    """Rows per grid step: the whole batch when it fits one tile (the block
    then equals the array's own dims), else at least ``SUBLANES`` rows —
    a Pallas TPU block's second-to-last dim is a multiple of 8 or whole."""
    return b if b <= bt else max(bt, SUBLANES)


def _pad_batch(xr, xi, bt):
    b = xr.shape[0]
    pad = (-b) % bt
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
        xi = jnp.pad(xi, ((0, pad), (0, 0)))
    return xr, xi, b, pad


def _tile_for(p: plan_lib.Pass, batch_tiles: Mapping[int, int] | None) -> int:
    if batch_tiles is not None and p.n in batch_tiles:
        return batch_tiles[p.n]
    return plan_lib.pick_batch_tile(p)


def _leaf_kernel(
    xr, xi, p: plan_lib.Pass, inverse, interpret, batch_tiles, natural_order=True
) -> Planes:
    """Single-pallas_call transform of the last axis (2-D input)."""
    if p.kind == "bluestein":
        return _bluestein_pass(xr, xi, p, inverse, interpret, _tile_for(p, batch_tiles))
    if p.n == 1:
        return xr, xi
    bt = _batch_tile(_tile_for(p, batch_tiles), xr.shape[0])
    xr, xi, b, pad = _pad_batch(xr, xi, bt)
    if p.kind == "direct":
        wr, wi = _direct_luts(p.n, inverse)
        yr, yi = dft_matmul_call(
            xr, xi, jnp.asarray(wr), jnp.asarray(wi), batch_tile=bt, interpret=interpret
        )
    else:
        w1r, w1i, tr, ti, w2r, w2i = _fused_luts(p.n1, p.n2, inverse, natural_order)
        yr, yi = fft4step_call(
            xr,
            xi,
            jnp.asarray(w1r),
            jnp.asarray(w1i),
            jnp.asarray(tr),
            jnp.asarray(ti),
            jnp.asarray(w2r),
            jnp.asarray(w2i),
            batch_tile=bt,
            natural_order=natural_order,
            interpret=interpret,
        )
    # The identity slice would still cost a jaxpr eqn — keep unpadded
    # schedules at pallas_call + reshape only.
    return (yr, yi) if pad == 0 else (yr[:b], yi[:b])


def _apply_pass(
    xr, xi, p: plan_lib.Pass, fs, inverse, interpret, batch_tiles, chunk=None,
    degradations=None, index=None,
) -> Planes:
    """One row-axis program pass over (B, n) split planes.  ``chunk``
    overrides the VMEM-heuristic grid-step width (the tuner's hook).

    Kernel passes run under :func:`repro.core.faults.run_leaf`: a leaf
    that fails to trace/compile raises ``KernelError`` with the compiler's
    message.  Only an injected fault is retried, then quarantines the
    (pallas, kind) pair and demotes the pass to the traced-XLA fallback,
    recorded on ``degradations``.  The no-fault jaxpr is untouched."""
    # A pass may pin its own direction (the Bluestein inner conv is always
    # forward-then-inverse regardless of the outer transform's direction).
    inverse = p.inverse if p.inverse is not None else inverse
    b, n = xr.shape
    if p.kind == "reorder":
        # Digit-reversal relayout — only programs with ≥ 3 factors
        # (N > 2³²) reach this; plain XLA transpose, one HBM round trip.
        perm = (0,) + tuple(range(len(fs), 0, -1))
        xr = xr.reshape(b, *fs).transpose(perm).reshape(b, n)
        xi = xi.reshape(b, *fs).transpose(perm).reshape(b, n)
        return xr, xi
    return faults.run_leaf(
        "pallas",
        p.kind,
        lambda: _pass_kernel(xr, xi, p, inverse, interpret, batch_tiles, chunk),
        lambda: _xla_pass(xr, xi, p, inverse),
        degradations=degradations,
        index=index,
    )


def _row_transform_xla(xr2, xi2, p: plan_lib.Pass, luts, natural: bool = True):
    """Traced last-axis transform of (R, f) planes — the fallback's engine
    (the same pure-jnp tiles the kernels embed, just not inside a
    pallas_call)."""
    if p.kind == "direct":
        return dft_tile(xr2, xi2, jnp.asarray(luts[0]), jnp.asarray(luts[1]))
    w1r, w1i, tr, ti, w2r, w2i = (jnp.asarray(a) for a in luts)
    return four_step_tile(xr2, xi2, w1r, w1i, tr, ti, w2r, w2i, p.n1, p.n2, natural)


def _bluestein_xla_pass(xr, xi, p: plan_lib.Pass, inverse) -> Planes:
    """One Bluestein program stage, traced through XLA.

    Same interned chirp/B̂ tables as the kernel path; the pad-length
    transform runs through :func:`repro.core.fft_xla.four_step_fft`
    (forward for ``fwd``, true inverse — 1/M folded — for ``inv``).
    """
    n, m_pad = p.n, p.n1
    if p.stage in ("pre", "fwd"):
        ar, ai = tw.bluestein_chirp(n, inverse)
        xr, xi = cmul(xr, xi, jnp.asarray(ar)[None], jnp.asarray(ai)[None])
        xr = jnp.pad(xr, ((0, 0), (0, m_pad - n)))
        xi = jnp.pad(xi, ((0, 0), (0, m_pad - n)))
        if p.stage == "pre":
            return xr, xi
        xr, xi = fft_xla.four_step_fft(xr, xi)
    if p.stage in ("mul", "fwd"):
        br, bi = tw.bluestein_spectrum(n, m_pad, inverse)
        return cmul(xr, xi, jnp.asarray(br)[None], jnp.asarray(bi)[None])
    if p.stage == "inv":
        xr, xi = fft_xla.four_step_fft(xr, xi, inverse=True)
    elif p.stage != "post":
        raise ValueError(f"unknown bluestein stage {p.stage!r}")
    pr, pi = tw.bluestein_postchirp(n, inverse)
    return cmul(
        xr[:, :n], xi[:, :n], jnp.asarray(pr)[None], jnp.asarray(pi)[None]
    )


def _xla_pass(xr, xi, p: plan_lib.Pass, inverse) -> Planes:
    """Traced-XLA execution of one non-reorder row pass over (B, n) planes —
    the degradation target of :func:`_apply_pass`.

    Same host-cached LUT tables, same per-pass 1/f inverse folding and
    twiddle-after convention as :func:`_pass_kernel`, but its transposes
    are plain XLA ops.
    """
    b, n = xr.shape
    if p.kind == "bluestein":
        return _bluestein_xla_pass(xr, xi, p, inverse)
    pencils, stride, f = p.view_in if p.view_in else (1, 1, p.n)
    if pencils == 1:
        natural = p.order == "natural"
        luts = _transform_luts(p, inverse, natural)
        return _row_transform_xla(xr, xi, p, luts, natural=natural)
    luts = _transform_luts(p, inverse)
    if stride == 1:
        rr = xr.reshape(b * pencils, f)
        ri = xi.reshape(b * pencils, f)
        rr, ri = _row_transform_xla(rr, ri, p, luts)
        if p.view_out != p.view_in:
            # Natural-order write: (b, p, f) → (b, f, p), materialized.
            rr = rr.reshape(b, pencils, f).swapaxes(-1, -2)
            ri = ri.reshape(b, pencils, f).swapaxes(-1, -2)
        return rr.reshape(b, n), ri.reshape(b, n)
    # Strided-column pass: transform length f down axis -2 of the
    # (b·groups, f, stride) view, then the inter-factor twiddle.
    groups = pencils // stride
    xr3 = xr.reshape(b * groups, f, stride).swapaxes(-1, -2)
    xi3 = xi.reshape(b * groups, f, stride).swapaxes(-1, -2)
    rr, ri = _row_transform_xla(xr3.reshape(-1, f), xi3.reshape(-1, f), p, luts)
    yr3 = rr.reshape(b * groups, stride, f).swapaxes(-1, -2)
    yi3 = ri.reshape(b * groups, stride, f).swapaxes(-1, -2)
    if p.twiddle_after is not None:
        tr, ti = _pass_twiddle_luts(*p.twiddle_after, inverse)
        yr3, yi3 = cmul(yr3, yi3, jnp.asarray(tr)[None], jnp.asarray(ti)[None])
    return yr3.reshape(b, n), yi3.reshape(b, n)


def _pass_kernel(
    xr, xi, p: plan_lib.Pass, inverse, interpret, batch_tiles, chunk
) -> Planes:
    """The pallas execution of one non-reorder row pass (direction already
    resolved by :func:`_apply_pass`)."""
    b, n = xr.shape
    pencils, stride, f = p.view_in
    if pencils == 1:
        # Whole-signal pass: the ≤ FUSED_MAX one-call regime.
        return _leaf_kernel(
            xr, xi, p, inverse, interpret, batch_tiles,
            natural_order=p.order == "natural",
        )
    luts = _transform_luts(p, inverse)
    width = stride if stride > 1 else pencils
    chunk = _fit_chunk(chunk, width, p) if chunk else plan_lib.pick_pass_chunk(p)
    if stride == 1:
        if p.view_out != p.view_in:
            # Row pass with the natural-order transpose fused into its
            # strided write: (b, p, f) → (b, f, p) flattens naturally.
            xr3 = xr.reshape(b, pencils, f)
            xi3 = xi.reshape(b, pencils, f)
            yr3, yi3 = pencil.rows_natural_call(
                xr3, xi3, luts, kind=p.kind, n1=p.n1, n2=p.n2,
                chunk=chunk, interpret=interpret,
            )
            return yr3.reshape(b, n), yi3.reshape(b, n)
        # Pencil-order row pass: contiguous rows, plain leaf kernel.
        rr = xr.reshape(b * pencils, f)
        ri = xi.reshape(b * pencils, f)
        rr, ri = _leaf_kernel(rr, ri, p, inverse, interpret, batch_tiles)
        return rr.reshape(b, n), ri.reshape(b, n)
    # Strided-column pass (+ fused inter-factor twiddle epilogue).
    groups = pencils // stride
    xr3 = xr.reshape(b * groups, f, stride)
    xi3 = xi.reshape(b * groups, f, stride)
    twiddle = None
    if p.twiddle_after is not None:
        twiddle = _pass_twiddle_luts(*p.twiddle_after, inverse)
    xr3, xi3 = pencil.cols_pass_call(
        xr3, xi3, luts, twiddle, kind=p.kind, n1=p.n1, n2=p.n2,
        chunk=chunk, interpret=interpret,
    )
    return xr3.reshape(b, n), xi3.reshape(b, n)


def image_chunk(p: plan_lib.Pass, w: int) -> int:
    """Column-pass chunk for an image of width ``w``.  Ragged widths (the
    m+1 half-spectrum bins of rfft2): a chunk near the width would nearly
    double the pass (pow2-floored chunk + 1 ragged column → a whole extra
    chunk of padding), so shrink until the padding is under half a chunk —
    but not below one 128-lane tile.  An image at most one lane tile wide
    is one whole-width chunk."""
    if w <= LANES:
        return w
    chunk = plan_lib.pick_pass_chunk(p, width=w)
    while chunk > LANES and (-w) % chunk >= chunk // 2:
        chunk //= 2
    return chunk


def _fit_chunk(c: int, w: int, p: plan_lib.Pass) -> int:
    """Clamp a (possibly tuned) chunk to the width and the VMEM budget —
    a cache entry tuned for one shape must not break another.  The chunk
    stays at least one 128-lane tile (or the whole width below that):
    Mosaic refuses narrower column blocks."""
    if w <= LANES:
        return w
    top = 1 << (w.bit_length() - 1)
    c = max(LANES, min(c, top))
    while c > LANES and plan_lib._pass_chunk_bytes(p, c) > plan_lib.VMEM_BUDGET:
        c //= 2
    return c


def _cols_image_pass(
    xr, xi, p: plan_lib.Pass, inverse, interpret, chunk=None,
    degradations=None, index=None,
) -> Planes:
    """Column pass of a 2-D program, under the same fault protocol as the
    row passes (see :func:`_apply_pass`)."""
    return faults.run_leaf(
        "pallas",
        p.kind,
        lambda: _cols_image_kernel(xr, xi, p, inverse, interpret, chunk),
        lambda: _cols_image_xla(xr, xi, p, inverse),
        degradations=degradations,
        index=index,
    )


def _cols_image_xla(xr, xi, p: plan_lib.Pass, inverse) -> Planes:
    """Traced-XLA execution of an axis -2 column pass (degradation target):
    materialize the width transpose, run the generic 1-D fallback over the
    column axis, transpose back."""
    b, rows, w = xr.shape
    pencils, stride, f = p.view_in if p.view_in else (1, 1, p.n)
    xt_r = jnp.swapaxes(xr, -1, -2).reshape(b * w, rows)
    xt_i = jnp.swapaxes(xi, -1, -2).reshape(b * w, rows)
    if pencils == 1 or f == rows:
        # Whole-column transform (incl. the distributed driver's synthetic
        # (q, q, n) pass): one natural-order row transform of length rows.
        natural = p.order == "natural"
        yr, yi = _row_transform_xla(
            xt_r, xt_i, p, _transform_luts(p, inverse, natural), natural=natural
        )
    else:
        # Strip-mined column factor: the re-tagged 1-D split program of the
        # n2 axis applies verbatim on the transposed (B·w, n2) view.
        yr, yi = _xla_pass(xt_r, xt_i, p, inverse)
    yr = yr.reshape(b, w, rows).swapaxes(-1, -2)
    yi = yi.reshape(b, w, rows).swapaxes(-1, -2)
    return yr, yi


def _cols_image_kernel(xr, xi, p: plan_lib.Pass, inverse, interpret, chunk=None) -> Planes:
    """Column pass of a 2-D program: transform axis -2 of the (B, n2, w)
    image view through the strided-pencil kernels, sweeping the image width
    chunk-by-chunk (``chunk`` overrides the VMEM-heuristic width — the
    tuner's hook).  Non-power-of-two widths (the m+1 bins of an rfft2
    half-spectrum) pad up to a chunk multiple around the call.

    Fused-regime columns (``view_in == (1, 1, n2)``) are one in-place
    whole-column pass.  Strip-mined columns (``n2 > FUSED_MAX``) arrive as
    the re-tagged 1-D program of the n2 axis: the strided factor runs
    through :func:`~repro.kernels.pencil.cols_pass_call` on the
    ``(B, f, stride·w)`` view with its inter-factor twiddle broadcast
    across the width in VMEM, and the final contiguous factor through
    :func:`~repro.kernels.pencil.cols_natural_call`, which fuses the
    n2-axis digit transpose into its strided write — zero standalone HBM
    transposes either way."""
    b, rows, w = xr.shape
    pencils, stride, f = p.view_in if p.view_in else (1, 1, p.n)
    luts = _transform_luts(p, inverse)
    chunk = _fit_chunk(chunk, w, p) if chunk else image_chunk(p, w)
    pad = (-w) % chunk
    if pad:
        xr = jnp.pad(xr, ((0, 0), (0, 0), (0, pad)))
        xi = jnp.pad(xi, ((0, 0), (0, 0), (0, pad)))
    wp = w + pad
    if pencils == 1 or f == rows:
        # Whole-column pass: the transform spans the full -2 axis — the
        # fused-regime n2 ≤ FUSED_MAX case, or the distributed driver's
        # synthetic (q, q, n) plan pass over a width-q slab.
        yr, yi = pencil.cols_pass_call(
            xr, xi, luts, kind=p.kind, n1=p.n1, n2=p.n2,
            chunk=chunk, interpret=interpret,
        )
    elif stride > 1:
        # Strided column factor: n2-index = t·stride + r, transform over t.
        # The (r, image-width) pair rides along as the kernel's pencil
        # columns; the twiddle phase depends only on r, so the (f, stride)
        # grid is served one column per chunk and width-broadcast in VMEM.
        assert pencils == stride, (p.view_in, "≥3-factor columns are gated")
        x3r = xr.reshape(b, f, stride * wp)
        x3i = xi.reshape(b, f, stride * wp)
        twiddle = None
        if p.twiddle_after is not None:
            twiddle = _pass_twiddle_rows(*p.twiddle_after, inverse)
        yr, yi = pencil.cols_pass_call(
            x3r, x3i, luts, twiddle, kind=p.kind, n1=p.n1, n2=p.n2,
            chunk=chunk, interpret=interpret, tw_every=wp,
        )
        yr = yr.reshape(b, rows, wp)
        yi = yi.reshape(b, rows, wp)
    else:
        # Final contiguous factor, natural-order digit transpose fused
        # into the write: (B, P, f, wp) → (B, f, P, wp).
        if p.view_out == p.view_in:
            raise NotImplementedError(
                "pencil-order strip-mined column programs are not compiled"
            )
        x4r = xr.reshape(b, pencils, f, wp)
        x4i = xi.reshape(b, pencils, f, wp)
        yr, yi = pencil.cols_natural_call(
            x4r, x4i, luts, kind=p.kind, n1=p.n1, n2=p.n2,
            chunk=chunk, interpret=interpret,
        )
        yr = yr.reshape(b, rows, wp)
        yi = yi.reshape(b, rows, wp)
    if pad:
        yr, yi = yr[..., :w], yi[..., :w]
    return yr, yi


def execute_program(
    xr: jax.Array,
    xi: jax.Array,
    passes: Sequence[plan_lib.Pass],
    *,
    inverse: bool = False,
    interpret: bool | None = None,
    batch_tiles: Mapping[int, int] | None = None,
    chunks: Mapping[int, int] | None = None,
    degradations: list | None = None,
) -> Planes:
    """Walk a linearized pass program over 2-D (B, n) split planes.

    One ``pallas_call`` per pass, under the scope ``p{i}_rows`` /
    ``p{i}_cols``; the only ops between passes are row-major reshapes
    (free where the tiled layout allows, see the module docstring).
    ``chunks`` (pass index → grid-step width) carries the tuner's per-pass
    picks; unlisted passes fall back to the VMEM-budget heuristic.  ``degradations`` (a plan's ledger) collects
    any leaf demoted to the traced-XLA fallback.
    """
    if interpret is None:
        interpret = should_interpret()
    fs = [q.n for q in passes if q.kind != "reorder"]
    for i, p in enumerate(passes):
        with jax.named_scope(plan_lib.pass_scope(i, p)):
            xr, xi = _apply_pass(
                xr, xi, p, fs, inverse, interpret, batch_tiles,
                chunk=chunks.get(i) if chunks else None,
                degradations=degradations, index=i,
            )
    return xr, xi


def execute_program2d(
    xr: jax.Array,
    xi: jax.Array,
    passes: Sequence[plan_lib.Pass],
    *,
    inverse: bool = False,
    interpret: bool | None = None,
    batch_tiles: Mapping[int, int] | None = None,
    chunks: Mapping[int, int] | None = None,
    degradations: list | None = None,
) -> Planes:
    """Walk a mixed-axis pass program over 3-D (B, n2, n) image planes.

    ``axis=-1`` passes run the 1-D machinery over the ``(B·n2, n)`` row
    view; ``axis=-2`` passes transform the columns of the ``(B, n2, n)``
    view through the strided-pencil kernels — in place for fused-regime
    column lengths, strip-mined (multi-factor, width-swept) beyond.  The
    row→column handoff is a free row-major reshape — zero materialized
    transposes, which is what makes a planned ``fft2`` exactly rows+cols
    kernel calls.  ``chunks`` maps pass index → tuned grid-step width.
    """
    if interpret is None:
        interpret = should_interpret()
    fs = [q.n for q in passes if q.kind != "reorder" and q.axis == -1]
    for i, p in enumerate(passes):
        # Re-read per pass: a Bluestein row program changes the row width
        # mid-program (n → pad → n).
        b, rows, n = xr.shape
        chunk = chunks.get(i) if chunks else None
        with jax.named_scope(plan_lib.pass_scope(i, p)):
            if p.axis == -2:
                xr, xi = _cols_image_pass(
                    xr, xi, p, inverse, interpret, chunk=chunk,
                    degradations=degradations, index=i,
                )
                continue
            xr2, xi2 = _apply_pass(
                xr.reshape(b * rows, n), xi.reshape(b * rows, n),
                p, fs, inverse, interpret, batch_tiles, chunk=chunk,
                degradations=degradations, index=i,
            )
            w = xr2.shape[-1]
            xr, xi = xr2.reshape(b, rows, w), xi2.reshape(b, rows, w)
    return xr, xi


def _cols_plan_pass(fft_plan: plan_lib.FFTPlan, stride: int) -> plan_lib.Pass:
    """A synthetic strided-column pass running the whole plan's transform
    down the -2 axis of an (..., n, stride) view — the distributed pencil
    driver's local column transform, no materialized swapaxes."""
    leaf = fft_plan.passes[0]
    return plan_lib.Pass(
        kind=leaf.kind,
        n=fft_plan.n,
        n1=leaf.n1,
        n2=leaf.n2,
        view_in=(stride, stride, fft_plan.n),
        view_out=(stride, stride, fft_plan.n),
        order="natural",
    )


def execute_plan(
    xr: jax.Array,
    xi: jax.Array,
    fft_plan: plan_lib.FFTPlan,
    *,
    inverse: bool = False,
    interpret: bool | None = None,
    batch_tiles: Mapping[int, int] | None = None,
    order: str = "natural",
    axis: int = -1,
    chunks: Mapping[int, int] | None = None,
    degradations: list | None = None,
) -> Planes:
    """Execute a pre-computed :class:`~repro.core.plan.FFTPlan` with the
    Pallas kernels over ``axis`` (-1 or -2; any leading batch dims).

    ``batch_tiles`` (leaf length → tile) and ``chunks`` (pass index →
    grid-step width) let a :class:`PlannedFFT` carry its negotiated or
    tuned sizes; unlisted entries fall back to the VMEM-budget pick.
    ``order='pencil'`` leaves the spectrum in k₁-major pencil layout (the
    fft→pointwise→ifft fast path).  ``axis=-2`` transforms the second-to-last
    axis in place via the strided-column kernel when the plan is single-pass
    (the distributed pencil driver's case), falling back to a transpose
    sandwich otherwise.  A multi-axis plan (``fft_plan.n2`` set) consumes a
    3-D (..., n2, n) image and walks its joint program with
    :func:`execute_program2d`.
    """
    if interpret is None:
        interpret = should_interpret()
    if fft_plan.n2 is not None:
        if axis != -1:
            raise faults.PlanError(
                "multi-axis plans always transform the last two axes"
            )
        rows, n = xr.shape[-2:]
        if (rows, n) != (fft_plan.n2, fft_plan.n):
            raise faults.PlanError(
                f"plan is for ({fft_plan.n2}, {fft_plan.n}) images, got ({rows}, {n})"
            )
        lead = xr.shape[:-2]
        b = int(np.prod(lead)) if lead else 1
        yr, yi = execute_program2d(
            xr.reshape(b, rows, n),
            xi.reshape(b, rows, n),
            fft_plan.passes,
            inverse=inverse,
            interpret=interpret,
            batch_tiles=batch_tiles,
            chunks=chunks,
            degradations=degradations,
        )
        return yr.reshape(*lead, rows, n), yi.reshape(*lead, rows, n)
    if axis == -2:
        n, q = xr.shape[-2:]
        if n != fft_plan.n:
            raise faults.PlanError(f"plan is for n={fft_plan.n}, axis -2 has n={n}")
        lead = xr.shape[:-2]
        b = int(np.prod(lead)) if lead else 1
        if len(fft_plan.passes) == 1 and fft_plan.n > 1:
            p = _cols_plan_pass(fft_plan, q)
            with jax.named_scope(plan_lib.pass_scope(0, p)):
                yr, yi = _cols_image_pass(
                    xr.reshape(b, n, q), xi.reshape(b, n, q), p, inverse, interpret,
                    degradations=degradations,
                )
            return yr.reshape(*lead, n, q), yi.reshape(*lead, n, q)
        xr, xi = jnp.swapaxes(xr, -1, -2), jnp.swapaxes(xi, -1, -2)
        yr, yi = execute_plan(
            xr, xi, fft_plan, inverse=inverse, interpret=interpret,
            batch_tiles=batch_tiles, order=order, chunks=chunks,
            degradations=degradations,
        )
        return jnp.swapaxes(yr, -1, -2), jnp.swapaxes(yi, -1, -2)
    if axis != -1:
        raise faults.PlanError(f"execute_plan handles axis -1 or -2, got {axis}")
    n = xr.shape[-1]
    if n != fft_plan.n:
        raise faults.PlanError(f"plan is for n={fft_plan.n}, input has n={n}")
    passes = (
        fft_plan.passes
        if order == "natural"
        else plan_lib.compile_passes(fft_plan.n, order=order)
    )
    lead = xr.shape[:-1]
    b = int(np.prod(lead)) if lead else 1
    yr, yi = execute_program(
        xr.reshape(b, n),
        xi.reshape(b, n),
        passes,
        inverse=inverse,
        interpret=interpret,
        batch_tiles=batch_tiles,
        chunks=chunks,
        degradations=degradations,
    )
    # Inverse scaling is folded into each pass's transform LUT (1/f each);
    # the factors multiply so the total is exactly 1/n.
    return yr.reshape(*lead, n), yi.reshape(*lead, n)


def fft(
    xr: jax.Array,
    xi: jax.Array,
    *,
    inverse: bool = False,
    interpret: bool | None = None,
) -> Planes:
    """Plan-deriving convenience: plans ``n`` and calls :func:`execute_plan`.

    Non-power-of-two lengths route through the planner's Bluestein leaf.
    """
    n = xr.shape[-1]
    return execute_plan(
        xr, xi, plan_lib.plan_fft(n), inverse=inverse, interpret=interpret
    )


def ifft(xr, xi, *, interpret: bool | None = None) -> Planes:
    return fft(xr, xi, inverse=True, interpret=interpret)
