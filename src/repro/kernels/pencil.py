"""Strided-pencil Pallas kernels — the pass-program executors for the split
regime (N > FUSED_MAX).

The planner (``repro.core.plan.compile_passes``) linearizes a split-regime
transform into passes over *pencil views* of the flat buffer.  The two pass
shapes map onto two kernels, and all the glue the old recursion routed
through HBM (``swapaxes`` re-tilings, the inter-factor twiddle ``cmul``, the
natural-order transpose) happens inside their VMEM bodies:

``cols_pass_call``
    Transform along the **middle** axis of a ``(R, f, s)`` view — i.e. the
    strided columns of the ``(b, n1, n2)`` signal view, read and written in
    place through BlockSpecs that index ``(1, f, chunk)`` sub-blocks.  No
    materialized HBM ``swapaxes``: the (f, chunk) tile is transposed in VMEM,
    pushed through the shared tile engines (:func:`~repro.kernels.dft_matmul.
    dft_tile` for f ≤ 1024, :func:`~repro.kernels.fft4step.four_step_tile`
    beyond), transposed back, and multiplied by its chunk of the inter-factor
    twiddle grid (a host-cached LUT served chunk-by-chunk through its own
    BlockSpec — the paper's texture table, §2.3.1).

``rows_natural_call``
    Transform along the **last** axis of a ``(B, p, f)`` view and write each
    (chunk, f) result tile *transposed* into the ``(B, f, p)`` output view —
    the four-step natural-order transpose folded into the final pass's
    strided write (output BlockSpec ``(1, f, chunk)`` at column ``chunk``),
    costing zero standalone HBM transpose.

``rfft_recomb_call`` / ``irfft_recomb_call``
    The Hermitian even/odd recombination of the real-FFT packing as a single
    epilogue pass (one HBM round trip) instead of the ~10-op traced XLA glue.
    The spectrum is swept in 128-lane blocks; the Z[-k] reversal reads the
    two mirrored blocks through their index maps and reverses each inside
    one vector register (a lane gather), so no block is ever wider than a
    lane tile, whatever the half-spectrum width.

Every block's last two dims are multiples of the (8, 128) vector tile or
the array's own dims, which is what the TPU's Mosaic compiler accepts.
Grid dimensions are ``parallel`` everywhere (no cross-step carries).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fft_xla import cmul
from repro.core.limits import LANES, SUBLANES, VMEM_LIMIT
from repro.kernels.dft_matmul import dft_tile
from repro.kernels.fft4step import four_step_rows, four_step_tile

__all__ = [
    "cols_pass_call",
    "cols_natural_call",
    "rows_natural_call",
    "rfft_recomb_call",
    "irfft_recomb_call",
]


def _tile_transform(xr, xi, luts, kind: str, n1: int, n2: int, scratch=()):
    """Dispatch a (bt, f) VMEM tile to the shared direct/four-step engines.
    On the TPU the four-step runs row group by row group in place over the
    two ``scratch`` (bt, f) VMEM refs (see :func:`_scratch`); without
    scratch (Triton has none) it runs on the whole tile's values."""
    if kind == "direct":
        wr, wi = luts
        return dft_tile(xr, xi, wr, wi)
    if not scratch:
        return four_step_tile(xr, xi, *luts, n1, n2)
    s_r, s_i = scratch
    s_r[...] = xr
    s_i[...] = xi

    def write(rows, yr, yi):
        s_r[rows, :] = yr
        s_i[rows, :] = yi

    four_step_rows(s_r, s_i, luts, n1, n2, write)
    return s_r[...], s_i[...]


def _scratch(kind: str, rows: int, f: int) -> list:
    """The VMEM scratch a four-step tile of ``rows`` pencils needs."""
    if kind == "direct":
        return []
    return [pltpu.VMEM((rows, f), jnp.float32)] * 2


def _split_rest(rest, n_luts: int, kind: str, scratch: bool = True):
    """(luts, extra inputs, outputs, scratch) out of a kernel's ``rest``."""
    n_scr = 2 if scratch and kind != "direct" else 0
    body = rest[: len(rest) - n_scr]
    return (
        [r[...] for r in body[:n_luts]],
        body[n_luts:-2],
        body[-2:],
        rest[len(rest) - n_scr:],
    )


def _lut_specs(kind: str, f: int, n1: int, n2: int, index_map):
    if kind == "direct":
        return [pl.BlockSpec((f, f), index_map)] * 2
    return (
        [pl.BlockSpec((n1, n1), index_map)] * 2
        + [pl.BlockSpec((n1, n2), index_map)] * 2
        + [pl.BlockSpec((n2, n2), index_map)] * 2
    )


def _as_ops(luts):
    return [jnp.asarray(a) for a in luts]


def _params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT
    )


def _make_cols_kernel(
    kind: str, n1: int, n2: int, n_luts: int, tw: str | None,
    chunk: int = 0, tw_every: int = 0, tw_rows: int = 0,
):
    def kernel(x_r, x_i, *rest):
        luts, tws, (o_r, o_i), scratch = _split_rest(rest, n_luts, kind)
        if tw is not None:
            t_r, t_i = tws
        f, c = x_r.shape[1], x_r.shape[2]
        # (1, f, c) block → (c, f): the chunk's c pencils become tile rows.
        xr = x_r[...].reshape(f, c).swapaxes(0, 1)
        xi = x_i[...].reshape(f, c).swapaxes(0, 1)
        yr, yi = _tile_transform(xr, xi, luts, kind, n1, n2, scratch)
        if tw == "row":
            # Width-broadcast twiddle: the chunk's one phase row (1, f),
            # picked out of its (tw_rows, f) block, scales every pencil.
            r = (pl.program_id(1) * chunk // tw_every) % tw_rows
            yr, yi = cmul(
                yr, yi, t_r[pl.ds(r, 1), :], t_i[pl.ds(r, 1), :]
            )
        yr = yr.swapaxes(0, 1)  # back to (f, c): bin-major, pencil columns
        yi = yi.swapaxes(0, 1)
        if tw == "grid":
            # Inter-factor twiddle epilogue: bin k of pencil p ⊙ T[k, p].
            yr, yi = cmul(yr, yi, t_r[...], t_i[...])
        o_r[...] = yr.reshape(1, f, c)
        o_i[...] = yi.reshape(1, f, c)

    return kernel


def cols_pass_call(
    xr: jax.Array,
    xi: jax.Array,
    luts,
    twiddle=None,
    *,
    kind: str,
    n1: int = 0,
    n2: int = 0,
    chunk: int,
    interpret: bool = False,
    tw_every: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Strided-column transform pass: x (R, f, s), FFT of length f down the
    middle axis, written in place (same layout).  ``twiddle`` is the (f, s)
    inter-factor grid (split planes) applied as the VMEM epilogue.

    ``tw_every`` is the width-broadcast mode of the strip-mined column
    passes of a 2-D program: the last axis is (pencil-phase, image-width)
    flattened, ``s = s_tw · tw_every``, and every flat position inside one
    width run shares the phase.  ``twiddle`` is then the TRANSPOSED
    ``(s_tw, f)`` grid: the kernel is served the 8-row block holding its
    chunk's phase row (``chunk`` must divide ``tw_every``) and broadcasts
    that row across the chunk's image columns in VMEM instead of
    materialising the grid at image width in HBM."""
    r, f, s = xr.shape
    assert s % chunk == 0, (s, chunk)
    grid = (r, s // chunk)
    sig = pl.BlockSpec((1, f, chunk), lambda i, j: (i, 0, j))
    in_specs = [sig, sig] + _lut_specs(kind, f, n1, n2, lambda i, j: (0, 0))
    operands = [xr, xi] + _as_ops(luts)
    tw, tw_rows = None, 0
    if twiddle is not None and tw_every is not None:
        assert tw_every % chunk == 0, (tw_every, chunk)
        assert s % tw_every == 0, (s, tw_every)
        s_tw = s // tw_every
        tw = "row"
        tw_rows = SUBLANES if s_tw % SUBLANES == 0 else s_tw
        tw_spec = pl.BlockSpec(
            (tw_rows, f), lambda i, j: ((j * chunk // tw_every) // tw_rows, 0)
        )
        in_specs += [tw_spec, tw_spec]
        operands += _as_ops(twiddle)
    elif twiddle is not None:
        tw = "grid"
        tw_spec = pl.BlockSpec((f, chunk), lambda i, j: (0, j))
        in_specs += [tw_spec, tw_spec]
        operands += _as_ops(twiddle)
    out_shape = [
        jax.ShapeDtypeStruct((r, f, s), jnp.float32),
        jax.ShapeDtypeStruct((r, f, s), jnp.float32),
    ]
    fn = pl.pallas_call(
        _make_cols_kernel(
            kind, n1, n2, len(luts), tw, chunk, tw_every or 0, tw_rows
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[sig, sig],
        out_shape=out_shape,
        scratch_shapes=_scratch(kind, chunk, f),
        interpret=interpret,
        compiler_params=_params("parallel", "parallel"),
    )
    return tuple(fn(*operands))


def _make_rows_kernel(kind: str, n1: int, n2: int, n_luts: int, scratch=True):
    """The rows-natural kernel body; ``scratch=False`` for a lowering
    without scratch operands (the Triton one)."""

    def kernel(x_r, x_i, *rest):
        luts, _, (o_r, o_i), scr = _split_rest(rest, n_luts, kind, scratch)
        c, f = x_r.shape[1], x_r.shape[2]
        xr = x_r[...].reshape(c, f)
        xi = x_i[...].reshape(c, f)
        yr, yi = _tile_transform(xr, xi, luts, kind, n1, n2, scr)
        # Natural-order transpose fused into the write: (c, f) → (f, c).
        o_r[...] = yr.swapaxes(0, 1).reshape(1, f, c)
        o_i[...] = yi.swapaxes(0, 1).reshape(1, f, c)

    return kernel


def rows_natural_call(
    xr: jax.Array,
    xi: jax.Array,
    luts,
    *,
    kind: str,
    n1: int = 0,
    n2: int = 0,
    chunk: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Contiguous-row transform pass with the natural-order transpose fused
    into its strided write: x (B, p, f) → y (B, f, p), where
    y[b, k, q] = FFT_f(x[b, q, :])[k]."""
    b, p, f = xr.shape
    assert p % chunk == 0, (p, chunk)
    grid = (b, p // chunk)
    in_sig = pl.BlockSpec((1, chunk, f), lambda i, j: (i, j, 0))
    out_sig = pl.BlockSpec((1, f, chunk), lambda i, j: (i, 0, j))
    in_specs = [in_sig, in_sig] + _lut_specs(kind, f, n1, n2, lambda i, j: (0, 0))
    operands = [xr, xi] + _as_ops(luts)
    out_shape = [
        jax.ShapeDtypeStruct((b, f, p), jnp.float32),
        jax.ShapeDtypeStruct((b, f, p), jnp.float32),
    ]
    fn = pl.pallas_call(
        _make_rows_kernel(kind, n1, n2, len(luts)),
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_sig, out_sig],
        out_shape=out_shape,
        scratch_shapes=_scratch(kind, chunk, f),
        interpret=interpret,
        compiler_params=_params("parallel", "parallel"),
    )
    return tuple(fn(*operands))


def _make_cols_natural_kernel(kind: str, n1: int, n2: int, n_luts: int):
    def kernel(x_r, x_i, *rest):
        luts, _, (o_r, o_i), scratch = _split_rest(rest, n_luts, kind)
        f, c = x_r.shape[2], x_r.shape[3]
        # (1, 1, f, c) block → (c, f): the chunk's image columns become rows.
        xr = x_r[...].reshape(f, c).swapaxes(0, 1)
        xi = x_i[...].reshape(f, c).swapaxes(0, 1)
        yr, yi = _tile_transform(xr, xi, luts, kind, n1, n2, scratch)
        # The n2-axis digit transpose lives in the BlockSpec indexing (the
        # in/out p and k axes are swapped); the tile itself writes bin-major.
        o_r[...] = yr.swapaxes(0, 1).reshape(1, f, c)
        o_i[...] = yi.swapaxes(0, 1).reshape(1, f, c)

    return kernel


def cols_natural_call(
    xr: jax.Array,
    xi: jax.Array,
    luts,
    *,
    kind: str,
    n1: int = 0,
    n2: int = 0,
    chunk: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Final strip-mined column pass with the natural-order digit transpose
    fused into its strided write: x (B, P, f, w) → y (B, f, P, w), where
    ``y[b, k, p, :] = FFT_f(x[b, p, :, :], axis=0)[k]`` — i.e. the length-f
    transform runs down the n2-axis factor while the image width ``w`` rides
    along in chunks, and output n2-position ``k·P + p`` lands natural order
    with zero standalone HBM transpose (the 2-D analogue of
    :func:`rows_natural_call`).  The output is written through its
    ``(B, f, P·w)`` row-major view, whose ``(1, f, chunk)`` blocks keep the
    block's last two dims tile-aligned (a ``(…, 1, chunk)`` block of the 4-D
    view would not be)."""
    b, p, f, w = xr.shape
    assert w % chunk == 0, (w, chunk)
    per_row = w // chunk
    grid = (b, p, per_row)
    in_sig = pl.BlockSpec((1, 1, f, chunk), lambda i, q, j: (i, q, 0, j))
    out_sig = pl.BlockSpec(
        (1, f, chunk), lambda i, q, j: (i, 0, q * per_row + j)
    )
    in_specs = [in_sig, in_sig] + _lut_specs(
        kind, f, n1, n2, lambda i, q, j: (0, 0)
    )
    operands = [xr, xi] + _as_ops(luts)
    out_shape = [
        jax.ShapeDtypeStruct((b, f, p * w), jnp.float32),
        jax.ShapeDtypeStruct((b, f, p * w), jnp.float32),
    ]
    fn = pl.pallas_call(
        _make_cols_natural_kernel(kind, n1, n2, len(luts)),
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_sig, out_sig],
        out_shape=out_shape,
        scratch_shapes=_scratch(kind, chunk, f),
        interpret=interpret,
        compiler_params=_params("parallel", "parallel", "parallel"),
    )
    yr, yi = fn(*operands)
    return yr.reshape(b, f, p, w), yi.reshape(b, f, p, w)


# ---------------------------------------------------------------------------
# Hermitian recombination epilogue passes (rfft / irfft packing)
# ---------------------------------------------------------------------------

#: Spectrum rows per grid step of the recombination passes (a ragged last
#: row block is masked by Pallas, so the batch is never padded).
RECOMB_ROWS = 256


def _row_tile(b: int) -> int:
    return b if b <= RECOMB_ROWS else RECOMB_ROWS


def _rev_lanes(a):
    """Reverse a (rows, 128) block along its lanes: a gather inside one
    vector register, the only lane reversal Mosaic lowers.
    A one-row block is gathered as a full sublane tile (the lowering
    rejects the one-row gather)."""
    if a.shape[0] == 1:
        return _rev_lanes(jnp.broadcast_to(a, (SUBLANES, a.shape[1])))[:1]
    t = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.take_along_axis(
        a, a.shape[1] - 1 - t, axis=1, mode="promise_in_bounds"
    )


def _mirror(a, b, r: int):
    """The mirrored spectrum block M[t] = Z[(q−j)·128 + r − t] of output
    block j, where m = q·128 + r: block ``b`` (q−j) holds its first r+1
    lanes and block ``a`` (q−j−1) the rest, so M is the reversed pair read
    at a static lane offset."""
    c = a.shape[1]
    pair = jnp.concatenate([_rev_lanes(b), _rev_lanes(a)], axis=1)
    return pair[:, c - 1 - r: 2 * c - 1 - r]


def _fwd_math(zr, zi, fr, fi, wr, wi):
    """X[k] = E[k] + w[k]·O[k] from Z[k] and its mirror Z[(m−k) % m]."""
    er, ei = (zr + fr) * 0.5, (zi - fi) * 0.5
    or_, oi = (zi + fi) * 0.5, (fr - zr) * 0.5
    tr, ti = cmul(or_, oi, wr, wi)
    return er + tr, ei + ti


def _inv_math(xr, xi, fr, fi, wr, wi):
    """Z[k] = E[k] + i·w[k]·D[k] from X[k] and its mirror X[m−k]."""
    er, ei = (xr + fr) * 0.5, (xi - fi) * 0.5
    or_, oi = cmul((xr - fr) * 0.5, (xi + fi) * 0.5, wr, wi)
    return er - oi, ei + or_


def _recomb_tiled(fwd, zr, zi, wr, wi, m, interpret):
    """Lane-tiled recombination for m ≥ 128.  Grid (row blocks, 128-lane
    output blocks j); step j reads its own block and the two input blocks
    its mirror spans (see :func:`_mirror`), clamped into range where the
    lanes they would supply fall outside the output.  The forward pass
    writes the (B, m+1) bins: the two wrap-arounds of the mod-m indexing —
    the mirror of bin 0 and the Nyquist bin's Z[m] — both read Z[0]."""
    b, w_in = zr.shape
    c = LANES
    q, r = divmod(m, c)
    last = -(-w_in // c) - 1  # last input block
    bt = _row_tile(b)
    w_out = m + 1 if fwd else m
    here = lambda i, j: (i, jnp.minimum(j, last))  # noqa: E731
    lo = lambda i, j: (i, jnp.maximum(q - j - 1, 0))  # noqa: E731
    hi = lambda i, j: (i, jnp.minimum(q - j, last))  # noqa: E731
    blk = lambda imap: pl.BlockSpec((bt, c), imap)  # noqa: E731
    w_spec = pl.BlockSpec((1, c), lambda i, j: (0, j))

    def kernel(z_r, z_i, a_r, a_i, b_r, b_i, w_r, w_i, o_r, o_i):
        zr_, zi_ = z_r[...], z_i[...]
        fr = _mirror(a_r[...], b_r[...], r)
        fi = _mirror(a_i[...], b_i[...], r)
        if fwd:
            k = pl.program_id(1) * c + jax.lax.broadcasted_iota(
                jnp.int32, zr_.shape, 1
            )
            fr = jnp.where(k == 0, zr_[:, 0:1], fr)
            fi = jnp.where(k == 0, zi_[:, 0:1], fi)
            zr_ = jnp.where(k == m, b_r[:, 0:1], zr_)
            zi_ = jnp.where(k == m, b_i[:, 0:1], zi_)
            yr, yi = _fwd_math(zr_, zi_, fr, fi, w_r[...], w_i[...])
        else:
            yr, yi = _inv_math(zr_, zi_, fr, fi, w_r[...], w_i[...])
        o_r[...] = yr
        o_i[...] = yi

    fn = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(b, bt), pl.cdiv(w_out, c)),
        in_specs=[blk(here)] * 2 + [blk(lo)] * 2 + [blk(hi)] * 2 + [w_spec] * 2,
        out_specs=[blk(lambda i, j: (i, j))] * 2,
        out_shape=[jax.ShapeDtypeStruct((b, w_out), jnp.float32)] * 2,
        interpret=interpret,
        compiler_params=_params("parallel", "parallel"),
    )
    return tuple(fn(zr, zi, zr, zi, zr, zi, wr, wi))


def _recomb_small(fwd, zr, zi, wr, wi, m, interpret):
    """Whole-row recombination for m < 128 (rows narrower than one lane
    tile, which a 128-lane block cannot sweep): the index maps k → k mod m and k → (m − k) mod m over the output
    bins are two tiny 0/1 matrices, so the gather and the Nyquist bin are
    exact-precision matmuls and the row needs no lane shuffle."""
    b, w_in = zr.shape
    w_out = m + 1 if fwd else m
    k = np.arange(w_out)
    rows = np.arange(w_in)[:, None]
    here = (rows == k % m).astype(np.float32)
    mirror = (rows == (m - k) % m if fwd else rows == m - k).astype(np.float32)
    bt = _row_tile(b)
    sig_in = pl.BlockSpec((bt, w_in), lambda i: (i, 0))
    sig_out = pl.BlockSpec((bt, w_out), lambda i: (i, 0))
    mat = pl.BlockSpec((w_in, w_out), lambda i: (0, 0))
    w_spec = pl.BlockSpec((1, w_out), lambda i: (0, 0))
    math = _fwd_math if fwd else _inv_math
    dot = lambda x, p: jnp.dot(  # noqa: E731
        x, p, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    def kernel(z_r, z_i, h_ref, f_ref, w_r, w_i, o_r, o_i):
        h, f = h_ref[...], f_ref[...]
        zr_, zi_ = z_r[...], z_i[...]
        yr, yi = math(
            dot(zr_, h), dot(zi_, h), dot(zr_, f), dot(zi_, f),
            w_r[...], w_i[...],
        )
        o_r[...] = yr
        o_i[...] = yi

    fn = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(b, bt),),
        in_specs=[sig_in, sig_in, mat, mat, w_spec, w_spec],
        out_specs=[sig_out, sig_out],
        out_shape=[jax.ShapeDtypeStruct((b, w_out), jnp.float32)] * 2,
        interpret=interpret,
        compiler_params=_params("parallel"),
    )
    return tuple(
        fn(zr, zi, jnp.asarray(here), jnp.asarray(mirror), wr[:, :w_out],
           wi[:, :w_out])
    )


def _recomb_call(fwd, zr, zi, wr, wi, m, interpret):
    wr = jnp.asarray(wr, jnp.float32).reshape(1, -1)
    wi = jnp.asarray(wi, jnp.float32).reshape(1, -1)
    call = _recomb_tiled if m >= LANES else _recomb_small
    return call(fwd, zr, zi, wr, wi, m, interpret)


def rfft_recomb_call(zr, zi, wr, wi, *, interpret: bool = False):
    """Forward recombination pass: packed spectrum (B, m) → bins (B, m+1).

    One ``pallas_call`` executing the arithmetic of
    :func:`repro.core.fft_xla.rfft_recomb` (``wr/wi``: the m+1 phasors
    e^{−2πik/n}) — the whole Hermitian epilogue costs one HBM round trip.
    """
    m = zr.shape[-1]
    return _recomb_call(True, zr, zi, wr, wi, m, interpret)


def irfft_recomb_call(xr, xi, wr, wi, *, interpret: bool = False):
    """Inverse recombination pass: bins (B, m+1) → packed spectrum (B, m)."""
    m = xr.shape[-1] - 1
    return _recomb_call(False, xr, xi, wr, wi, m, interpret)
