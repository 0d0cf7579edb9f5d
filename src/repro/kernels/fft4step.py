"""Fused four-step FFT Pallas kernel — one HBM round trip for N ≤ 65536.

The paper's central optimisation (§2.3.2): rather than one kernel per
butterfly level (log₂N global round trips), divide the signal so that *all*
levels execute in on-chip memory, touching the slow tier once.  Fermi shared
memory → TPU VMEM; butterfly warps → MXU matmuls:

    view x as (n1, n2) row-major
    A = W1 · X                   column DFTs      (MXU GEMM 1)
    B = A ⊙ T                    twiddle          (VPU, fused)
    C = B · W2                   row DFTs         (MXU GEMM 2)
    Y = Cᵀ flattened             natural order    (VMEM-internal relayout)

The signal tile, both DFT matrices, the twiddle grid, the intermediate and
the output tile are co-resident in VMEM; the LUT operands are pinned to block
(0, 0) for every grid step so Mosaic hoists their copy out of the batch loop
(texture-memory analogue).  The batch grid dimension is ``parallel``.

The whole VMEM dataflow lives in the row-group functions, which
:func:`four_step_rows` sweeps over a tile held in refs (and
:func:`four_step_tile` maps over a tile of values), so the pass-program
kernels (``repro.kernels.pencil``) embed the same four-step engine inside
their strided-column and transposed-write passes — the row group is the
unit of fusion.  The sweep goes a row group at a time, which keeps what
Mosaic unrolls (and its compile time) at one group, however large the
batch tile.  On top of the selectable output layout (``natural_order``),
``fft4step_call`` accepts a post-GEMM per-bin twiddle (``twiddle_after``)
applied in the epilogue before the write, so a multiplicative phase stage
(modulation, delay, inter-level twiddle of a follow-on factor) costs zero
extra HBM passes.

Both GEMMs are plain 2-D contractions with 128-aligned operand shapes for
n1, n2 ≥ 128 (N ≥ 16384), one signal at a time.  Below that the split keeps
n2 = 128 lanes and n1 ≥ 8 sublanes, and the natural-order Cᵀ of one signal
would merge n1-lane rows.  So a *lane group* of p = n2/n1 signals runs the
factors the other way round (:func:`_lane_group`): the n2-point DFT first,
on an operand built from the signals' (n1, n2) views by lane rotations, and
the n1-point DFT last, in the MXU's transposed-operand form, whose result
rows are each signal's natural-order output in whole 128-lane rows.  The
GEMMs run at ``Precision.HIGHEST`` (float32 on the
MXU) — the FFT's 1e-3 accuracy contract needs more than one bf16 pass.
Inverse transforms use conjugated LUTs with 1/N folded into W2 — the
scaled table *is* the LUT, no extra pass (paper §2.3.1 spirit).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fft_xla import cmul
from repro.core.limits import VMEM_LIMIT, row_group
from repro.core.plan import kernel_name

__all__ = [
    "fft4step_call", "four_step_rows", "four_step_tile", "cgemm_tile", "lane_group_luts",
]


def cgemm_tile(ar, ai, br, bi, rhs_transposed: bool = False):
    """Karatsuba complex GEMM on split planes: 3 real MXU GEMMs.
    ``rhs_transposed`` contracts both operands' last dims (A·Bᵀ), the
    MXU's transposed-operand form."""
    if rhs_transposed:
        dot = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    else:
        dot = functools.partial(
            jnp.dot, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    k1 = dot(ar + ai, br)
    k2 = dot(ar, bi - br)
    k3 = dot(ai, br + bi)
    return k1 - k3, k1 + k2


def lane_group_luts(w1r, w1i, tr, ti, w2r, w2i):
    """The natural-order LUTs of a lane group (``n1 < LANES``) from the
    plan's (W1, T, W2), all (n2, n2): the ``n2``-point DFT becomes GEMM-1's,
    its columns in the (lane block h, row r) order of
    :func:`_lane_block_transpose`'s rows (column h·n1 + r is input index
    p·r + h); the twiddle grid, transposed, repeats across the p signals'
    lane blocks; and GEMM-2's is ``I_p ⊗ W1``, the ``n1``-point DFT once
    per signal.  Host arrays; the inverse's 1/N stays in the ``n2``
    matrix, where the plan folded it."""
    n1, n2 = tr.shape
    p = n2 // n1
    cols = (np.arange(p)[:, None] + p * np.arange(n1)[None, :]).ravel()
    eye = np.eye(p, dtype=np.float32)
    return (
        w2r[:, cols], w2i[:, cols],
        np.tile(tr.T, (1, p)), np.tile(ti.T, (1, p)),
        np.kron(eye, w1r), np.kron(eye, w1i),
    )


def _one_signal(xr, xi, w1r, w1i, tr, ti, w2r, w2i, n1: int, n2: int, natural_order: bool):
    """One (1, n) signal: the (n1, n2) view is a plain 2-D matrix."""
    n = n1 * n2
    ar, ai = cgemm_tile(w1r, w1i, xr.reshape(n1, n2), xi.reshape(n1, n2))
    br = ar * tr - ai * ti
    bi = ar * ti + ai * tr
    cr, ci = cgemm_tile(br, bi, w2r, w2i)
    if natural_order:
        return cr.T.reshape(1, n), ci.T.reshape(1, n)
    return cr.reshape(1, n), ci.reshape(1, n)


def _lane_block_transpose(vs, n1: int, n2: int):
    """GEMM-1's operand for signals ``vs`` (each its (n1, n2) view): per
    p = n2 // n1 signals an (n2, n2) block whose (row block h, lane block
    b) holds lane block h of signal b, the blocks side by side.  Lane
    rotations and selects inside whole 128-lane rows — no lane split or
    merge — and no arithmetic across signals."""
    p = n2 // n1
    blk = jax.lax.broadcasted_iota(jnp.int32, (n1, n2), 1) // n1
    cols = []
    for g in range(0, len(vs), p):
        grp = vs[g:g + p]
        rows = []
        for h in range(p):
            acc = grp[h]
            for b in range(p):
                if b != h:
                    rolled = pltpu.roll(grp[b], (b - h) % p * n1, 1)
                    acc = jnp.where(blk == b, rolled, acc)
            rows.append(acc)
        cols.append(jnp.concatenate(rows, axis=0))
    return jnp.concatenate(cols, axis=1)


def _lane_group(vr, vi, luts, n1: int, n2: int):
    """Natural-order transforms of the signals ``vr``/``vi`` (lists of
    (n1, n2) views, a multiple of p = n2 // n1 of them), with the LUTs of
    :func:`lane_group_luts`.  Write j = n1·(p·r + h) + j2 and k = k1 + n2·k2:

        A[k1, (b, j2)] = Σ_(h, r) W1'[k1, (h, r)] · X[(h, r), (b, j2)]   GEMM-1
        B = A ⊙ T'                                                    twiddle
        Y[(b, k2), k1] = Σ_(b', j2) (I ⊗ W_n1)[(b, k2), (b', j2)] · B[k1, (b', j2)]
                                                        GEMM-2, A·Bᵀ form

    so rows b·n1 … (b+1)·n1 of each group's result are signal b's
    natural-order (n1, n2) rows.  Every GEMM is 128 × 128 or wider: one
    weight load of B serves the group's 128 result rows.  The price of the
    block-diagonal GEMM-2 is that a NaN or Inf in one signal reaches the
    other signals of its p-group through the zero weights; one GEMM per
    signal on its lane slice would keep them apart, at 1.4–1.8× this
    kernel's time for p ≥ 4 (v5e).  Returns the list of (yr, yi)."""
    w1r, w1i, tr, ti, w2r, w2i = luts
    p = n2 // n1
    ar, ai = cgemm_tile(
        w1r, w1i, _lane_block_transpose(vr, n1, n2), _lane_block_transpose(vi, n1, n2)
    )
    brs, bis = [], []
    for g in range(len(vr) // p):
        cols = slice(g * n2, (g + 1) * n2)
        brs.append(ar[:, cols] * tr - ai[:, cols] * ti)
        bis.append(ar[:, cols] * ti + ai[:, cols] * tr)
    cr, ci = cgemm_tile(
        w2r, w2i, jnp.concatenate(brs), jnp.concatenate(bis), rhs_transposed=True
    )
    return [
        (cr[b * n1:(b + 1) * n1, g * n2:(g + 1) * n2],
         ci[b * n1:(b + 1) * n1, g * n2:(g + 1) * n2])
        for g in range(len(vr) // p)
        for b in range(p)
    ]


def _group_size(n1: int, n2: int, natural_order: bool) -> int:
    """Signals per row-group step; a lane group's rows leave in natural
    order, so pencil order runs one signal at a time."""
    return row_group(n1, n2) if natural_order else 1


def four_step_tile(
    xr, xi, w1r, w1i, tr, ti, w2r, w2i, n1: int, n2: int, natural_order: bool = True
):
    """The four-step dataflow on a (bt, n1·n2) tile of values.

    Pure jnp, traced directly by the XLA fallback
    (``ops._row_transform_xla``).  Maps the row group
    of :func:`four_step_rows` over the tile's rows, zero-padding them to a
    whole number of groups.  Returns (yr, yi) of shape (bt, n1·n2), in
    natural or pencil (k1-major) order; the LUTs are the leaf's, as
    ``ops._fused_luts`` builds them for that order."""
    luts = (w1r, w1i, tr, ti, w2r, w2i)
    bt = xr.shape[0]
    n = n1 * n2
    g = _group_size(n1, n2, natural_order)
    pad = -bt % g
    if pad:
        zeros = jnp.zeros((pad, n), jnp.float32)
        xr, xi = jnp.concatenate([xr, zeros]), jnp.concatenate([xi, zeros])

    def step(planes):
        gr, gi = planes
        if g == 1:
            return _one_signal(gr, gi, *luts, n1, n2, natural_order)
        ys = _lane_group(
            [gr[k].reshape(n1, n2) for k in range(g)],
            [gi[k].reshape(n1, n2) for k in range(g)],
            luts, n1, n2,
        )
        return tuple(jnp.stack([y[i].reshape(n) for y in ys]) for i in (0, 1))

    yr, yi = jax.lax.map(step, (xr.reshape(-1, g, n), xi.reshape(-1, g, n)))
    return yr.reshape(-1, n)[:bt], yi.reshape(-1, n)[:bt]


def four_step_rows(x_r, x_i, luts, n1: int, n2: int, write, natural_order=True):
    """Run the four-step over every row of the (bt, n) refs ``x_r``/``x_i``,
    a row group per ``fori_loop`` step, and hand each row's result to
    ``write(rows, yr, yi)`` (``rows`` a one-row slice).

    The loop keeps the unrolled kernel body at one group's size, whatever
    the tile (see :func:`~repro.core.limits.row_group`).  A group is one
    signal once ``n1`` fills a 128-row MXU operand, or in pencil order;
    else it is a lane group (:func:`_lane_group`) of signals read through
    their (n1, n2) views.  A tile of fewer rows than a group is
    zero-padded to one; a ragged tail is computed as the tile's last full
    group, of which only the tail rows are written — so ``write`` may
    target the input refs themselves (an in-place sweep): rows are
    independent, and no row is read after it was written.  ``luts`` =
    (w1r, w1i, tr, ti, w2r, w2i), loaded values."""
    bt, n = x_r.shape
    g = _group_size(n1, n2, natural_order)

    def row(start, k):
        return pl.ds(start + k if k else start, 1)

    def group(start, count=g):
        if g == 1:
            rows = row(start, 0)
            return [_one_signal(x_r[rows, :], x_i[rows, :], *luts, n1, n2, natural_order)]
        zero = jnp.zeros((n1, n2), jnp.float32)

        def views(ref):
            return [
                ref[row(start, k), :].reshape(n1, n2) if k < count else zero
                for k in range(g)
            ]

        ys = _lane_group(views(x_r), views(x_i), luts, n1, n2)
        return [(yr.reshape(1, n), yi.reshape(1, n)) for yr, yi in ys]

    if bt < g:
        for k, y in enumerate(group(0, bt)[:bt]):
            write(row(0, k), *y)
        return

    def body(s, carry):
        start = pl.multiple_of(s * g, g)
        for k, y in enumerate(group(start)):
            write(row(start, k), *y)
        return carry

    jax.lax.fori_loop(0, bt // g, body, 0)
    tail = bt % g
    if tail:
        ys = group(bt - g)
        for k in range(g - tail, g):
            write(row(bt - g, k), *ys[k])


def _make_kernel(n1: int, n2: int, natural_order: bool, has_epilogue: bool):
    def kernel(x_r, x_i, w1_r, w1_i, t_r, t_i, w2_r, w2_i, *rest):
        if has_epilogue:
            e_r, e_i, o_r, o_i = rest
        else:
            o_r, o_i = rest
        luts = [r[...] for r in (w1_r, w1_i, t_r, t_i, w2_r, w2_i)]

        def write(rows, yr, yi):
            if has_epilogue:
                # Post-GEMM per-position twiddle: y[b, j] *= e[j].
                yr, yi = cmul(yr, yi, e_r[...], e_i[...])
            o_r[rows, :] = yr
            o_i[rows, :] = yi

        four_step_rows(x_r, x_i, luts, n1, n2, write, natural_order)

    return kernel


def fft4step_call(
    xr: jax.Array,
    xi: jax.Array,
    w1r: jax.Array,
    w1i: jax.Array,
    twr: jax.Array,
    twi: jax.Array,
    w2r: jax.Array,
    w2i: jax.Array,
    *,
    batch_tile: int,
    natural_order: bool = True,
    twiddle_after: tuple[jax.Array, jax.Array] | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused four-step FFT: x (B, n1·n2) split-complex; B % batch_tile == 0.
    The LUTs are the leaf's as ``ops._fused_luts`` builds them for the
    order asked (for a natural-order lane group, :func:`lane_group_luts`);
    the twiddle grid's last dim is ``n2``.

    ``twiddle_after`` — optional (real, imag) per-output-position phasors of
    shape (n,): multiplied into the result in the VMEM epilogue (after the
    ``natural_order`` relayout), so phase post-processing rides the same
    HBM round trip.  The pass program's *inter-factor* twiddle goes through
    ``kernels.pencil``'s column kernel instead (it is per-pencil-phase, not
    per-position); this call-level hook is the public surface for per-bin
    phase stages — modulation, delay, fftshift-by-phase-ramp.
    """
    b, n = xr.shape
    n2 = twr.shape[1]
    n1 = n // n2
    assert n == n1 * n2, (n, n1, n2)
    assert b % batch_tile == 0, (b, batch_tile)
    grid = (b // batch_tile,)
    sig = pl.BlockSpec((batch_tile, n), lambda i: (i, 0))
    luts = [w1r, w1i, twr, twi, w2r, w2i]
    in_specs = [sig, sig] + [pl.BlockSpec(a.shape, lambda i: (0, 0)) for a in luts]
    operands = [xr, xi] + luts
    if twiddle_after is not None:
        er, ei = twiddle_after
        er = jnp.asarray(er, jnp.float32).reshape(1, n)
        ei = jnp.asarray(ei, jnp.float32).reshape(1, n)
        lute = pl.BlockSpec((1, n), lambda i: (0, 0))
        in_specs += [lute, lute]
        operands += [er, ei]
    out_shape = [
        jax.ShapeDtypeStruct((b, n), jnp.float32),
        jax.ShapeDtypeStruct((b, n), jnp.float32),
    ]
    fn = pl.pallas_call(
        _make_kernel(n1, n2, natural_order, twiddle_after is not None),
        name=kernel_name("fft4step"),
        grid=grid,
        in_specs=in_specs,
        out_specs=[sig, sig],
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT
        ),
    )
    return tuple(fn(*operands))
